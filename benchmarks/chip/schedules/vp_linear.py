"""The VP-linear schedule (DDPM's linear betas in continuous time, Song et
al. 2021) in float64, from a configuration file's ``schedule`` block
(``beta_0``, ``beta_1``):

    log alpha_t = -t^2 (beta_1 - beta_0) / 4 - t beta_0 / 2,
    sigma_t = sqrt(1 - alpha_t^2),   lam_t = log(alpha_t / sigma_t).

A schedule module gives ``lam(t, block)``, its inverse ``t_of_lam(lam,
block)``, ``alpha(t, block)`` and ``sigma(t, block)``; ``reference.py``
builds the SA-Solver tables from these four alone.
"""

from __future__ import annotations

import numpy as np


def lam(t, block: dict):
    b0, b1 = block["beta_0"], block["beta_1"]
    t = np.asarray(t, np.float64)
    log_alpha = -(t * t) * (b1 - b0) / 4.0 - t * b0 / 2.0
    return log_alpha - 0.5 * np.log(-np.expm1(2.0 * log_alpha))


def t_of_lam(lam, block: dict):
    b0, b1 = block["beta_0"], block["beta_1"]
    lam = np.asarray(lam, np.float64)
    log_alpha = -0.5 * np.log1p(np.exp(-2.0 * lam))
    a, b = (b1 - b0) / 4.0, b0 / 2.0
    return (-b + np.sqrt(b * b - 4.0 * a * log_alpha)) / (2.0 * a)


def alpha(t, block: dict):
    return np.sqrt(1.0 / (1.0 + np.exp(-2.0 * lam(t, block))))


def sigma(t, block: dict):
    return np.sqrt(1.0 / (1.0 + np.exp(2.0 * lam(t, block))))
