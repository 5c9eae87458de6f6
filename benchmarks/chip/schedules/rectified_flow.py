"""Rectified flow (Esser et al., arXiv:2403.03206), as SD3 trains it:
``x_t = (1 - t) x_0 + t eps``, so ``alpha_t = 1 - t``, ``sigma_t = t`` and
``lam_t = log((1 - t) / t)``, in float64. The ``schedule`` block gives
only ``t_start`` and ``t_end``, strictly inside (0, 1), where lam is
finite. SD3's timestep ``shift`` re-spaces its Euler sampler's grid; the
SA-Solver grid here is uniform in lam, so there is no shift.
"""

from __future__ import annotations

import numpy as np


def lam(t, block: dict):
    t = np.asarray(t, np.float64)
    return np.log1p(-t) - np.log(t)


def t_of_lam(lam, block: dict):
    return 1.0 / (1.0 + np.exp(np.asarray(lam, np.float64)))


def alpha(t, block: dict):
    return 1.0 - np.asarray(t, np.float64)


def sigma(t, block: dict):
    return np.asarray(t, np.float64)
