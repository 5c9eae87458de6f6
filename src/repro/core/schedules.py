"""Noise schedules and timestep grids for diffusion SDE/ODE sampling.

Conventions (paper §3):
    forward:  x_t | x_0 ~ N(alpha_t x_0, sigma_t^2 I)
    log-SNR:  lambda_t = log(alpha_t / sigma_t)      (strictly decreasing in t)
    EDM sigma: sigma^EDM_t = sigma_t / alpha_t = exp(-lambda_t)

Sampling runs in *reverse* time: the step grid ``t_0 = T > t_1 > ... > t_M``
so ``lambda`` strictly increases along the solve.

All schedule math is exposed both as float64 host (numpy) functions — used by
the coefficient engine, where the h^s cancellations demand f64 — and as jnp
functions for in-graph use (model conditioning, baselines).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "NoiseSchedule",
    "VPLinearSchedule",
    "VPCosineSchedule",
    "VESchedule",
    "EDMSchedule",
    "RectifiedFlowSchedule",
    "timestep_grid",
    "get_schedule",
]


class NoiseSchedule:
    """Base class. Subclasses implement log_alpha(t) / log_sigma(t) (numpy,
    float64, vectorized) and the inverse lambda -> t."""

    # ---- numpy (host, float64) ------------------------------------------
    def log_alpha(self, t):  # pragma: no cover - abstract
        raise NotImplementedError

    def log_sigma(self, t):  # pragma: no cover - abstract
        raise NotImplementedError

    def alpha(self, t):
        return np.exp(self.log_alpha(t))

    def sigma(self, t):
        return np.exp(self.log_sigma(t))

    def lam(self, t):
        return self.log_alpha(t) - self.log_sigma(t)

    def edm_sigma(self, t):
        """sigma_t / alpha_t = exp(-lambda_t)."""
        return np.exp(-self.lam(t))

    def t_of_lam(self, lam):  # pragma: no cover - abstract
        raise NotImplementedError

    def t_of_edm_sigma(self, s):
        s = np.asarray(s, dtype=np.float64)
        return self.t_of_lam(-np.log(s))

    # ---- jnp (device) -----------------------------------------------------
    def log_alpha_j(self, t):  # pragma: no cover - abstract
        raise NotImplementedError

    def log_sigma_j(self, t):  # pragma: no cover - abstract
        raise NotImplementedError

    def alpha_j(self, t):
        return jnp.exp(self.log_alpha_j(t))

    def sigma_j(self, t):
        return jnp.exp(self.log_sigma_j(t))

    def lam_j(self, t):
        return self.log_alpha_j(t) - self.log_sigma_j(t)

    def d_alpha_j(self, t):
        """d alpha_t / dt, elementwise (forward-mode derivative)."""
        t = jnp.asarray(t, jnp.float32)
        return jax.jvp(self.alpha_j, (t,), (jnp.ones_like(t),))[1]

    def d_sigma_j(self, t):
        """d sigma_t / dt, elementwise (forward-mode derivative)."""
        t = jnp.asarray(t, jnp.float32)
        return jax.jvp(self.sigma_j, (t,), (jnp.ones_like(t),))[1]

    # ---- defaults ----------------------------------------------------------
    #: default integration span [t_end, t_start]
    t_start: float = 1.0
    t_end: float = 1e-3

    def validate_span(self, t_start: float, t_end: float) -> None:
        """Reject a requested solve span the schedule cannot represent.

        Default: every span is fine. Schedules with a hard usable
        boundary (the cosine schedule's saturation clip) override this to
        raise a targeted error instead of letting grid construction fail
        later with a confusing strictly-decreasing violation."""

    def prior_scale(self, t) -> float:
        """Std of the terminal prior x_T ~ N(0, prior_scale^2 I).

        VP schedules terminate at the unit Gaussian; variance-exploding
        schedules override this (VESchedule returns sigma(t))."""
        return 1.0


@dataclasses.dataclass(frozen=True)
class VPLinearSchedule(NoiseSchedule):
    """DDPM linear-beta VP schedule (continuous form, Song et al. 2021).

    log alpha_t = -t^2 (beta_1 - beta_0)/4 - t beta_0 / 2,   t in [0, 1]
    sigma_t = sqrt(1 - alpha_t^2)
    """

    beta_0: float = 0.1
    beta_1: float = 20.0
    t_start: float = 1.0
    t_end: float = 1e-3

    def log_alpha(self, t):
        t = np.asarray(t, dtype=np.float64)
        return -(t * t) * (self.beta_1 - self.beta_0) / 4.0 - t * self.beta_0 / 2.0

    def log_sigma(self, t):
        la = self.log_alpha(t)
        # log sqrt(1 - e^{2 la}) computed stably
        return 0.5 * np.log(-np.expm1(2.0 * la))

    def t_of_lam(self, lam):
        lam = np.asarray(lam, dtype=np.float64)
        # alpha^2 = sigmoid(2 lam)  =>  log alpha = -0.5 log(1 + e^{-2 lam})
        log_alpha = -0.5 * np.log1p(np.exp(-2.0 * lam))
        # solve (b1-b0)/4 t^2 + b0/2 t + log_alpha = 0 for t >= 0
        A = (self.beta_1 - self.beta_0) / 4.0
        B = self.beta_0 / 2.0
        L = -log_alpha  # >= 0
        return (-B + np.sqrt(B * B + 4.0 * A * L)) / (2.0 * A)

    def log_alpha_j(self, t):
        return -(t * t) * (self.beta_1 - self.beta_0) / 4.0 - t * self.beta_0 / 2.0

    def log_sigma_j(self, t):
        la = self.log_alpha_j(t)
        return 0.5 * jnp.log(-jnp.expm1(2.0 * la))


@dataclasses.dataclass(frozen=True)
class VPCosineSchedule(NoiseSchedule):
    """iDDPM cosine schedule (Nichol & Dhariwal), continuous form.

    alpha_t = cos(pi/2 * (t + s)/(1 + s)) / cos(pi/2 * s/(1 + s)),
    clipped so that log alpha stays finite near t=1.
    """

    s: float = 0.008
    t_start: float = 0.9946  # standard clip used by DPM-Solver for cosine
    t_end: float = 1e-3

    def validate_span(self, t_start: float, t_end: float) -> None:
        if t_start > self.t_start + 1e-12:
            raise ValueError(
                f"t_start={t_start:g} is beyond the cosine schedule's usable "
                f"span: log(alpha) saturates above t={self.t_start:g} (the "
                f"1e-12 clip), lambda is not invertible there, and a grid "
                f"over that region would collapse to duplicate timesteps. "
                f"Request t_start <= {self.t_start:g}, or construct "
                f"VPCosineSchedule(t_start=...) with a larger clip "
                f"boundary explicitly.")

    def _log_alpha_raw(self, t):
        t = np.asarray(t, dtype=np.float64)
        f = np.cos(np.pi / 2.0 * (t + self.s) / (1.0 + self.s))
        f0 = math.cos(math.pi / 2.0 * self.s / (1.0 + self.s))
        return np.log(np.clip(f / f0, 1e-12, None))

    def log_alpha(self, t):
        return self._log_alpha_raw(t)

    def log_sigma(self, t):
        la = self.log_alpha(t)
        return 0.5 * np.log(-np.expm1(2.0 * np.minimum(la, -1e-12)))

    def t_of_lam(self, lam):
        lam = np.asarray(lam, dtype=np.float64)
        log_alpha = -0.5 * np.log1p(np.exp(-2.0 * lam))
        f0 = math.cos(math.pi / 2.0 * self.s / (1.0 + self.s))
        arg = np.clip(np.exp(log_alpha) * f0, -1.0, 1.0)
        t = (2.0 * (1.0 + self.s) / np.pi) * np.arccos(arg) - self.s
        # Clip the upper end to the schedule's own t_start, NOT 1.0:
        # log_alpha saturates (the 1e-12 clip) as t -> 1, so the inversion
        # quantizes there — a [0, 1] clip let near-duplicate t's through
        # and timestep_grid(kind="logsnr"|"karras") could emit repeated
        # endpoints at high step counts and die on its strictly-decreasing
        # check. t_start = 0.9946 is the standard operating boundary
        # (the DPM-Solver cosine clip); beyond it the schedule is out of
        # contract anyway. The LOWER bound stays the formula's domain
        # edge 0.0, not t_end: the inversion is well-conditioned all the
        # way down, and pinning it at t_end would quantize (or kill)
        # custom-span grids that solve below the default 1e-3.
        return np.clip(t, 0.0, self.t_start)

    def log_alpha_j(self, t):
        f = jnp.cos(jnp.pi / 2.0 * (t + self.s) / (1.0 + self.s))
        f0 = math.cos(math.pi / 2.0 * self.s / (1.0 + self.s))
        return jnp.log(jnp.clip(f / f0, 1e-12, None))

    def log_sigma_j(self, t):
        la = self.log_alpha_j(t)
        return 0.5 * jnp.log(-jnp.expm1(2.0 * jnp.minimum(la, -1e-12)))


@dataclasses.dataclass(frozen=True)
class VESchedule(NoiseSchedule):
    """Variance-exploding / EDM-style schedule: alpha = 1, sigma_t = t.

    Time *is* the EDM sigma. Used for the EDM baseline-VE CIFAR10 model in
    the paper's §6.2/§6.4 experiments.
    """

    sigma_min: float = 0.02
    sigma_max: float = 80.0

    @property
    def t_start(self):  # type: ignore[override]
        return self.sigma_max

    @property
    def t_end(self):  # type: ignore[override]
        return self.sigma_min

    def log_alpha(self, t):
        return np.zeros_like(np.asarray(t, dtype=np.float64))

    def log_sigma(self, t):
        return np.log(np.asarray(t, dtype=np.float64))

    def t_of_lam(self, lam):
        return np.exp(-np.asarray(lam, dtype=np.float64))

    def log_alpha_j(self, t):
        return jnp.zeros_like(t)

    def log_sigma_j(self, t):
        return jnp.log(t)

    def prior_scale(self, t) -> float:
        return float(self.sigma(t))


# EDM is the VE schedule plus Karras preconditioning at the model boundary;
# for solver purposes they are identical.
EDMSchedule = VESchedule


@dataclasses.dataclass(frozen=True)
class RectifiedFlowSchedule(NoiseSchedule):
    """Rectified flow (Esser et al., arXiv:2403.03206): the straight path
    ``x_t = (1 - t) x_0 + t eps``, so alpha_t = 1 - t, sigma_t = t and
    lambda_t = log((1 - t) / t), t in (0, 1).

    lambda is infinite at both ends, so a span must lie strictly inside
    (0, 1). SD3's timestep ``shift`` re-spaces its Euler sampler's grid
    and is no property of the schedule: a log-SNR grid replaces it.
    """

    t_start: float = 0.999
    t_end: float = 1e-3

    def validate_span(self, t_start: float, t_end: float) -> None:
        if not 0.0 < t_end < t_start < 1.0:
            raise ValueError(
                f"rectified flow needs 0 < t_end < t_start < 1, got "
                f"t_start={t_start:g}, t_end={t_end:g}: log-SNR is "
                f"infinite at t = 0 and t = 1")

    def log_alpha(self, t):
        return np.log1p(-np.asarray(t, dtype=np.float64))

    def log_sigma(self, t):
        return np.log(np.asarray(t, dtype=np.float64))

    def t_of_lam(self, lam):
        return 1.0 / (1.0 + np.exp(np.asarray(lam, dtype=np.float64)))

    def alpha(self, t):
        return 1.0 - np.asarray(t, dtype=np.float64)

    def sigma(self, t):
        return np.asarray(t, dtype=np.float64)

    def log_alpha_j(self, t):
        return jnp.log1p(-t)

    def log_sigma_j(self, t):
        return jnp.log(t)

    def alpha_j(self, t):
        return 1.0 - t

    def sigma_j(self, t):
        return t


def timestep_grid(
    schedule: NoiseSchedule,
    n_steps: int,
    *,
    kind: str = "logsnr",
    t_start: float | None = None,
    t_end: float | None = None,
    rho: float = 7.0,
) -> np.ndarray:
    """Return ``t_0 > t_1 > ... > t_M`` (M = n_steps), float64.

    kind:
      "time"     uniform in t
      "logsnr"   uniform in lambda (log-SNR)           [paper's LDM setting]
      "karras"   uniform in sigma_EDM^{1/rho}          [paper's EDM setting]
    """
    t0 = float(schedule.t_start if t_start is None else t_start)
    t1 = float(schedule.t_end if t_end is None else t_end)
    if not t0 > t1:
        raise ValueError(f"need t_start > t_end, got {t0} <= {t1}")
    if n_steps < 1:
        raise ValueError("n_steps must be >= 1")
    schedule.validate_span(t0, t1)
    if kind == "time":
        ts = np.linspace(t0, t1, n_steps + 1, dtype=np.float64)
    elif kind == "logsnr":
        l0, l1 = float(schedule.lam(t0)), float(schedule.lam(t1))
        lams = np.linspace(l0, l1, n_steps + 1, dtype=np.float64)
        ts = schedule.t_of_lam(lams)
        ts[0], ts[-1] = t0, t1  # kill inverse round-off at the ends
    elif kind == "karras":
        s0, s1 = float(schedule.edm_sigma(t0)), float(schedule.edm_sigma(t1))
        grid = np.linspace(s0 ** (1.0 / rho), s1 ** (1.0 / rho), n_steps + 1)
        ts = schedule.t_of_edm_sigma(grid ** rho)
        ts[0], ts[-1] = t0, t1
    else:
        raise ValueError(f"unknown grid kind: {kind!r}")
    if not np.all(np.diff(ts) < 0):
        raise ValueError("timestep grid must be strictly decreasing")
    return ts


_REGISTRY: dict[str, Callable[[], NoiseSchedule]] = {
    "vp_linear": VPLinearSchedule,
    "vp_cosine": VPCosineSchedule,
    "ve": VESchedule,
    "edm": VESchedule,
    "rectified_flow": RectifiedFlowSchedule,
}


def get_schedule(name: str, **kwargs) -> NoiseSchedule:
    try:
        return _REGISTRY[name](**kwargs)
    except KeyError:
        raise ValueError(f"unknown schedule {name!r}; have {sorted(_REGISTRY)}")
