"""Denoiser adapter layer: raw network -> solver-facing model contract.

Every executor in the sampler registry consumes ``model_fn(x, t)`` whose
output is the *plan's* parameterization (x0-prediction for the baselines
and the "data" SA-Solver path, eps-prediction for the "noise" SA path).
Real checkpoints come in three output conventions — eps-, x0- and
v-prediction — and are usually served under classifier-free guidance with
per-request conditioning. :class:`Denoiser` closes that gap:

- **prediction-type conversion** — ``convert_prediction`` maps any of
  ``eps``/``x0``/``v``/``flow`` to any other in-graph using the
  schedule's ``alpha_t``/``sigma_t`` (and their time derivatives, for
  ``flow``) at the (traced) evaluation time, via the identities of
  ``x_t = alpha_t x_0 + sigma_t eps``, ``v = alpha_t eps - sigma_t x_0``
  and the flow velocity ``u = dx_t/dt = alpha_t' x_0 + sigma_t' eps``
  (rectified flow: ``u = eps - x_0``).
- **classifier-free guidance** — the cond and uncond branches are fused
  into ONE batched network evaluation (a stacked leading axis of 2, vmap
  over the network), then combined as ``(1 - s) * uncond + s * cond``.
  That form — not ``uncond + s (cond - uncond)`` — makes guidance scale
  1.0 *bitwise* equal to the conditional branch, so the guided executor
  at s = 1 reproduces the unguided path exactly. The scale is traced
  data: a guidance-scale sweep reuses one compilation.
- **conditioning pytree** — ``cond`` is threaded alongside ``x`` as a
  traced argument of the jitted executor (never baked as a constant), so
  per-request conditioning rides the serving compile cache; only its
  shape/dtype structure keys the executor.

A :class:`Denoiser` is passed wherever ``model_fn`` is accepted
(``sample`` / ``sample_batched`` / ``sample_sharded`` / ``ServeEngine``);
the base layer binds it to the plan's parameterization and the per-call
``cond``/``guidance_scale`` at trace time (see
``repro.core.samplers.base``).

NFE accounting: one *guided* evaluation costs two *network* evaluations
under CFG (one fused call over a doubled lane count).
``SamplerSpec.nfe`` counts guided (solver-level) evaluations;
``SamplerSpec.network_nfe`` counts network forwards.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from .schedules import NoiseSchedule

__all__ = [
    "PREDICTION_TYPES",
    "CachedNetwork",
    "Denoiser",
    "canonical_prediction",
    "convert_prediction",
]

#: canonical prediction-type names (aliases: "data"/"x0", "noise"/"eps")
PREDICTION_TYPES = ("x0", "eps", "v", "flow")

_ALIASES = {
    "data": "x0", "x0": "x0",
    "noise": "eps", "eps": "eps", "epsilon": "eps",
    "v": "v", "v_prediction": "v",
    "flow": "flow",
}


def canonical_prediction(name: str) -> str:
    """Normalize a prediction-type name ("data"/"x0", "noise"/"eps", "v")."""
    try:
        return _ALIASES[name]
    except KeyError:
        raise ValueError(
            f"unknown prediction type {name!r}; one of "
            f"{sorted(set(_ALIASES))}")


def convert_prediction(pred: jnp.ndarray, x: jnp.ndarray, t,
                       src: str, dst: str,
                       schedule: NoiseSchedule) -> jnp.ndarray:
    """Convert a network output between prediction types, in-graph.

    Uses ``x_t = a x_0 + s eps`` and ``v = a eps - s x_0`` with
    ``a = alpha_t``, ``s = sigma_t`` from the schedule's jnp functions at
    the traced evaluation time ``t``. The v inversions use the general
    ``1/(a^2 + s^2)`` normalizer so non-VP schedules stay exact.
    """
    src, dst = canonical_prediction(src), canonical_prediction(dst)
    if src == dst:
        return pred
    if "flow" in (src, dst):
        return _convert_flow(pred, x, t, src, dst, schedule)
    a = schedule.alpha_j(t)
    s = schedule.sigma_j(t)
    if dst == "x0":
        if src == "eps":
            return (x - s * pred) / a
        return (a * x - s * pred) / (a * a + s * s)      # src == "v"
    if dst == "eps":
        if src == "x0":
            return (x - a * pred) / s
        return (s * x + a * pred) / (a * a + s * s)      # src == "v"
    # dst == "v"
    if src == "x0":
        return a * (x - a * pred) / s - s * pred
    return a * pred - s * (x - s * pred) / a             # src == "eps"


def _convert_flow(pred, x, t, src, dst, schedule):
    """Conversions to and from the flow velocity ``u = a' x_0 + s' eps``:
    with ``x = a x_0 + s eps``, ``x_0 = (s' x - s u) / D`` and ``eps =
    (a u - a' x) / D`` for ``D = a s' - s a'`` (rectified flow: D = 1,
    ``x_0 = x - t u``)."""
    a, s = schedule.alpha_j(t), schedule.sigma_j(t)
    da, ds = schedule.d_alpha_j(t), schedule.d_sigma_j(t)
    if src == "flow":
        x0 = (ds * x - s * pred) / (a * ds - s * da)
        return convert_prediction(x0, x, t, "x0", dst, schedule)
    x0 = convert_prediction(pred, x, t, src, "x0", schedule)
    eps = convert_prediction(pred, x, t, src, "eps", schedule)
    return da * x0 + ds * eps


@dataclasses.dataclass(frozen=True, eq=False)
class CachedNetwork:
    """Feature-cached companion of a :class:`Denoiser`'s network
    (DeepCache-style step-to-step activation reuse).

    Args:
        call: ``(x, t, cond, feats, refresh) -> (prediction, new_feats)``.
            On ``refresh`` the deep feature segment is recomputed and
            returned; otherwise the cached ``feats`` stand in and pass
            through unchanged. Predictions follow the owning Denoiser's
            ``prediction`` convention. ``refresh`` may be a Python bool
            (graph-specializing) or a traced scalar bool.
        init: ``(x) -> feats`` — a zero feature pytree for one *network*
            input ``x`` (pre-CFG-doubling; the Denoiser stacks a leading
            [2] axis under guidance).
    """

    call: Callable
    init: Callable


@dataclasses.dataclass(frozen=True, eq=False)
class Denoiser:
    """A raw network wrapped into the solver-facing model contract.

    Args:
        network: ``(x, t, cond) -> prediction`` in ``prediction``'s
            convention. Unconditional networks ignore ``cond`` (callers
            pass ``cond=None``).
        schedule: the noise schedule whose ``alpha_t``/``sigma_t`` drive
            the in-graph prediction conversion. Must match the plan's.
        prediction: the network's output convention — ``"eps"``/``"x0"``/
            ``"v"``/``"flow"`` (aliases ``"noise"``/``"data"`` accepted).
        guidance: enable classifier-free guidance. The executor traces a
            doubled-lane fused network evaluation and combines branches
            with the per-call (traced) ``guidance_scale``.
        null_cond: the unconditional conditioning for CFG. ``None`` means
            "zeros like the per-call cond" (the common null-embedding
            convention when the null token is the zero vector).
        params: the network's weights as a pytree of arrays, or ``None``
            when ``network`` closes over its own. When set, ``network``
            is called as ``network(params, x, t, cond)`` (and a cached
            companion as ``cached.call(params, ...)``), and the executors
            pass ``params`` to the compiled program as an argument. A
            closed-over weight array is instead embedded in every
            compiled executable as a constant: at published widths that
            is gigabytes per executable, in the compile and on the
            device.

    Identity semantics: ``eq=False`` keeps the dataclass hashable by
    object identity, and instances are weak-referenceable — the sampler
    compile cache keys executors on a *weak* identity token of the
    Denoiser exactly as it does for plain ``model_fn`` callables, so the
    cache never pins the network (or the params its closure holds).
    """

    network: Callable[[jnp.ndarray, Any, Any], jnp.ndarray]
    schedule: NoiseSchedule
    prediction: str = "eps"
    guidance: bool = False
    null_cond: Any = None
    #: optional feature-cached companion network; required when a sampler
    #: spec sets ``feature_cache`` (see CachedNetwork)
    cached: CachedNetwork | None = None
    params: Any = None

    def __post_init__(self):
        object.__setattr__(
            self, "prediction", canonical_prediction(self.prediction))

    # ------------------------------------------------------------- statics
    def statics(self, target: str) -> tuple:
        """Trace-relevant identity for the compile-cache key: everything
        that changes the adapter's graph except the network itself (which
        is keyed separately, by weak identity)."""
        return ("denoiser", self.prediction, bool(self.guidance),
                canonical_prediction(target), self.schedule)

    # ------------------------------------------------------------ binding
    def bind(self, params) -> "Denoiser":
        """This denoiser with ``params`` (traced, inside an executor)
        folded into its networks; ``self`` when it carries no params."""
        if self.params is None:
            return self
        cached = self.cached
        if cached is not None:
            cached = CachedNetwork(functools.partial(cached.call, params),
                                   cached.init)
        return dataclasses.replace(
            self, network=functools.partial(self.network, params),
            cached=cached, params=None)

    def _cfg_pair(self, x, cond, cfg_sharding):
        """Stack the cond/uncond lanes ([2] leading axis). When
        ``cfg_sharding`` names a mesh axis, constrain that axis onto it —
        XLA then places the two branches on disjoint device halves
        (sharded CFG) instead of doubling the per-device batch."""
        null = self.null_cond
        if null is None and cond is not None:
            null = jax.tree.map(jnp.zeros_like, cond)
        pair = jax.tree.map(lambda c, n: jnp.stack([c, n]), cond, null)
        xx = jnp.stack([x, x])
        if cfg_sharding is not None:
            constrain = lambda a: jax.lax.with_sharding_constraint(
                a, cfg_sharding)
            xx = constrain(xx)
            pair = jax.tree.map(constrain, pair)
        return xx, pair

    @staticmethod
    def _combine(c_out, u_out, scale):
        s = jnp.asarray(scale, c_out.dtype)
        # (1-s)*u + s*c: at s == 1.0 this is bitwise the cond branch
        # (0*u + c), unlike u + s*(c-u) whose re-association rounds
        return (1.0 - s) * u_out + s * c_out

    def evaluate(self, x: jnp.ndarray, t, cond, scale,
                 cfg_sharding=None) -> jnp.ndarray:
        """One guided (or plain) network evaluation, in ``self.prediction``
        convention. Under guidance the cond/uncond branches run as ONE
        network call over a stacked leading axis of 2.

        The network runs under ``jax.named_scope("backbone")`` so its ops
        carry a ``backbone`` op-name path in the lowered HLO —
        ``repro.launch.hlo_cost`` reads that metadata to attribute HBM
        bytes to the backbone region vs the solver-update region."""
        if not self.guidance:
            with jax.named_scope("backbone"):
                return self.network(x, t, cond)
        xx, pair = self._cfg_pair(x, cond, cfg_sharding)
        with jax.named_scope("backbone"):
            out = jax.vmap(self.network, in_axes=(0, None, 0))(xx, t, pair)
        return self._combine(out[0], out[1], scale)

    def init_feats(self, x):
        """Zero feature cache for one solver state ``x`` (the guided pair
        gets a stacked leading [2] axis, matching ``evaluate``'s lanes)."""
        assert self.cached is not None, "Denoiser built without cached="
        f = self.cached.init(x)
        if self.guidance:
            f = jax.tree.map(lambda a: jnp.stack([a, a]), f)
        return f

    def evaluate_cached(self, x, t, cond, scale, feats, refresh,
                        cfg_sharding=None):
        """``evaluate`` through the feature-cached network. Returns
        ``(prediction, new_feats)``."""
        assert self.cached is not None, "Denoiser built without cached="
        if not self.guidance:
            with jax.named_scope("backbone"):
                return self.cached.call(x, t, cond, feats, refresh)
        xx, pair = self._cfg_pair(x, cond, cfg_sharding)
        fn = lambda xi, ci, fi: self.cached.call(xi, t, ci, fi, refresh)
        with jax.named_scope("backbone"):
            out, new_feats = jax.vmap(fn)(xx, pair, feats)
        return self._combine(out[0], out[1], scale), new_feats

    def as_model_fn(self, target: str, cond, scale,
                    cfg_sharding=None) -> Callable:
        """Bind this denoiser to a plan's parameterization and one call's
        (traced) conditioning + guidance scale, yielding the
        ``model_fn(x, t)`` closure the executors consume."""
        target = canonical_prediction(target)

        def model_fn(x, t):
            raw = self.evaluate(x, t, cond, scale, cfg_sharding)
            return convert_prediction(raw, x, t, self.prediction, target,
                                      self.schedule)

        return model_fn

    def as_cached_model_fn(self, target: str, cond, scale,
                           cfg_sharding=None) -> Callable:
        """Feature-cached twin of :meth:`as_model_fn`:
        ``model_fn(x, t, feats, refresh) -> (prediction, new_feats)``."""
        target = canonical_prediction(target)

        def model_fn(x, t, feats, refresh):
            raw, new_feats = self.evaluate_cached(
                x, t, cond, scale, feats, refresh, cfg_sharding)
            pred = convert_prediction(raw, x, t, self.prediction, target,
                                      self.schedule)
            return pred, new_feats

        return model_fn

    def __repr__(self) -> str:
        return (f"Denoiser(prediction={self.prediction!r}, "
                f"guidance={self.guidance}, schedule={self.schedule!r})")
