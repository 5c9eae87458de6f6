"""Step-granular sampler execution: the scan step as the scheduling unit.

The whole-solve executors (``execute_sa`` and the baseline scans) fuse
all M solver steps into one ``lax.scan`` — the fastest shape when a
microbatch runs start-to-finish, and the serving engine keeps it as the
non-interleaved fast path. Continuous batching needs the opposite
factoring: ONE compiled **step function** whose carry is an explicit
pytree the engine owns, so requests can join a running batch at any step
boundary, freed lanes can be recycled mid-flight, and per-lane progress
(every lane at its own step index) lives in the carry instead of the
loop structure.

The carry (leading axis = batch lanes, one slice per lane):

- ``inner``   — the family's own state (SA: ``{x, buf}`` with the ring
  history; DDIM: ``{x}``; DPM-Solver++(2M): ``{x, x0}``; EDM: ``{x}``
  in the scaled space),
- ``i``       — per-lane step index (int32). SA starts at ``-1``: the
  warm-up model evaluation (``e0``) runs *in-band* as the lane's first
  tick, so a mid-flight join is pure data writes and every tick spends
  a fixed number of batched model evaluations,
- ``keys``    — the lane's per-step PRNG keys, ``split(solve_key, M)``
  precomputed at join time. Identical to what the whole-solve executor
  derives internally, and carried per lane, so **lane migration cannot
  change a request's noise stream** — the keys move with the lane,
- ``active``  — the lane mask: free/finished lanes still compute (the
  compiled shape is fixed) but every carry write is masked,
- ``x_final`` — the finished sample, captured the tick a lane completes,
- ``err``     — the predictor-vs-corrector residual (free in PEC/PECE:
  both combines are computed anyway), driving masked early exit,
- ``tol`` / ``min_i`` — per-lane early-exit tolerance (≤ 0 disables; the
  disabled path is bitwise-identical to the whole-solve executor) and
  minimum completed steps before an exit is allowed,
- ``guard`` — per-lane numerical-guard interval (int32; 0 disables).
  Every ``guard`` steps (and on the lane's finishing tick) the lane's
  family state and would-be final sample are checked for non-finite
  values; a tripped lane is deactivated WITHOUT capturing ``x_final``
  and flagged in ``aux["failed"]`` so the scheduler can free it and
  surface ``status="failed_numerics"`` instead of returning garbage.
  The interval is carry *data* — toggling the guard or sweeping its
  interval never recompiles, and with ``guard == 0`` every masked
  write degenerates to the unguarded bytes,
- ``scale`` (+ optional ``cond``) — per-lane guidance scale and
  conditioning, bound into the model exactly as the whole-solve path
  binds them.

Three compiled entry points per step key, all fixed-shape so a
join/leave churn sweep compiles NOTHING after warmup:

- ``step(arrays, carry) -> (carry, aux)`` — one solver step for every
  lane (vmapped per lane; plan arrays broadcast). ``aux`` carries the
  per-tick ``finished``/``stepped`` flags, per-lane step indices, the
  residuals, and (stream mode) the per-step denoised ``x0`` previews.
- ``join(arrays, carry, lane, x_T, keys, tol, min_i, scale[, guard]
  [, cond])`` — masked carry write admitting one request into one lane
  (scalar traced lane index: any lane, one compilation).
- ``copy(dst_carry, src_carry, dst_lane, src_lane)`` — lane migration:
  moves one lane's entire carry slice (state, history, step index, RNG
  keys) between same-shaped batches, so merging half-empty batches is
  bitwise-invisible to the migrated request.

The compile cache here is keyed by the **step function**, not the serve
bucket: ``(family, stepwise statics, step count, table widths, latent
shape/dtype, lane count, model token, cond structure, stream)``. Specs
that differ only in tau / per-interval program orders / coefficient
values share one entry — their differences are plan *data* — so a serve
bucket is strictly finer than its step function and warmup survives any
bucket churn. ``stepwise_cache_stats()`` mirrors the whole-solve cache's
contract (``benchmarks/bench_continuous.py`` asserts zero misses across
a join/leave churn sweep).
"""

from __future__ import annotations

import dataclasses
import weakref
from collections import OrderedDict
from typing import Callable, Hashable

import jax
import jax.numpy as jnp

import numpy as np

from .base import (SamplerPlan, _adapter_statics, _bind_model,
                   _check_model, _deref_model, _model_params, _model_token,
                   _weak, carry_dtype, cond_struct, get_family)

__all__ = [
    "StepAdapter",
    "StepFns",
    "stepwise_adapter",
    "stepwise_supported",
    "make_stepfns",
    "fresh_carry",
    "stepwise_cache_stats",
    "clear_stepwise_cache",
]


# ------------------------------------------------------------------ protocol
@dataclasses.dataclass(frozen=True)
class StepAdapter:
    """A family's per-lane step view, built by ``family.stepwise(spec)``.

    ``step(dev, model_fn, inner, ic, init, key)`` advances one lane one
    solver step and returns ``(inner', final, x0, err)``: the family
    state, the would-be final sample if the lane stopped after this
    tick, the denoised preview, and the step's error residual (``inf``
    when the family has no free residual — early exit then never
    fires). ``ic`` is the clamped step index and ``init`` the in-band
    warm-up predicate (constant False for families with ``i0 == 0``).
    All members are pure; the trace-relevant identity lives in
    ``statics`` (part of the step-function cache key).
    """

    statics: tuple
    #: first per-lane index; -1 = the family needs an in-band init tick
    i0: int
    #: model evals spent per tick per lane (static: the compiled shape)
    evals_per_tick: int
    #: dev arrays -> M (shape-static step count)
    n_steps_of: Callable[[dict], int]
    #: (dev, x_T) -> per-lane inner pytree (pure data transform, no eval)
    init_inner: Callable
    #: (dev, model_fn, inner, ic, init, key) -> (inner', final, x0, err)
    step: Callable
    #: plan -> the device arrays this adapter's step consumes (families
    #: may extend/fold ``plan.arrays``, e.g. SA's per-step PECE flags)
    arrays: Callable[[SamplerPlan], dict]
    #: plan -> extra aval-relevant hashables for the cache key (table
    #: widths, optional-array presence) — anything that changes the
    #: traced argument avals without changing the statics
    shape_key: Callable[[SamplerPlan], tuple] = lambda plan: ()


def stepwise_supported(spec) -> bool:
    return getattr(get_family(spec.name), "stepwise", None) is not None


def stepwise_adapter(spec) -> StepAdapter:
    family = get_family(spec.name)
    build = getattr(family, "stepwise", None)
    if build is None:
        raise ValueError(
            f"sampler family {spec.name!r} has no step-granular adapter; "
            "step-scheduled (continuous-batching) serving needs one — "
            "register the family with a `stepwise=` builder or serve it "
            "through the whole-solve scheduler")
    adapter = build(spec)
    if not isinstance(adapter, StepAdapter):
        raise TypeError(
            f"{spec.name}.stepwise must return a StepAdapter, got "
            f"{type(adapter).__name__}")
    return adapter


# -------------------------------------------------------------- build carry
def fresh_carry(plan: SamplerPlan, batch: int, shape, dtype,
                *, cond=None, model_fn=None,
                guard_every: int = 0) -> dict:
    """An all-lanes-free carry for one running batch.

    ``cond`` is a per-request conditioning prototype (arrays or
    ShapeDtypeStructs — only shapes/dtypes matter); lanes are zeroed and
    inactive until ``join`` writes them. When the spec enables feature
    caching the carry grows a per-lane ``feats`` pytree whose avals come
    from the model's ``init_feats`` (pass the Denoiser as ``model_fn``).
    ``guard_every`` seeds every lane's numerical-guard interval (data —
    ``join`` overwrites it per request; 0 disables the guard).
    """
    adapter = stepwise_adapter(plan.spec)
    arrays = adapter.arrays(plan)
    cdt = carry_dtype(plan.spec.precision)
    M = adapter.n_steps_of(arrays)
    proto = jax.random.PRNGKey(0)
    inner_s = jax.eval_shape(
        lambda x: adapter.init_inner(arrays, x),
        jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype)))
    carry = {
        "inner": jax.tree.map(
            lambda s: jnp.zeros((batch,) + tuple(s.shape), s.dtype),
            inner_s),
        "i": jnp.full((batch,), adapter.i0, jnp.int32),
        "keys": jnp.zeros((batch, M) + proto.shape, proto.dtype),
        "active": jnp.zeros((batch,), bool),
        "x_final": jnp.zeros((batch,) + tuple(shape), cdt),
        "err": jnp.full((batch,), jnp.inf, jnp.float32),
        "tol": jnp.zeros((batch,), jnp.float32),
        "min_i": jnp.zeros((batch,), jnp.int32),
        "scale": jnp.ones((batch,), jnp.float32),
        "guard": jnp.full((batch,), int(guard_every), jnp.int32),
    }
    if cond is not None:
        carry["cond"] = jax.tree.map(
            lambda c: jnp.zeros((batch,) + tuple(c.shape),
                                jnp.dtype(c.dtype)), cond)
    if plan.spec.feature_cache is not None:
        if model_fn is None or not hasattr(model_fn, "init_feats"):
            raise ValueError(
                "spec.feature_cache needs the feats avals: pass the "
                "Denoiser (built with cached=) as fresh_carry(..., "
                "model_fn=)")
        feats_s = jax.eval_shape(
            model_fn.init_feats,
            jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype)))
        carry["feats"] = jax.tree.map(
            lambda s: jnp.zeros((batch,) + tuple(s.shape), s.dtype),
            feats_s)
    return carry


# ------------------------------------------------------------ compile cache
_STEP_CACHE: OrderedDict = OrderedDict()
_STEP_CACHE_MAX = 64
_STEP_STATS = {"hits": 0, "misses": 0, "evictions": 0, "aot_fallbacks": 0}
_STEP_TOKEN_IDX = 7  # position of the model token inside a step key


def stepwise_cache_stats() -> dict:
    return dict(_STEP_STATS, size=len(_STEP_CACHE))


def clear_stepwise_cache() -> None:
    _STEP_CACHE.clear()
    for k in _STEP_STATS:
        _STEP_STATS[k] = 0


def _token_matches(token, ref) -> bool:
    if token is ref:  # WeakMethod
        return True
    return getattr(token, "ref", None) is ref


def _on_model_death(ref) -> None:
    for key in [k for k in _STEP_CACHE
                if _token_matches(k[_STEP_TOKEN_IDX], ref)]:
        if _STEP_CACHE.pop(key, None) is not None:
            _STEP_STATS["evictions"] += 1


class StepFns:
    """One compiled step function and its lane-admission/migration
    companions. ``warm(arrays, carry, cond=...)`` AOT-compiles all three
    (``jit(...).lower(...).compile()``) so the serving hot path —
    including every later join, leave, and migration — never traces."""

    __slots__ = ("adapter", "cell", "key", "shape", "dtype", "has_cond",
                 "_step", "_join", "_copy", "_aot_step", "_aot_join",
                 "_aot_copy")

    def __init__(self, adapter, cell, key, shape, dtype, has_cond,
                 step, join, copy):
        self.adapter = adapter
        self.cell = cell
        self.key = key
        self.shape = tuple(shape)
        self.dtype = jnp.dtype(dtype)
        self.has_cond = has_cond
        self._step, self._join, self._copy = step, join, copy
        self._aot_step = self._aot_join = self._aot_copy = None

    @staticmethod
    def _call(aot, fn, *args):
        if aot is not None:
            try:
                return aot(*args)
            except TypeError:
                # aval drift vs the warmed shapes: jit fallback, counted
                # so the degradation is observable
                _STEP_STATS["aot_fallbacks"] += 1
        return fn(*args)

    def step(self, arrays, carry):
        params = _model_params(_deref_model(self.cell))
        return self._call(self._aot_step, self._step, arrays, carry, params)

    def join(self, arrays, carry, lane, x_T, keys, tol, min_i, scale,
             guard=0, cond=None):
        # numpy scalars, not jnp: each jnp scalar is its own device_put
        # dispatch, and joins sit on the serving hot path
        args = [arrays, carry, np.int32(lane), x_T, keys,
                np.float32(tol), np.int32(min_i), np.float32(scale),
                np.int32(guard)]
        if self.has_cond:
            args.append(cond)
        return self._call(self._aot_join, self._join, *args)

    def copy(self, dst_carry, src_carry, dst_lane, src_lane):
        return self._call(self._aot_copy, self._copy, dst_carry, src_carry,
                          np.int32(dst_lane), np.int32(src_lane))

    @property
    def warmed(self) -> bool:
        return self._aot_step is not None

    def warm(self, arrays, carry, *, cond=None) -> None:
        """AOT-compile step/join/copy against this batch's avals.

        ``cond`` is the per-request conditioning prototype (no lane
        axis) — required when the carry has one. Idempotent.
        """
        if self.warmed:
            return
        aval = lambda t: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(tuple(a.shape),
                                           jnp.dtype(a.dtype)), t)
        arrays_s, carry_s = aval(arrays), aval(carry)
        params_s = aval(_model_params(_deref_model(self.cell)))
        self._aot_step = self._step.lower(arrays_s, carry_s,
                                          params_s).compile()
        proto = jax.random.PRNGKey(0)
        M = carry["keys"].shape[1]
        i_s = jax.ShapeDtypeStruct((), jnp.int32)
        f_s = jax.ShapeDtypeStruct((), jnp.float32)
        x_s = jax.ShapeDtypeStruct(self.shape, self.dtype)
        k_s = jax.ShapeDtypeStruct((M,) + proto.shape, proto.dtype)
        join_args = [arrays_s, carry_s, i_s, x_s, k_s, f_s, i_s, f_s, i_s]
        if self.has_cond:
            if cond is None:
                raise ValueError(
                    "this step function was built with conditioning; "
                    "warm(..., cond=per_request_prototype) is required")
            join_args.append(aval(cond))
        self._aot_join = self._join.lower(*join_args).compile()
        self._aot_copy = self._copy.lower(carry_s, carry_s, i_s,
                                          i_s).compile()


def _make_run_step(adapter, dadapter, cell, has_cond: bool, stream: bool,
                   has_fc: bool = False):
    def run_step(arrays, carry, params):
        m = _deref_model(cell)
        M = adapter.n_steps_of(arrays)

        def lane(inner, i, keys, active, x_final, err_prev, tol, min_i,
                 scale, guard, cond, feats):
            model = _bind_model(m, dadapter, cond, scale, params=params)
            init = i < 0
            ic = jnp.clip(i, 0, M - 1)
            if has_fc:
                # wrap the bound model at trace time: the tick's FIRST
                # model call carries the refresh predicate (plan schedule
                # OR residual trigger; init ticks always refresh), any
                # later call this tick (the PECE re-eval) reuses the
                # fresh features. The box threads feats through the
                # adapter's unchanged (x, t) model contract.
                refresh0 = (init | arrays["fc_refresh"][ic]
                            | (jnp.isfinite(err_prev)
                               & (err_prev >= arrays["fc_thresh"])))
                box = {"feats": feats, "first": True}
                cached_call = model.cached_call

                def step_model(x_in, t_in):
                    r = refresh0 if box["first"] else False
                    box["first"] = False
                    e, box["feats"] = cached_call(x_in, t_in,
                                                  box["feats"], r)
                    return e
            else:
                box = {"feats": feats}
                step_model = model
            inner2, final, x0, err = adapter.step(arrays, step_model,
                                                  inner, ic, init, keys[ic])
            i_new = jnp.where(init, 0, ic + 1)
            err = jnp.where(init, jnp.inf, err)
            # masked early exit: the residual must fall strictly below
            # the lane's tolerance (tol <= 0 can never fire — err >= 0)
            # and the lane must have completed min_i steps. Reaching
            # i_new == M is the whole-solve endpoint.
            fin = active & ((i_new >= M)
                            | ((err < tol) & (i_new >= min_i)))
            # per-lane numerical guard: every `guard` steps (and on the
            # finishing tick) reduce the family state + would-be final
            # sample to one finiteness bit. The interval is carry DATA —
            # guard == 0 makes `bad` constant-False, so every masked
            # write below selects the unguarded bytes and toggling the
            # guard never recompiles.
            due = (guard > 0) & (((i_new % jnp.maximum(guard, 1)) == 0)
                                 | fin)
            finite = jnp.bool_(True)
            for leaf in jax.tree.leaves(inner2) + [final]:
                finite &= jnp.all(
                    jnp.isfinite(leaf.astype(jnp.float32)))
            bad = active & due & ~finite
            fin = fin & ~bad
            keep = lambda n, o: jnp.where(active, n, o)
            new = {
                "inner": jax.tree.map(keep, inner2, inner),
                "i": jnp.where(active, i_new, i),
                "keys": keys,
                "active": active & ~fin & ~bad,
                "x_final": jnp.where(fin, final, x_final),
                "err": jnp.where(active, err, err_prev),
                "tol": tol,
                "min_i": min_i,
                "scale": scale,
                "guard": guard,
            }
            if has_cond:
                new["cond"] = cond
            if has_fc:
                new["feats"] = jax.tree.map(keep, box["feats"], feats)
            aux = {"finished": fin, "stepped": active & ~init,
                   "failed": bad, "i": new["i"], "err": new["err"]}
            if stream:
                aux["x0"] = x0
            return new, aux

        cond = carry["cond"] if has_cond else None
        feats = carry["feats"] if has_fc else None
        return jax.vmap(lane)(
            carry["inner"], carry["i"], carry["keys"], carry["active"],
            carry["x_final"], carry["err"], carry["tol"], carry["min_i"],
            carry["scale"], carry["guard"], cond, feats)

    return run_step


def _make_run_join(adapter, has_cond: bool, has_fc: bool = False):
    def run_join(arrays, carry, lane, x_T, keys, tol, min_i, scale,
                 guard=0, cond=None):
        payload = {
            "inner": adapter.init_inner(arrays, x_T),
            "i": jnp.int32(adapter.i0),
            "keys": keys,
            "active": jnp.asarray(True),
            "x_final": jnp.zeros_like(carry["x_final"][0]),
            "err": jnp.float32(jnp.inf),
            "tol": tol,
            "min_i": min_i,
            "scale": scale,
            "guard": jnp.asarray(guard, jnp.int32),
        }
        if has_cond:
            payload["cond"] = cond
        if has_fc:
            # fresh lanes start with zero features; the init tick's
            # forced refresh overwrites them before any reuse
            payload["feats"] = jax.tree.map(lambda f: jnp.zeros_like(f[0]),
                                            carry["feats"])
        return jax.tree.map(lambda c, p: c.at[lane].set(p), carry, payload)

    return run_join


def _run_copy(dst, src, dst_lane, src_lane):
    return jax.tree.map(lambda d, s: d.at[dst_lane].set(s[src_lane]),
                        dst, src)


def make_stepfns(plan: SamplerPlan, model_fn, shape, dtype, batch: int, *,
                 cond=None, guidance_scale=1.0, stream: bool = False,
                 model_key: Hashable | None = None) -> StepFns:
    """The (LRU-cached) compiled step/join/copy bundle for one step key.

    ``cond`` is a *per-request* conditioning prototype; like the
    whole-solve entry points, conditioning values and the guidance scale
    are traced per-lane data — only cond's shape/dtype structure keys
    the entry. Two plans whose specs differ only in tau / program
    orders / coefficient values resolve to the SAME entry: their step
    functions are one compilation fed different table data.
    """
    adapter = stepwise_adapter(plan.spec)
    cond_c, _ = _check_model(plan, model_fn, cond, guidance_scale)
    dadapter = _adapter_statics(plan, model_fn)
    cell_ref = _weak(model_fn)
    if model_key is not None:
        token = ("user", model_key)
    else:
        token = _model_token(model_fn)
        if token is None:
            token = ("strong", id(model_fn))
            cell_ref = None
    key = (plan.spec.name, adapter.statics,
           adapter.n_steps_of(adapter.arrays(plan)),
           adapter.shape_key(plan), tuple(shape), jnp.dtype(dtype).name,
           int(batch), token, dadapter, cond_struct(cond_c), bool(stream))
    entry = _STEP_CACHE.get(key)
    if entry is not None:
        _STEP_CACHE.move_to_end(key)
        _STEP_STATS["hits"] += 1
        if isinstance(entry.cell[0], weakref.ref):
            entry.cell[0] = cell_ref if cell_ref is not None else model_fn
        return entry
    _STEP_STATS["misses"] += 1
    if model_key is None and not isinstance(token, tuple):
        # storage token with an eviction callback for when the model dies
        token = _model_token(model_fn, _on_model_death)
        key = key[:_STEP_TOKEN_IDX] + (token,) + key[_STEP_TOKEN_IDX + 1:]
    cell = [cell_ref if cell_ref is not None else model_fn]
    has_cond = cond is not None
    has_fc = plan.spec.feature_cache is not None
    entry = StepFns(
        adapter, cell, key, shape, dtype, has_cond,
        jax.jit(_make_run_step(adapter, dadapter, cell, has_cond, stream,
                               has_fc)),
        jax.jit(_make_run_join(adapter, has_cond, has_fc)),
        jax.jit(_run_copy))
    _STEP_CACHE[key] = entry
    while len(_STEP_CACHE) > _STEP_CACHE_MAX:
        _STEP_CACHE.popitem(last=False)
        _STEP_STATS["evictions"] += 1
    return entry
