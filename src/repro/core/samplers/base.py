"""Plan/execute sampler API: one registry for SA-Solver and every baseline.

The sampling stack is split into three phases so serving can select,
configure, compile-cache, and swap solvers at runtime without code changes:

1. **Spec** — a frozen, hashable :class:`SamplerSpec` naming a registered
   sampler family plus all hyperparameters (grid, tau/eta, orders,
   parameterization). ``SamplerSpec.from_nfe`` converts a model-evaluation
   budget into the family's step count (PEC vs PECE vs 2-evals-per-step
   Heun all differ), so "NFE" means the same thing for every sampler.
2. **Plan** — :func:`build_plan` runs the family's host-side float64
   precompute (timestep grid, coefficient tables, per-interval constants)
   once and packages it as a :class:`SamplerPlan` whose ``arrays`` dict is
   a device-ready pytree of f32 ``jnp`` arrays. Plans are cached by spec.
3. **Execute** — :func:`sample` looks up a pure jitted executor in an LRU
   compile cache keyed on (family statics, shape, dtype, model identity,
   batch lane count, mesh/sharding identity, denoiser-adapter statics,
   conditioning structure) and runs it with ``plan.arrays`` passed as
   *traced arguments* — so re-planning with a
   different tau / grid / coefficient table reuses the compiled step
   loop, only a different step count retraces. The model identity is a
   *weakref* (or a caller-stable ``model_key``): the cache never pins
   model parameters, and executors are evicted when their model is
   garbage-collected. :func:`sample_batched` vmaps the executor over a
   leading key axis for fleet-style generation; :func:`sample_sharded`
   additionally places that request axis on the ``data`` axis of a mesh
   (replicated plan arrays, donated carry); :func:`warmup` AOT-compiles
   one batch bucket (``jit(...).lower().compile()``) so a serving hot
   path never traces. ``trajectory=True`` returns the per-step state and
   denoised previews (stacked ``lax.scan`` outputs) so serving can
   stream intermediates. ``repro.serve`` builds the request
   queue/microbatching service on these four entry points.

The model argument of every entry point is either a plain
``model_fn(x, t)`` already speaking the plan's parameterization, or a
:class:`repro.core.denoiser.Denoiser` wrapping a raw eps-/x0-/v-prediction
network (optionally under classifier-free guidance). The binding happens
*inside* the jitted executor: the per-call conditioning pytree ``cond``
and ``guidance_scale`` are traced arguments — a guidance-scale sweep or a
new conditioning batch reuses one compilation; only the cond's
shape/dtype structure keys the executor.

Registering a new sampler::

    register_sampler(SamplerFamily(
        name="my_solver",
        plan=my_plan_fn,        # spec -> (arrays: dict[str, jnp], host: dict)
        execute=my_exec_fn,     # (statics, arrays, model_fn, x, key, trajectory)
        statics=lambda spec: (),  # trace-relevant spec fields only
        nfe_of=lambda spec: spec.n_steps,
        steps_from_nfe=lambda nfe, kw: max(1, nfe),
    ))
"""

from __future__ import annotations

import dataclasses
import types
import weakref
from collections import OrderedDict
from typing import Any, Callable, Hashable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..denoiser import Denoiser, canonical_prediction, convert_prediction
from ..schedules import NoiseSchedule, get_schedule, timestep_grid
from ..tau import TauSchedule

__all__ = [
    "PRECISIONS",
    "carry_dtype",
    "SamplerSpec",
    "SamplerPlan",
    "SamplerFamily",
    "Sampler",
    "register_sampler",
    "get_family",
    "make_sampler",
    "list_samplers",
    "build_plan",
    "cond_struct",
    "sample",
    "sample_batched",
    "sample_sharded",
    "warmup",
    "compile_cache_stats",
    "clear_compile_cache",
]

ModelFn = Callable[[jnp.ndarray, jnp.ndarray], jnp.ndarray]

#: legal values of ``SamplerSpec.precision``
PRECISIONS = ("f32", "bf16")


def carry_dtype(precision: str):
    """Scan-carry dtype of the hot-loop precision policy (one definition
    for SA and every baseline): step arithmetic accumulates in f32
    either way, so at "f32" the policy casts are dtype identities
    (bitwise no-ops) and at "bf16" only the carried state, history, and
    model input narrow."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"precision={precision!r}; expected one of {PRECISIONS}")
    return jnp.bfloat16 if precision == "bf16" else jnp.float32


# --------------------------------------------------------------------- spec
@dataclasses.dataclass(frozen=True)
class SamplerSpec:
    """Frozen, hashable description of one configured sampler.

    Families read the subset of fields they understand; the rest keep their
    defaults and are ignored. ``schedule`` is a registry name ("vp_linear")
    or a (frozen) :class:`NoiseSchedule` instance. ``ts`` overrides the
    (grid, n_steps) construction with an explicit decreasing grid — used by
    the legacy shims and by grid-search callers.
    """

    name: str = "sa"
    schedule: Any = "vp_linear"
    n_steps: int = 20
    grid: str = "logsnr"  # "time" | "logsnr" | "karras"
    rho: float = 7.0
    t_start: float | None = None
    t_end: float | None = None
    ts: tuple[float, ...] | None = None
    parameterization: str = "data"  # "data" | "noise"
    # SA-Solver family
    tau: Any = 1.0  # float or TauSchedule
    predictor_order: int = 3
    corrector_order: int = 3
    mode: str = "PEC"  # "PEC" | "PECE"
    #: optional :class:`repro.core.programs.StepProgram` — per-interval
    #: (predictor order, corrector order, P/PEC/PECE mode, tau) tracks.
    #: When set it shadows tau/predictor_order/corrector_order/mode
    #: above. Hashable, so it joins the compile-cache key (via the
    #: family statics) and the serving bucket key (the spec itself);
    #: per-interval orders and taus are table *data* — only the mode
    #: pattern is trace-relevant. A program pinning constant order/tau
    #: is bitwise-identical to the fixed-spec path.
    program: Any = None
    #: "einsum" (one XLA contraction), "kernel" (the Pallas sa_update
    #: path; interpret-mode on CPU), or "fused" (dual-output
    #: predictor+corrector kernel — one pass over x/xi/history, ring only)
    combine: str = "einsum"
    #: evaluation-history layout: "ring" (fixed ring buffer, one
    #: dynamic_update_index row write per step) or "concat" (the seed
    #: layout that re-materializes the buffer twice per step; kept as the
    #: regression/benchmark baseline). The f32 ring einsum/kernel path is
    #: bitwise-identical to concat.
    history: str = "ring"
    denoise_final: bool = True
    #: hot-loop precision policy: "f32", or "bf16" to carry the scan
    #: state and history buffer (and feed the model) in bfloat16 with f32
    #: accumulation inside every combine — coefficient tables stay f32.
    #: Part of the executor statics, so it keys the compile cache and the
    #: serving bucket (the spec is the bucket key).
    precision: str = "f32"
    # DDIM family
    eta: float = 0.0
    # EDM stochastic family
    s_churn: float = 40.0
    s_tmin: float = 0.05
    s_tmax: float = 50.0
    s_noise: float = 1.003
    # Denoiser adapter (see repro.core.denoiser)
    #: output convention of the network behind the model argument —
    #: "eps" | "x0"/"data" | "v". None means "already the plan's
    #: parameterization" (the legacy plain-model_fn contract).
    prediction: str | None = None
    #: classifier-free guidance: the executor fuses cond/uncond into one
    #: doubled-lane network eval per model call (requires a Denoiser).
    guidance: bool = False
    #: DeepCache-style step-to-step feature caching (requires a Denoiser
    #: built with ``cached=``; a family with ``supports_feature_cache`` —
    #: the multistep core — and ring history). ``None`` = off;
    #: an int ``k`` refreshes the deep feature segment every k-th solver
    #: step (interval policy); ``("residual", thresh)`` refreshes when the
    #: previous step's free PECE predictor-vs-corrector residual meets
    #: ``thresh`` (residual policy; PECE mode only). Policy *parameters*
    #: (k, thresh) are plan data — only on/off is trace-relevant.
    feature_cache: Any = None

    def resolve_schedule(self) -> NoiseSchedule:
        if isinstance(self.schedule, NoiseSchedule):
            return self.schedule
        return get_schedule(self.schedule)

    def grid_ts(self) -> np.ndarray:
        """The decreasing float64 solve grid ``t_0 > ... > t_M``."""
        if self.ts is not None:
            ts = np.asarray(self.ts, dtype=np.float64)
            if len(ts) != self.n_steps + 1:
                raise ValueError(
                    f"explicit ts has {len(ts)} points but n_steps="
                    f"{self.n_steps} needs {self.n_steps + 1}")
            return ts
        return timestep_grid(
            self.resolve_schedule(), self.n_steps, kind=self.grid,
            t_start=self.t_start, t_end=self.t_end, rho=self.rho)

    @property
    def nfe(self) -> int:
        """Guided (solver-level) model evaluations this spec will spend
        (family-exact)."""
        return get_family(self.name).nfe_of(self)

    @property
    def network_nfe(self) -> int:
        """Raw network forwards: under classifier-free guidance every
        guided evaluation is one fused network call over a doubled lane
        count — 2x the compute of an unguided evaluation."""
        return self.nfe * (2 if self.guidance else 1)

    @classmethod
    def from_nfe(cls, name: str, nfe: int, **kw) -> "SamplerSpec":
        """Build a spec whose step count spends (at most) ``nfe`` model
        evaluations — the conversion is per-family (PEC: NFE = M + 1,
        PECE: 2M + 1, DDIM-like: M, Heun-like: 2M)."""
        if nfe < 1:
            raise ValueError("nfe must be >= 1")
        n_steps = get_family(name).steps_from_nfe(nfe, kw)
        return cls(name=name, n_steps=n_steps, **kw)

    def replace(self, **kw) -> "SamplerSpec":
        return dataclasses.replace(self, **kw)


# --------------------------------------------------------------------- plan
@dataclasses.dataclass(frozen=True, eq=False)
class SamplerPlan:
    """Host precompute, packaged for the device.

    ``arrays`` is the device-ready pytree (dict of f32 jnp arrays) handed
    to the jitted executor as traced arguments; ``host`` keeps float64
    artifacts (the grid, coefficient tables) for introspection and
    ``init_noise``; ``statics`` are the trace-relevant hashables the
    executor branches on (part of the compile-cache key).
    """

    spec: SamplerSpec
    arrays: dict
    host: dict
    statics: tuple

    @property
    def ts(self) -> np.ndarray:
        return self.host["ts"]


# ----------------------------------------------------------------- registry
def _data_convention(spec: "SamplerSpec") -> str:
    return "data"


@dataclasses.dataclass(frozen=True)
class SamplerFamily:
    name: str
    #: spec -> (arrays: dict[str, jnp.ndarray], host: dict)
    plan: Callable[[SamplerSpec], tuple]
    #: (statics, arrays, model_fn, x, key, trajectory) -> x0 | (x0, traj)
    execute: Callable
    #: spec -> hashable tuple of the fields the executor branches on
    statics: Callable[[SamplerSpec], tuple]
    nfe_of: Callable[[SamplerSpec], int]
    steps_from_nfe: Callable[[int, dict], int]
    #: spec -> the prediction convention this family's executors consume
    #: ("data" -> x0-hat, "noise" -> eps-hat). The denoiser adapter
    #: converts any wrapped network to this convention in-graph.
    model_convention: Callable[[SamplerSpec], str] = _data_convention
    #: spec -> repro.core.samplers.stepwise.StepAdapter, or None when the
    #: family has no step-granular executor (whole-solve scan only)
    stepwise: Callable | None = None
    #: whether the family's executors dispatch the Denoiser's cached
    #: (split-segment) eval — spec.feature_cache is rejected otherwise
    #: (the knob would be silently inert)
    supports_feature_cache: bool = False
    #: whether the family consumes FULL step programs (per-interval order
    #: and mode tracks, not just the tau track). True for families on the
    #: multistep core; the baselines only honor program tau tracks.
    full_programs: bool = False
    #: whether tau is definitionally inert for this family (a
    #: deterministic family maps every tau to 0) — lets the autotuner and
    #: tier ladders skip tau moves instead of sweeping a no-op axis
    tau_inert: bool = False


_REGISTRY: dict[str, SamplerFamily] = {}


def register_sampler(family: SamplerFamily) -> SamplerFamily:
    if not isinstance(family, SamplerFamily):
        raise TypeError("register_sampler takes a SamplerFamily")
    _REGISTRY[family.name] = family
    return family


def get_family(name: str) -> SamplerFamily:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown sampler {name!r}; registered: {list_samplers()}")


def list_samplers() -> list[str]:
    return sorted(_REGISTRY)


# ------------------------------------------------------------- plan caching
_PLAN_CACHE: OrderedDict = OrderedDict()
_PLAN_CACHE_MAX = 128


def build_plan(spec: SamplerSpec) -> SamplerPlan:
    """Resolve a spec into its (cached) device-ready plan."""
    try:
        plan = _PLAN_CACHE.get(spec)
    except TypeError:  # unhashable field (e.g. a raw np.ndarray ts)
        plan = None
        spec_key = None
    else:
        spec_key = spec
    if plan is not None:
        _PLAN_CACHE.move_to_end(spec_key)
        return plan
    family = get_family(spec.name)
    arrays, host = family.plan(spec)
    if "ts" not in host:
        host["ts"] = spec.grid_ts()
    plan = SamplerPlan(spec=spec, arrays=arrays, host=host,
                       statics=family.statics(spec))
    if spec_key is not None:
        _PLAN_CACHE[spec_key] = plan
        while len(_PLAN_CACHE) > _PLAN_CACHE_MAX:
            _PLAN_CACHE.popitem(last=False)
    return plan


# ------------------------------------------------------------ compile cache
_COMPILE_CACHE: OrderedDict = OrderedDict()
_COMPILE_CACHE_MAX = 64
_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0, "aot_fallbacks": 0}
_MODEL_TOKEN_IDX = 4  # position of the model token inside a cache key


def compile_cache_stats() -> dict:
    return dict(_CACHE_STATS, size=len(_COMPILE_CACHE))


def clear_compile_cache() -> None:
    _COMPILE_CACHE.clear()
    for k in _CACHE_STATS:
        _CACHE_STATS[k] = 0


class _CacheEntry:
    """One compiled executor: the jitted wrapper, an optional AOT-compiled
    executable (``warmup``), and a weak cell holding the model_fn used for
    (re)tracing — weak so the cache never pins model parameters."""

    __slots__ = ("fn", "cell", "aot")

    def __init__(self, fn, cell):
        self.fn = fn
        self.cell = cell
        self.aot = None


def _weak(model_fn, callback=None):
    """A weak ref to ``model_fn`` for the trace cell (None if not
    weakrefable). Bound methods get :class:`weakref.WeakMethod` — a plain
    ref to the transient method object would die immediately."""
    try:
        if isinstance(model_fn, types.MethodType):
            return weakref.WeakMethod(model_fn, callback)
        return weakref.ref(model_fn, callback)
    except TypeError:
        return None


class _WeakIdToken:
    """Weak *identity* of a model for the cache key.

    Hashes by ``id`` and compares equal only to tokens of the same live
    object — so unhashable callables work, value-equal but distinct
    models never share an executor, and the token holds no strong
    reference. A dead token equals nothing (and its entry is evicted by
    the death callback before the id can be recycled under a live key).
    """

    __slots__ = ("ref", "oid")

    def __init__(self, obj, callback=None):
        self.ref = weakref.ref(obj, callback)
        self.oid = id(obj)

    def __hash__(self):
        return self.oid

    def __eq__(self, other):
        if not isinstance(other, _WeakIdToken):
            return NotImplemented
        a = self.ref()
        return a is not None and a is other.ref()


def _model_token(model_fn, callback=None):
    """Weak identity token for the cache key; None -> strong fallback.

    Bound methods go through :class:`weakref.WeakMethod` (equality by
    instance + function, surviving the transient method object); other
    callables get a :class:`_WeakIdToken`.
    """
    if isinstance(model_fn, types.MethodType):
        try:
            tok = weakref.WeakMethod(model_fn, callback)
            hash(tok)  # hashes the method -> needs a hashable instance
            return tok
        except TypeError:
            return None
    try:
        return _WeakIdToken(model_fn, callback)
    except TypeError:
        return None


def _token_matches(token, ref) -> bool:
    if token is ref:  # WeakMethod
        return True
    return isinstance(token, _WeakIdToken) and token.ref is ref


def _on_model_death(ref) -> None:
    """Weakref callback: the model behind ``ref`` was garbage-collected, so
    its executors (whose traced constants pin the model's param buffers)
    are dead weight — evict them eagerly."""
    for key in [k for k in _COMPILE_CACHE
                if _token_matches(k[_MODEL_TOKEN_IDX], ref)]:
        if _COMPILE_CACHE.pop(key, None) is not None:
            _CACHE_STATS["evictions"] += 1


def _deref_model(cell):
    m = cell[0]
    if isinstance(m, weakref.ref):
        m = m()
    if m is None:
        raise RuntimeError(
            "the model_fn behind this cached executor was garbage-"
            "collected; call sample()/sample_batched() with a live "
            "model_fn (or pass model_key= to share executors across "
            "model_fn instances)")
    return m


# -------------------------------------------------- denoiser adapter hooks
def _adapter_statics(plan: SamplerPlan, model_fn) -> tuple | None:
    """Trace-relevant identity of the model adaptation for the cache key.

    None -> the model already speaks the plan's convention (legacy plain
    ``model_fn``); a tuple -> a Denoiser binding or a plain-model
    prediction-type conversion (both change the traced graph).
    """
    target = get_family(plan.spec.name).model_convention(plan.spec)
    if isinstance(model_fn, Denoiser):
        return model_fn.statics(target)
    pred = plan.spec.prediction
    if pred is not None and \
            canonical_prediction(pred) != canonical_prediction(target):
        return ("convert", canonical_prediction(pred),
                canonical_prediction(target), plan.spec.resolve_schedule())
    return None


def _model_params(model_fn):
    """The weights a Denoiser hands its executors as an argument (None
    for plain callables, which close over their own)."""
    return model_fn.params if isinstance(model_fn, Denoiser) else None


def _bind_model(m, adapter, cond, scale, cfg_shard=None, params=None):
    """Build the executor-facing ``model_fn(x, t)`` closure at trace time,
    folding in the traced ``cond``/``scale``/``params`` arguments. When
    the model is a Denoiser with a feature-cached companion, the closure
    additionally carries ``cached_call(x, t, feats, refresh) -> (pred,
    feats)`` and ``init_feats(x)`` attributes for feature-caching
    executors.
    ``cfg_shard`` (a NamedSharding over the CFG axis) requests sharded
    classifier-free guidance inside the Denoiser."""
    if adapter is None:
        return m
    if adapter[0] == "denoiser":
        m = m.bind(params)
        fn = m.as_model_fn(adapter[3], cond, scale, cfg_shard)
        if m.cached is not None:
            fn.cached_call = m.as_cached_model_fn(
                adapter[3], cond, scale, cfg_shard)
            fn.init_feats = m.init_feats
        return fn
    _, src, dst, schedule = adapter  # plain model_fn, converted output
    return lambda x, t: convert_prediction(m(x, t), x, t, src, dst, schedule)


def cond_struct(cond):
    """Hashable shape/dtype structure of a conditioning pytree — the only
    part of ``cond`` that keys an executor (and a serving bucket); values
    stay traced data. The single definition both layers share: if the
    compile-cache key and the bucket key ever hashed cond differently,
    buckets would split or executors collide."""
    if cond is None:
        return None
    leaves, treedef = jax.tree_util.tree_flatten(cond)
    return (treedef, tuple((tuple(l.shape), jnp.dtype(l.dtype).name)
                           for l in leaves))


def _host_scale_not_unity(guidance_scale) -> bool:
    """True when ``guidance_scale`` is a host value (Python/numpy
    scalar, list/tuple, or numpy array — NOT a jax device array)
    provably != 1.0. Host values are checked for free; device arrays
    return False so the caller never forces a blocking device->host
    sync."""
    if isinstance(guidance_scale, (int, float, np.floating, np.integer)):
        return float(guidance_scale) != 1.0
    if isinstance(guidance_scale, (np.ndarray, list, tuple)):
        return bool(np.any(np.asarray(guidance_scale) != 1.0))
    return False


def _check_model(plan: SamplerPlan, model_fn, cond, guidance_scale):
    """Validate the model argument against the spec's denoiser fields and
    canonicalize (cond, scale) into traced arrays."""
    spec = plan.spec
    if isinstance(model_fn, Denoiser):
        if bool(spec.guidance) != bool(model_fn.guidance):
            raise ValueError(
                f"spec.guidance={spec.guidance} but the Denoiser has "
                f"guidance={model_fn.guidance}; the spec is what serving "
                "buckets and NFE accounting read — keep them consistent")
        if spec.prediction is not None and \
                canonical_prediction(spec.prediction) != model_fn.prediction:
            raise ValueError(
                f"spec.prediction={spec.prediction!r} but the Denoiser "
                f"predicts {model_fn.prediction!r}")
    else:
        if spec.guidance:
            raise ValueError(
                "spec.guidance=True needs a Denoiser model (classifier-"
                "free guidance requires the cond/uncond network contract)")
        if cond is not None:
            raise ValueError(
                "conditioning requires a Denoiser model; a plain "
                "model_fn(x, t) has no cond input")
    if spec.feature_cache is not None:
        if not get_family(spec.name).supports_feature_cache:
            raise ValueError(
                f"feature_cache is not supported by the {spec.name!r} "
                "family (its executors never dispatch the cached eval, so "
                "the knob would be silently inert); use a multistep-core "
                "family (sa, seeds, dpmpp_multistep)")
        if not (isinstance(model_fn, Denoiser)
                and model_fn.cached is not None):
            raise ValueError(
                "spec.feature_cache requires a Denoiser built with "
                "cached= (a CachedNetwork exposing the split-segment "
                "eval)")
    if cond is not None:
        cond = jax.tree.map(jnp.asarray, cond)
    guided = isinstance(model_fn, Denoiser) and model_fn.guidance
    if not guided and _host_scale_not_unity(guidance_scale):
        # host-side guard only: the old ``bool(jnp.any(scale != 1.0))``
        # forced a device->host round-trip on EVERY sample() call —
        # a blocking sync on the serving hot path. Python/numpy values
        # (the overwhelmingly common case) are checked for free here;
        # device-array inputs skip the check rather than sync — a
        # non-unity device-array scale without a guidance Denoiser is
        # silently inert, which the docstrings call out.
        raise ValueError(
            "guidance_scale has no effect without a guidance-enabled "
            "Denoiser — it would be silently dropped; wrap the network "
            "in Denoiser(..., guidance=True) (and set spec.guidance)")
    scale = jnp.asarray(guidance_scale, jnp.float32)
    return cond, scale


def _mesh_ident(mesh: Mesh | None, data_axis: str,
                cfg_axis: str | None = None):
    """Hashable identity of a mesh placement — part of the compile-cache
    key so sharded and unsharded executables never collide, and two
    meshes over different devices/axis layouts don't either. The CFG
    axis (sharded classifier-free guidance) changes the traced graph, so
    it joins the identity."""
    if mesh is None:
        return None
    return (tuple(mesh.shape.items()),
            tuple(int(d.id) for d in mesh.devices.flat),
            data_axis, cfg_axis)


def _compiled(plan: SamplerPlan, model_fn: ModelFn, shape, dtype,
              trajectory: bool, batch: int | None, *,
              model_key: Hashable | None = None,
              mesh: Mesh | None = None, data_axis: str = "data",
              cfg_axis: str | None = None,
              donate: bool = False, cond=None) -> _CacheEntry:
    """LRU-cached jitted executor.

    Keyed on (family name, executor statics, per-request shape, dtype,
    model token, trajectory, batch lane count (None = unbatched),
    mesh/sharding identity, denoiser-adapter statics, conditioning
    shape/dtype structure). The lane count is part of the key — not left
    to ``jax.jit``'s per-aval cache — so every serving bucket owns its
    entry and its AOT executable (``warmup``) can never be shadowed by a
    different bucket size. The model token is a
    caller-supplied stable ``model_key`` when given, else a *weakref*
    identity of ``model_fn`` (a plain callable or a Denoiser) — the cache
    holds no strong reference to the
    model (closures over full param trees would otherwise pin up to
    ``_COMPILE_CACHE_MAX`` param copies), and entries are evicted eagerly
    when their model is garbage-collected.

    ``plan.arrays``, the conditioning pytree, and the guidance scale are
    traced arguments, so two plans of the same
    family/statics (different tau, grid, or coefficient values at the same
    step count), a new conditioning batch of the same structure, or a new
    guidance scale all share one compilation; a different step count
    changes argument shapes and retraces inside the same entry via
    ``jax.jit``'s own cache.
    """
    cell_ref = _weak(model_fn)
    if model_key is not None:
        token = ("user", model_key)
    else:
        token = _model_token(model_fn)
        if token is None:
            # not weakly keyable: fall back to identity + a strong ref in
            # the cell, which pins the object so its id cannot recycle
            # (old behaviour; rare — functions/closures/methods/partials
            # are all weakly keyable)
            token = ("strong", id(model_fn))
            cell_ref = None
    adapter = _adapter_statics(plan, model_fn)
    cfg_shard = None
    if cfg_axis is not None:
        if mesh is None or cfg_axis not in mesh.shape:
            raise ValueError(
                f"cfg_axis={cfg_axis!r} needs a mesh with that axis "
                "(see repro.serve.sharding.auto_cfg_mesh)")
        if mesh.shape[cfg_axis] != 2:
            raise ValueError(
                f"cfg_axis {cfg_axis!r} has size {mesh.shape[cfg_axis]}; "
                "sharded CFG splits exactly the cond/uncond pair (size 2)")
        if not (isinstance(model_fn, Denoiser) and model_fn.guidance):
            raise ValueError(
                "cfg_axis only applies to a guidance-enabled Denoiser")
        cfg_shard = NamedSharding(mesh, P(cfg_axis))
    key = (plan.spec.name, plan.statics, tuple(shape),
           jnp.dtype(dtype).name, token, trajectory, batch,
           _mesh_ident(mesh, data_axis, cfg_axis), bool(donate), adapter,
           cond_struct(cond))
    entry = _COMPILE_CACHE.get(key)
    if entry is not None:
        _COMPILE_CACHE.move_to_end(key)
        _CACHE_STATS["hits"] += 1
        # refresh a weak cell so retraces (and user-keyed entries handed a
        # new functionally-equal model_fn) trace the live object; strong
        # cells stay pinned (their id backs the cache key)
        if isinstance(entry.cell[0], weakref.ref):
            entry.cell[0] = cell_ref if cell_ref is not None else model_fn
        return entry
    _CACHE_STATS["misses"] += 1
    family = get_family(plan.spec.name)
    statics = plan.statics

    if model_key is None and not isinstance(token, tuple):
        # storage token: equal/same-hash as the lookup token while the
        # model lives, plus an eviction callback when it dies
        token = _model_token(model_fn, _on_model_death)
        key = key[:_MODEL_TOKEN_IDX] + (token,) + key[_MODEL_TOKEN_IDX + 1:]

    cell = [cell_ref if cell_ref is not None else model_fn]

    if batch is not None:
        def run(arrays, xs, keys, cond, scale, params):
            m = _deref_model(cell)
            return jax.vmap(
                lambda x, k, c, s: family.execute(
                    statics, arrays,
                    _bind_model(m, adapter, c, s, cfg_shard, params), x, k,
                    trajectory)
            )(xs, keys, cond, scale)
    else:
        def run(arrays, x, k, cond, scale, params):
            m = _deref_model(cell)
            return family.execute(
                statics, arrays,
                _bind_model(m, adapter, cond, scale, cfg_shard, params),
                x, k, trajectory)

    jit_kw: dict = {}
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        lane = NamedSharding(mesh, P(data_axis))
        jit_kw["in_shardings"] = (
            rep,  # plan arrays: replicated (prefix over the whole pytree)
            NamedSharding(mesh, P(data_axis, *([None] * len(shape)))),
            lane,   # per-lane PRNG keys
            lane,   # cond pytree: leading request axis (prefix)
            lane,   # per-lane guidance scale
            rep,    # model weights: replicated
        )
        if donate:
            jit_kw["donate_argnums"] = (1,)  # the x_T carry buffer
    entry = _CacheEntry(jax.jit(run, **jit_kw), cell)
    _COMPILE_CACHE[key] = entry
    while len(_COMPILE_CACHE) > _COMPILE_CACHE_MAX:
        _COMPILE_CACHE.popitem(last=False)
    return entry


def _call(entry: _CacheEntry, arrays, x, k, cond, scale, params):
    if entry.aot is not None:
        try:
            return entry.aot(arrays, x, k, cond, scale, params)
        except TypeError:
            # aval mismatch vs the warmed bucket (e.g. a re-planned step
            # count changed the coefficient-table shapes, or a typed key
            # array): fall back to the jit wrapper, which retraces within
            # this entry; counted so the degradation is observable
            _CACHE_STATS["aot_fallbacks"] += 1
    return entry.fn(arrays, x, k, cond, scale, params)


def _default_donate() -> bool:
    # donation is a no-op (with a log warning) on the CPU backend
    return jax.default_backend() in ("tpu", "gpu")


# -------------------------------------------------------------- entrypoints
def sample(plan: SamplerPlan, model_fn: ModelFn, x_T: jnp.ndarray,
           key: jax.Array, *, cond=None, guidance_scale=1.0,
           trajectory: bool = False,
           model_key: Hashable | None = None):
    """Run one sampler end-to-end: ``x_T -> x_0``.

    ``model_fn`` is a plain ``(x, t)`` callable speaking the plan's
    parameterization, or a :class:`~repro.core.denoiser.Denoiser`
    wrapping a raw eps/x0/v network — in which case ``cond`` (a pytree of
    arrays threaded alongside ``x``) and ``guidance_scale`` are forwarded
    to it as *traced* arguments: sweeping the scale or swapping the
    conditioning values reuses one compilation.

    With ``trajectory=True`` returns ``(x_0, traj)`` where ``traj`` is a
    dict of per-step stacked outputs — ``traj["x"]`` the state after each
    step and ``traj["x0"]`` the step's denoised preview, both
    ``[n_steps, *x_T.shape]`` — for streaming/debugging. ``model_key``
    optionally replaces the weakref model identity in the compile-cache
    key with a caller-stable token (so re-created but functionally equal
    model closures share one executor).
    """
    cond, scale = _check_model(plan, model_fn, cond, guidance_scale)
    entry = _compiled(plan, model_fn, x_T.shape, x_T.dtype, trajectory,
                      None, model_key=model_key, cond=cond)
    return _call(entry, plan.arrays, x_T, key, cond, scale,
                 _model_params(model_fn))


def sample_batched(plan: SamplerPlan, model_fn: ModelFn, x_T: jnp.ndarray,
                   keys: jax.Array, *, cond=None, guidance_scale=1.0,
                   trajectory: bool = False,
                   model_key: Hashable | None = None):
    """Fleet-style generation: vmap the executor over a leading key axis.

    ``keys`` is a stacked PRNG-key array ``[K, ...]`` and ``x_T`` carries a
    matching leading axis ``[K, *shape]`` (one initial noise per key).
    With a Denoiser model, ``cond`` leaves carry the same leading ``K``
    axis (per-request conditioning) and ``guidance_scale`` is a scalar or
    a ``[K]`` per-request vector.
    """
    if x_T.shape[0] != keys.shape[0]:
        raise ValueError(
            f"leading axes must match: x_T {x_T.shape[0]} vs keys "
            f"{keys.shape[0]}")
    cond, scale = _check_model(plan, model_fn, cond, guidance_scale)
    scale = jnp.broadcast_to(scale, (int(x_T.shape[0]),))
    entry = _compiled(plan, model_fn, x_T.shape[1:], x_T.dtype, trajectory,
                      int(x_T.shape[0]), model_key=model_key, cond=cond)
    return _call(entry, plan.arrays, x_T, keys, cond, scale,
                 _model_params(model_fn))


def sample_sharded(plan: SamplerPlan, model_fn: ModelFn, x_T: jnp.ndarray,
                   keys: jax.Array, *, mesh: Mesh, data_axis: str = "data",
                   cfg_axis: str | None = None,
                   cond=None, guidance_scale=1.0,
                   trajectory: bool = False,
                   model_key: Hashable | None = None,
                   donate: bool | None = None):
    """``sample_batched`` with the leading request axis placed on the
    ``data`` axis of ``mesh``.

    Inputs get :class:`NamedSharding` placements (requests split over
    ``data_axis``, plan arrays replicated; conditioning leaves and the
    per-request guidance-scale vector ride the request axis too); the
    ``x_T`` carry buffer is
    donated (``donate_argnums``) on backends that implement donation.
    The compile-cache key carries the mesh/sharding identity, so sharded
    and unsharded executables for the same bucket never collide.

    ``cfg_axis`` names a size-2 mesh axis to carry the classifier-free
    cond/uncond pair (sharded CFG): the doubled-lane network eval inside
    the Denoiser is constrained onto that axis, so each device evaluates
    ONE branch at the local batch instead of both at a doubled local
    batch — numerically the combine is unchanged. Requires a
    guidance-enabled Denoiser and a cfg-factored mesh
    (``repro.serve.sharding.auto_cfg_mesh``); on a single device leave it
    ``None`` (the fused doubled-lane eval is the fallback).
    """
    if x_T.shape[0] != keys.shape[0]:
        raise ValueError(
            f"leading axes must match: x_T {x_T.shape[0]} vs keys "
            f"{keys.shape[0]}")
    if data_axis not in mesh.shape:
        raise ValueError(
            f"mesh has no axis {data_axis!r}; axes: {tuple(mesh.shape)}")
    n_data = mesh.shape[data_axis]
    if x_T.shape[0] % n_data:
        raise ValueError(
            f"request batch {x_T.shape[0]} is not divisible by mesh axis "
            f"{data_axis!r} (size {n_data}); pad the bucket first "
            "(repro.serve.sharding.align_bucket_sizes)")
    donate = _default_donate() if donate is None else donate
    cond, scale = _check_model(plan, model_fn, cond, guidance_scale)
    scale = jnp.broadcast_to(scale, (int(x_T.shape[0]),))
    entry = _compiled(plan, model_fn, x_T.shape[1:], x_T.dtype, trajectory,
                      int(x_T.shape[0]), model_key=model_key, mesh=mesh,
                      data_axis=data_axis, cfg_axis=cfg_axis,
                      donate=donate, cond=cond)
    return _call(entry, plan.arrays, x_T, keys, cond, scale,
                 _model_params(model_fn))


def warmup(plan: SamplerPlan, model_fn: ModelFn, shape, dtype=jnp.float32,
           *, batch: int | None = None, mesh: Mesh | None = None,
           data_axis: str = "data", cfg_axis: str | None = None,
           cond=None, trajectory: bool = False,
           model_key: Hashable | None = None,
           donate: bool | None = None):
    """AOT-compile one bucket: ``jit(run).lower(...).compile()``.

    ``shape`` is the per-request latent shape; ``batch`` the bucket size
    (None = the unbatched executor); ``cond`` a *per-request* conditioning
    prototype (arrays or ``ShapeDtypeStruct`` leaves — only shapes/dtypes
    matter; the batch axis is prepended here, mirroring ``x``). Under
    classifier-free guidance the traced network eval carries a doubled
    lane count — warming with the right ``cond`` structure is what keeps
    the guided hot path trace-free. The compiled executable is stored on
    the bucket's compile-cache entry, so subsequent ``sample_batched`` /
    ``sample_sharded`` calls for the same bucket dispatch straight to it —
    no tracing on the serving hot path. Idempotent per bucket; returns the
    executable.
    """
    if mesh is not None:
        donate = _default_donate() if donate is None else donate

    def _cond_aval(c):
        sh = tuple(c.shape)
        if batch is not None:
            sh = (batch,) + sh
        return jax.ShapeDtypeStruct(sh, jnp.dtype(c.dtype))

    cond_s = None if cond is None else jax.tree.map(_cond_aval, cond)
    entry = _compiled(plan, model_fn, tuple(shape), dtype, trajectory,
                      batch, model_key=model_key, mesh=mesh,
                      data_axis=data_axis, cfg_axis=cfg_axis,
                      donate=bool(donate), cond=cond_s)
    if entry.aot is None:
        aval = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        arrays_s = jax.tree.map(aval, plan.arrays)
        params_s = jax.tree.map(aval, _model_params(model_fn))
        # key aval follows the configured PRNG impl (threefry: (2,) u32,
        # rbg: (4,) u32) — hardcoding would silently strand the AOT
        # executable behind _call's jit fallback
        proto = jax.random.PRNGKey(0)
        if batch is not None:
            x_s = jax.ShapeDtypeStruct((batch,) + tuple(shape),
                                       jnp.dtype(dtype))
            k_s = jax.ShapeDtypeStruct((batch,) + proto.shape, proto.dtype)
            s_s = jax.ShapeDtypeStruct((batch,), jnp.float32)
        else:
            x_s = jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))
            k_s = jax.ShapeDtypeStruct(proto.shape, proto.dtype)
            s_s = jax.ShapeDtypeStruct((), jnp.float32)
        entry.aot = entry.fn.lower(arrays_s, x_s, k_s, cond_s, s_s,
                                   params_s).compile()
    return entry.aot


# ------------------------------------------------------------ bound sampler
class Sampler:
    """A spec bound to its plan — the one-stop object callers hold.

    ``make_sampler("sa", nfe=20, tau=0.4)`` -> plan once, then
    ``.sample`` / ``.sample_batched`` reuse the shared compile cache.
    """

    def __init__(self, spec: SamplerSpec):
        self.spec = spec
        self.plan = build_plan(spec)
        self.schedule = spec.resolve_schedule()

    @property
    def nfe(self) -> int:
        return self.spec.nfe

    def sample(self, model_fn: ModelFn, x_T: jnp.ndarray, key: jax.Array,
               *, cond=None, guidance_scale=1.0, trajectory: bool = False,
               model_key: Hashable | None = None):
        return sample(self.plan, model_fn, x_T, key, cond=cond,
                      guidance_scale=guidance_scale, trajectory=trajectory,
                      model_key=model_key)

    def sample_batched(self, model_fn: ModelFn, x_T: jnp.ndarray,
                       keys: jax.Array, *, cond=None, guidance_scale=1.0,
                       trajectory: bool = False,
                       model_key: Hashable | None = None):
        return sample_batched(self.plan, model_fn, x_T, keys, cond=cond,
                              guidance_scale=guidance_scale,
                              trajectory=trajectory, model_key=model_key)

    def sample_sharded(self, model_fn: ModelFn, x_T: jnp.ndarray,
                       keys: jax.Array, *, mesh: Mesh,
                       data_axis: str = "data",
                       cfg_axis: str | None = None, cond=None,
                       guidance_scale=1.0, trajectory: bool = False,
                       model_key: Hashable | None = None,
                       donate: bool | None = None):
        return sample_sharded(self.plan, model_fn, x_T, keys, mesh=mesh,
                              data_axis=data_axis, cfg_axis=cfg_axis,
                              cond=cond, guidance_scale=guidance_scale,
                              trajectory=trajectory,
                              model_key=model_key, donate=donate)

    def init_noise(self, key: jax.Array, shape, dtype=jnp.float32):
        scale = self.schedule.prior_scale(float(self.plan.ts[0]))
        return scale * jax.random.normal(key, shape, dtype)

    def __repr__(self) -> str:
        return f"Sampler({self.spec!r})"


def make_sampler(name: str, **kw) -> Sampler:
    """Registry front door. ``nfe=`` routes through ``SamplerSpec.from_nfe``
    (per-family NFE -> steps conversion); all other keywords are
    ``SamplerSpec`` fields."""
    if "nfe" in kw:
        spec = SamplerSpec.from_nfe(name, kw.pop("nfe"), **kw)
    else:
        spec = SamplerSpec(name=name, **kw)
    return Sampler(spec)
