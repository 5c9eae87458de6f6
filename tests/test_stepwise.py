"""Step-granular executor: the scan step as the scheduling unit.

The load-bearing contract: driving a request tick-by-tick through
``make_stepfns``/``fresh_carry`` — including staggered mid-flight joins
into a shared carry — reproduces the whole-solve ``sample_batched``
executor. The scheduler machinery itself (join writes, masked carries,
lane recycling) is numerically transparent, so with a model whose own
evaluation is fusion-stable across compilation contexts the SA match is
**bitwise**. Two caveats the suite pins separately:

- an arbitrary model (here: the GMM score) may itself FMA-fuse
  differently inside ``lax.scan`` than in the per-step jit — that
  reassociation (~1 ulp per eval, compounding over steps) is a property
  of the model's XLA program, not of the scheduler, and is locked at
  float tolerance;
- the baseline families' scalar mul-add update chains reassociate the
  same way even with a stable model (SA's einsum contraction is the
  structurally stable one), so they are locked at float tolerance too.

Also covers: masked early exit on the predictor-vs-corrector residual
(tol <= 0 is exactly the disabled whole-solve trajectory), the ``ee_ok``
gating that keeps folded predictor-only program steps from spuriously
firing the exit, the step-function cache contract (tau/program sweeps
share one entry; lane-count changes do not), and the unregistered-family
error path.
"""

import dataclasses
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GMM, StepProgram, get_schedule
from repro.core.samplers import (SamplerSpec, build_plan,
                                 clear_stepwise_cache, fresh_carry,
                                 make_stepfns, sample_batched,
                                 stepwise_adapter, stepwise_cache_stats,
                                 stepwise_supported)

SCHED = get_schedule("vp_linear")
GMM_MODEL = GMM.default_2d().model_fn(SCHED, "data")
SHAPE = (48, 2)


def MODEL(x, t):
    """Fusion-stable denoiser: one multiply chain XLA compiles the same
    way in every context, isolating the scheduler's numerics."""
    return 0.3 * x * jnp.cos(t)


def _spec(**kw):
    kw.setdefault("name", "sa")
    kw.setdefault("schedule", SCHED)
    kw.setdefault("n_steps", 6)
    kw.setdefault("tau", 0.7)
    return SamplerSpec(**kw)


def _xt_keys(plan, n, dtype=jnp.float32):
    """Whole-solve inputs: per-request init noise + solve keys."""
    scale = plan.spec.resolve_schedule().prior_scale(float(plan.ts[0]))
    keys = jax.random.split(jax.random.PRNGKey(3), n)
    xT = jax.vmap(lambda k: scale * jax.random.normal(k, SHAPE,
                                                      dtype))(keys)
    return xT, jax.random.split(jax.random.PRNGKey(4), n)


def drive(plan, xT, solve_keys, *, model=MODEL, lanes=None, stagger=None,
          tol=0.0, min_i=0, stream=False, max_ticks=200):
    """Run every request through the step machinery to completion.

    ``stagger[b]`` delays request b's join until that tick — the shared
    carry keeps stepping earlier joiners in the meantime, which is
    exactly the continuous-batching interleave the bitwise contract must
    survive. Returns (x_final per request, n_steps per request, previews).
    """
    n = xT.shape[0]
    lanes = n if lanes is None else lanes
    stagger = [0] * n if stagger is None else list(stagger)
    fns = make_stepfns(plan, model, SHAPE, xT.dtype, lanes, stream=stream)
    arrays = fns.adapter.arrays(plan)
    M = fns.adapter.n_steps_of(arrays)
    carry = fresh_carry(plan, lanes, SHAPE, xT.dtype)
    done, steps = {}, {}
    previews = {b: [] for b in range(n)}
    owner = [None] * lanes  # lane -> request index
    for tick in range(max_ticks):
        for b in range(n):
            if stagger[b] == tick:
                lane = owner.index(None)
                owner[lane] = b
                carry = fns.join(
                    arrays, carry, lane, xT[b],
                    jax.random.split(solve_keys[b], M), tol, min_i, 1.0)
        if all(o is None for o in owner):
            if len(done) == n:
                break
            continue
        carry, aux = fns.step(arrays, carry)
        fin = jax.device_get(aux["finished"])
        stepped = jax.device_get(aux["stepped"])
        idx = jax.device_get(aux["i"])
        for lane, b in enumerate(owner):
            if b is None:
                continue
            if stream and stepped[lane]:
                previews[b].append(aux["x0"][lane])
            if fin[lane]:
                done[b] = np.asarray(carry["x_final"][lane])
                steps[b] = int(idx[lane])
                owner[lane] = None
    assert len(done) == n, f"unfinished after {max_ticks} ticks"
    return ([done[b] for b in range(n)], [steps[b] for b in range(n)],
            [previews[b] for b in range(n)])


def assert_matches_whole_solve(spec, *, bitwise, model=MODEL,
                               stagger=None, lanes=None,
                               dtype=jnp.float32):
    plan = build_plan(spec)
    xT, solve_keys = _xt_keys(plan, 3, dtype)
    ref = np.asarray(sample_batched(plan, model, xT, solve_keys))
    got, steps, _ = drive(plan, xT, solve_keys, model=model,
                          stagger=stagger, lanes=lanes)
    assert all(s == spec.n_steps for s in steps)
    for b in range(3):
        if bitwise:
            assert (ref[b] == got[b]).all(), f"request {b} diverged"
        else:
            np.testing.assert_allclose(
                ref[b], np.asarray(got[b], np.float32),
                rtol=2e-5, atol=2e-5)


# ------------------------------------------------------ SA bitwise parity
@pytest.mark.parametrize("mode,corr", [("PEC", 3), ("PEC", 0),
                                       ("PECE", 3), ("PECE", 1)])
def test_sa_stepwise_bitwise(mode, corr):
    """SA through the step machinery is byte-equal to the whole-solve
    scan — PEC/PECE, with and without a corrector."""
    assert_matches_whole_solve(_spec(mode=mode, corrector_order=corr),
                               bitwise=True)


@pytest.mark.parametrize("combine", ["kernel", "fused"])
def test_sa_stepwise_bitwise_combine_paths(combine):
    assert_matches_whole_solve(_spec(combine=combine), bitwise=True)


def test_sa_stepwise_bitwise_bf16_and_no_denoise():
    assert_matches_whole_solve(_spec(precision="bf16"), bitwise=True,
                               dtype=jnp.bfloat16)
    assert_matches_whole_solve(_spec(denoise_final=False), bitwise=True)


def test_sa_stepwise_bitwise_under_staggered_joins():
    """Mid-flight joins into a shared carry (other lanes mid-solve) must
    not perturb anyone: lanes are vmap-independent and the in-band init
    tick is pure per-lane data flow."""
    assert_matches_whole_solve(_spec(), bitwise=True,
                               stagger=[0, 3, 5], lanes=4)


def test_sa_stepwise_gmm_model_float_tolerance():
    """An arbitrary model's own eval may reassociate across compilation
    contexts (scan body vs per-step jit); the scheduler adds nothing
    beyond that — locked at float tolerance with the GMM score."""
    assert_matches_whole_solve(_spec(), bitwise=False, model=GMM_MODEL)


def test_sa_stepwise_bitwise_multi_segment_program():
    """A mode-switching program (P/PEC/PECE segments -> the per-step
    cond path) keeps the bitwise lock."""
    prog = StepProgram(mode=("P", "P", "PEC", "PEC", "PECE", "PECE"),
                       tau=(1.0, 1.0, 0.4, 0.4, 0.7, 0.7))
    assert_matches_whole_solve(_spec(program=prog), bitwise=True)


# --------------------------------------------------------- baseline parity
@pytest.mark.parametrize("name", ["ddim", "ddpm_ancestral",
                                  "dpm_solver_pp_2m", "euler_maruyama",
                                  "edm_heun", "edm_stochastic"])
def test_baseline_stepwise_matches_whole_solve(name):
    """Baselines match to float-reassociation level (XLA FMA-fuses their
    update chains differently across compilation contexts; SA's einsum
    is the structurally stable one)."""
    spec = _spec(name=name, tau=1.0)
    assert stepwise_supported(spec)
    assert_matches_whole_solve(spec, bitwise=False)


# -------------------------------------------------------------- early exit
def test_early_exit_fires_after_min_steps():
    """A generous tolerance retires lanes right at min_i; tol=0 lanes in
    the same carry run the full solve."""
    spec = _spec(n_steps=10, mode="PECE")
    plan = build_plan(spec)
    xT, solve_keys = _xt_keys(plan, 2)
    full, steps_full, _ = drive(plan, xT, solve_keys, tol=0.0, min_i=4)
    assert steps_full == [10, 10]
    early, steps_early, _ = drive(plan, xT, solve_keys, tol=1e3, min_i=4)
    assert steps_early == [4, 4]
    # the early sample is the corrector output at its exit step — finite
    # and different from the full solve
    for b in range(2):
        assert np.isfinite(early[b]).all()
        assert not (early[b] == full[b]).all()


def test_early_exit_disabled_is_exact():
    """tol <= 0 can never fire (err >= 0 is never < 0), so the early-exit
    machinery adds nothing to the disabled path."""
    spec = _spec(n_steps=5)
    plan = build_plan(spec)
    xT, solve_keys = _xt_keys(plan, 2)
    a, _, _ = drive(plan, xT, solve_keys, tol=0.0)
    b, _, _ = drive(plan, xT, solve_keys, tol=-1.0, min_i=0)
    ref = np.asarray(sample_batched(plan, MODEL, xT, solve_keys))
    for i in range(2):
        assert (a[i] == ref[i]).all() and (b[i] == ref[i]).all()


def test_predictor_only_steps_never_fire_exit():
    """On P-mode program steps there is no corrector, so the residual is
    degenerate; the ee_ok gate must hold the exit open only on PEC/PECE
    steps. With an all-P program even a huge tol never exits early."""
    prog = StepProgram(mode=("P",) * 6, tau=0.7)
    plan = build_plan(_spec(program=prog))
    xT, solve_keys = _xt_keys(plan, 2)
    _, steps, _ = drive(plan, xT, solve_keys, tol=float("inf"), min_i=0)
    assert steps == [6, 6]


# ------------------------------------------------------------- stream mode
def test_stream_previews_per_step():
    spec = _spec(n_steps=5)
    plan = build_plan(spec)
    xT, solve_keys = _xt_keys(plan, 2)
    _, _, previews = drive(plan, xT, solve_keys, stagger=[0, 2], lanes=2,
                           stream=True)
    for p in previews:
        assert len(p) == 5  # one per real step; the init tick emits none
        assert all(np.isfinite(np.asarray(x, np.float32)).all() for x in p)


# ------------------------------------------------------------ cache contract
def test_cache_shared_across_tau_and_program_data():
    """Specs differing only in tau / per-interval program orders resolve
    to ONE step-function entry — their differences are table data."""
    clear_stepwise_cache()
    base = _spec(n_steps=6)
    fns = make_stepfns(build_plan(base), MODEL, SHAPE, jnp.float32, 4)
    assert stepwise_cache_stats()["misses"] == 1
    # lower-order program tracks shrink the table/buffer width (an aval
    # change) unless the program floors it back with width=
    for spec in (base.replace(tau=0.2), base.replace(tau=1.1),
                 base.replace(program=StepProgram(tau=0.5)),
                 base.replace(program=StepProgram(predictor_order=2,
                                                  corrector_order=2,
                                                  tau=0.9, width=3))):
        got = make_stepfns(build_plan(spec), MODEL, SHAPE, jnp.float32, 4)
        assert got is fns, f"{spec} split the step-function cache"
    s = stepwise_cache_stats()
    assert s["misses"] == 1 and s["hits"] == 4 and s["size"] == 1
    # lane count IS aval-relevant: a different batch is a new entry
    make_stepfns(build_plan(base), MODEL, SHAPE, jnp.float32, 8)
    assert stepwise_cache_stats()["misses"] == 2


def test_warm_is_aot_and_idempotent():
    plan = build_plan(_spec(n_steps=4))
    fns = make_stepfns(plan, MODEL, SHAPE, jnp.float32, 2)
    arrays = fns.adapter.arrays(plan)
    carry = fresh_carry(plan, 2, SHAPE, jnp.float32)
    assert not fns.warmed
    fns.warm(arrays, carry)
    assert fns.warmed
    fns.warm(arrays, carry)  # no-op
    carry2, aux = fns.step(arrays, carry)  # all-free carry still steps
    assert not jax.device_get(aux["finished"]).any()
    assert not jax.device_get(carry2["active"]).any()


# ----------------------------------------------------------------- errors
def test_family_without_adapter_raises():
    """A family registered without a stepwise builder serves only through
    the whole-solve scheduler; asking for its step adapter is a clear
    error."""
    from repro.core.samplers.base import (SamplerFamily, _REGISTRY,
                                          register_sampler)
    fam = SamplerFamily(
        name="__scan_only__", plan=lambda s: ({}, {}),
        execute=lambda *a, **k: None, statics=lambda s: (),
        nfe_of=lambda s: s.n_steps, steps_from_nfe=lambda n, kw: n)
    register_sampler(fam)
    try:
        spec = _spec(name="__scan_only__")
        assert not stepwise_supported(spec)
        with pytest.raises(ValueError, match="no step-granular adapter"):
            stepwise_adapter(spec)
    finally:
        _REGISTRY.pop("__scan_only__", None)


def test_adapter_reports_in_band_init():
    adapter = stepwise_adapter(_spec())
    assert adapter.i0 == -1  # SA warm-up eval runs as the first tick
    assert stepwise_adapter(_spec(name="ddim")).i0 == 0


# ------------------------------------------------- step-path weight copies
DIT_SHAPE = (16, 8)  # the smoke DiT's latent: 16 tokens x 8 channels


def _dit(seed=0):
    """A Denoiser over the 2-block smoke DiT, its f32 weights perturbed
    off bf16 values (and the adaLN-zero projections off zero)."""
    from repro.configs import get_smoke
    from repro.core import Denoiser
    from repro.launch.sample import as_prediction_network
    from repro.models import build_model, init_params
    model = build_model(get_smoke("dit-s"))
    params = init_params(jax.random.PRNGKey(seed), model.param_defs(),
                         jnp.float32)
    params = jax.tree.map(
        lambda p: p + 0.02 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                               p.shape, p.dtype), params)
    net = as_prediction_network(model, SCHED, "eps")
    return Denoiser(net, SCHED, prediction="eps", params=params)


def _dit_ticks(den, ticks=3, lanes=2):
    """Warm a step function for ``den``, join two requests and run
    ``ticks`` ticks; returns the carry's leaves as numpy arrays."""
    plan = build_plan(_spec(n_steps=4))
    fns = make_stepfns(plan, den, DIT_SHAPE, jnp.float32, lanes)
    arrays = fns.adapter.arrays(plan)
    carry = fresh_carry(plan, lanes, DIT_SHAPE, jnp.float32)
    fns.warm(arrays, carry)
    M = fns.adapter.n_steps_of(arrays)
    for lane in range(lanes):
        k = jax.random.PRNGKey(10 + lane)
        carry = fns.join(arrays, carry, lane,
                         jax.random.normal(k, DIT_SHAPE),
                         jax.random.split(jax.random.fold_in(k, 1), M),
                         0.0, 0, 1.0)
    for _ in range(ticks):
        carry, _ = fns.step(arrays, carry)
    return [np.asarray(a) for a in jax.tree.leaves(carry)]


@pytest.fixture
def copies_on(monkeypatch):
    """The step path as on a backend whose default-precision f32 dots
    round to bf16 (the TPU)."""
    from repro.core.samplers import stepwise
    monkeypatch.setattr(stepwise, "_dots_round_to_bf16", lambda: True)
    clear_stepwise_cache()
    yield stepwise
    clear_stepwise_cache()


def test_weight_copy_tick_is_the_program_on_prerounded_weights(copies_on):
    """With the copies on, a tick reads the copies upcast to f32: bit for
    bit the unchanged step program on weights rounded to bf16 ahead of
    time and held as f32, which is what a TPU's default dot feeds the
    MXU from the f32 weights."""
    den = _dit()
    dots = den.network.dot_weights
    assert any(jax.tree.leaves(dots)) and not all(jax.tree.leaves(dots))
    got = _dit_ticks(den)
    pre = jax.tree.map(
        lambda p, d: p.astype(jnp.bfloat16).astype(jnp.float32) if d else p,
        den.params, dots)
    copies_on._dots_round_to_bf16 = lambda: False
    ref = _dit_ticks(dataclasses.replace(den, params=pre))
    assert all((a == b).all() for a, b in zip(got, ref))
    # and the rounding is real: the f32 weights give another tick
    full = _dit_ticks(den)
    assert any((a != b).any() for a, b in zip(got, full))


def test_weight_copy_made_once_per_weights_and_freed(copies_on):
    den = _dit()
    _dit_ticks(den, ticks=4)
    s = stepwise_cache_stats()
    assert s["weight_casts"] == 1, s
    sw = copies_on._step_weights(den)
    copy = weakref.ref(next(w for w, r in zip(sw.leaves, sw.rounded) if r))
    assert stepwise_cache_stats()["weight_casts"] == 1
    assert {str(w.dtype) for w, r in zip(sw.leaves, sw.rounded) if r} \
        == {"bfloat16"}
    assert all(w.dtype == jnp.float32 for w in jax.tree.leaves(den.params))
    del sw
    den2 = _dit(seed=5)
    _dit_ticks(den2, ticks=2)
    assert stepwise_cache_stats()["weight_casts"] == 2
    n_copies = len(copies_on._ROUNDED)
    del den
    gc.collect()
    assert copy() is None, "the old weights' copy outlived them"
    assert 0 < len(copies_on._ROUNDED) < n_copies


def test_weight_copy_off_on_cpu():
    """On the CPU a default dot multiplies in full f32: the step program
    takes the f32 weights unchanged and no copy is made."""
    from repro.core.samplers import stepwise
    clear_stepwise_cache()
    den = _dit()
    assert not stepwise._dots_round_to_bf16()
    assert stepwise._step_weights(den) is den.params
    _dit_ticks(den)
    assert stepwise_cache_stats()["weight_casts"] == 0
    assert not stepwise._ROUNDED


def test_solve_path_makes_no_weight_copy(copies_on):
    den = _dit()
    plan = build_plan(_spec(n_steps=4))
    keys = jax.random.split(jax.random.PRNGKey(0), 2)
    xT = jax.random.normal(jax.random.PRNGKey(1), (2,) + DIT_SHAPE)
    out = sample_batched(plan, den, xT, keys)
    assert np.isfinite(np.asarray(out)).all()
    assert stepwise_cache_stats()["weight_casts"] == 0
    assert not copies_on._ROUNDED
