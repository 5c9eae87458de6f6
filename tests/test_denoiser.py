"""Denoiser adapter layer: prediction-type conversion, classifier-free
guidance, and the cond/scale threading from executors to serving.

The analytic ground truth is the GMM oracle (``repro.core.oracle`` /
``repro.kernels.ref.denoiser_oracles``): the same closed-form posterior
expressed as an eps-, x0-, and v-prediction network, optionally
conditioned by an exact mean shift — so every adapter identity has an
exact reference. Bitwise contracts: same-convention wrapping is a
pass-through, and guidance scale 1.0 equals the unguided path (including
through ``serve``'s bucketing) by construction of the
``(1-s)*uncond + s*cond`` combine.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import GMM, Denoiser, convert_prediction, get_schedule
from repro.core.samplers import (SamplerSpec, build_plan,
                                 clear_compile_cache, compile_cache_stats,
                                 make_sampler, sample, sample_batched)
from repro.kernels.ref import denoiser_oracles
from repro.serve import Request, ServeEngine, bucket_key

SCHED = get_schedule("vp_linear")
GMM2 = GMM.default_2d()
NETS = denoiser_oracles(SCHED, GMM2)
XT = jax.random.normal(jax.random.PRNGKey(9), (256, 2))
KEY = jax.random.PRNGKey(0)
SPEC = SamplerSpec(name="sa", schedule=SCHED, n_steps=8, tau=0.7)
COND = jnp.asarray([0.8, -0.4], jnp.float32)


def serve_rids(engine, submits, spec, shape=(64, 2)):
    """submits: list of (rid, cond, scale)."""
    for rid, cond, scale in submits:
        engine.submit(spec, shape, rid=rid, cond=cond, guidance_scale=scale)
    return {res.rid: np.asarray(res.x0) for res in engine.run()}


# ------------------------------------------------- conversion identities
@pytest.mark.parametrize("src,dst", [
    ("eps", "x0"), ("x0", "eps"), ("v", "x0"), ("v", "eps"),
    ("x0", "v"), ("eps", "v"),
])
def test_convert_prediction_matches_analytic_oracle(src, dst):
    """Converting the src-convention oracle output must land on the
    dst-convention oracle output — the GMM gives every convention in
    closed form from one posterior."""
    t = jnp.float32(0.41)
    x = XT[:64]
    oracle = {
        "x0": GMM2.x0_prediction, "eps": GMM2.eps_prediction,
        "v": GMM2.v_prediction,
    }
    got = convert_prediction(oracle[src](SCHED, x, t), x, t, src, dst, SCHED)
    want = oracle[dst](SCHED, x, t)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_convert_prediction_aliases_and_passthrough():
    t = jnp.float32(0.5)
    x = XT[:32]
    p = GMM2.x0_prediction(SCHED, x, t)
    assert convert_prediction(p, x, t, "data", "x0", SCHED) is p
    assert convert_prediction(p, x, t, "x0", "data", SCHED) is p
    with pytest.raises(ValueError, match="unknown prediction"):
        convert_prediction(p, x, t, "nope", "x0", SCHED)


# ------------------------------------------- wrapped solves (eps/x0/v)
@pytest.mark.parametrize("pred", ["x0", "eps", "v"])
def test_all_prediction_wrappings_reach_same_solve(pred):
    """One planned SA spec samples an eps-, x0-, and v-prediction
    denoiser: all three wrap the same ground truth, so the solves agree
    (to f32 conversion round-off; x0 is exactly the plain path)."""
    plan = build_plan(SPEC)
    base = sample(plan, GMM2.model_fn(SCHED, "data"), XT, KEY)
    d = Denoiser(NETS[pred], SCHED, prediction=pred)
    out = sample(plan, d, XT, KEY)
    if pred == "x0":
        assert bool(jnp.all(out == base)), "x0 wrapping must pass through"
    else:
        np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                                   rtol=5e-4, atol=5e-4)


def test_noise_parameterization_target_conversion():
    """The adapter converts *to* the plan's convention, not just to x0:
    an x0 network wrapped for a noise-parameterization SA plan matches
    the native eps-model run."""
    spec = SPEC.replace(parameterization="noise", denoise_final=False)
    plan = build_plan(spec)
    base = sample(plan, GMM2.model_fn(SCHED, "noise"), XT, KEY)
    out = sample(plan, Denoiser(NETS["x0"], SCHED, prediction="x0"),
                 XT, KEY)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=5e-4, atol=5e-4)
    # and the eps wrapping of a noise-parameterization plan passes through
    out_eps = sample(plan, Denoiser(NETS["eps"], SCHED, prediction="eps"),
                     XT, KEY)
    assert bool(jnp.all(out_eps == base))


def test_plain_model_fn_with_spec_prediction_converts():
    """spec.prediction adapts even a plain (x, t) model_fn — an eps
    checkpoint works against a data-parameterization plan with no
    Denoiser wrapper (unconditional, unguided case)."""
    plan = build_plan(SPEC.replace(prediction="eps"))
    base = sample(build_plan(SPEC), GMM2.model_fn(SCHED, "data"), XT, KEY)
    out = sample(plan, GMM2.model_fn(SCHED, "noise"), XT, KEY)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=5e-4, atol=5e-4)


# --------------------------------------------------- guidance contracts
def test_guidance_scale_one_bitwise_equals_unguided():
    """scale 1.0 must be bitwise the unguided conditional path: the
    (1-s)*u + s*c combine makes the cond branch exact at s=1."""
    spec_g = SPEC.replace(guidance=True, prediction="eps")
    d_g = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    d_u = Denoiser(NETS["eps"], SCHED, prediction="eps")
    guided = sample(build_plan(spec_g), d_g, XT, KEY, cond=COND,
                    guidance_scale=1.0)
    unguided = sample(build_plan(SPEC.replace(prediction="eps")), d_u, XT,
                      KEY, cond=COND)
    assert bool(jnp.all(guided == unguided))


def test_guidance_scale_one_bitwise_through_serve_bucketing():
    """Acceptance: the bitwise s=1.0 contract survives the serving path
    (stacked lanes, pad slots, per-lane scale vectors)."""
    d_g = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    d_u = Denoiser(NETS["eps"], SCHED, prediction="eps")
    spec_g = SPEC.replace(guidance=True, prediction="eps")
    spec_u = SPEC.replace(prediction="eps")
    got_g = serve_rids(ServeEngine(d_g, bucket_sizes=(4,)),
                       [(r, COND * r, 1.0) for r in range(3)], spec_g)
    got_u = serve_rids(ServeEngine(d_u, bucket_sizes=(4,)),
                       [(r, COND * r, 1.0) for r in range(3)], spec_u)
    for r in range(3):
        assert (got_g[r] == got_u[r]).all(), f"rid {r} diverged"


def test_guided_eval_is_one_fused_network_call():
    """CFG must run cond/uncond as ONE vmapped network eval over a
    stacked leading axis — not two sequential calls. A per-eval runtime
    callback fires once per *fused* call (vmap batches it), so a guided
    solve shows exactly spec.nfe network dispatches, not 2x."""
    calls = []

    def probing_net(x, t, cond):
        jax.debug.callback(lambda: calls.append(1))
        return NETS["eps"](x, t, cond)

    d = Denoiser(probing_net, SCHED, prediction="eps", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="eps", n_steps=4)
    jax.block_until_ready(
        sample(build_plan(spec), d, XT[:32], KEY, cond=COND,
               guidance_scale=2.0))
    jax.effects_barrier()
    assert len(calls) == spec.nfe, (
        f"{len(calls)} network dispatches for {spec.nfe} guided evals — "
        "cond/uncond branches are not fused")


def test_guidance_moves_samples_toward_cond_shift():
    """Scale > 1 extrapolates toward the conditional branch: with a mean
    shift as conditioning, higher scale pushes the sample mean further
    along the shift than the unguided solve."""
    d = Denoiser(NETS["x0"], SCHED, prediction="x0", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="x0")
    plan = build_plan(spec)
    shift = jnp.asarray([3.0, 3.0], jnp.float32)
    lo = sample(plan, d, XT, KEY, cond=shift, guidance_scale=0.0)
    hi = sample(plan, d, XT, KEY, cond=shift, guidance_scale=2.0)
    proj = lambda z: float(jnp.mean(z @ (shift / jnp.linalg.norm(shift))))
    assert proj(hi) > proj(lo) + 1.0


def test_network_nfe_accounting():
    spec = SPEC.replace(guidance=True)
    assert spec.nfe == SPEC.nfe
    assert spec.network_nfe == 2 * SPEC.nfe
    assert SPEC.network_nfe == SPEC.nfe  # unguided: 1:1


# -------------------------------------------------- compile-cache contract
def test_guidance_scale_sweep_zero_compile_misses():
    """Acceptance: the scale is traced data — a sweep at fixed step count
    adds zero compile-cache misses after the first call."""
    clear_compile_cache()
    d = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    plan = build_plan(SPEC.replace(guidance=True, prediction="eps"))
    traces = {"n": 0}

    def traced_net(x, t, cond):
        traces["n"] += 1  # python body runs only while tracing
        return NETS["eps"](x, t, cond)

    d = Denoiser(traced_net, SCHED, prediction="eps", guidance=True)
    for s in (0.0, 0.5, 1.0, 2.0, 7.5):
        sample(plan, d, XT[:64], KEY, cond=COND, guidance_scale=s)
    stats = compile_cache_stats()
    assert stats["misses"] == 1, stats
    assert stats["hits"] == 4
    first = traces["n"]
    sample(plan, d, XT[:64], KEY, cond=jnp.ones(2), guidance_scale=3.3)
    assert traces["n"] == first, "new cond values re-traced"


def test_serve_guidance_sweep_zero_misses_after_warmup():
    """The serving hot path stays trace-free across a guidance-scale
    sweep: scales ride the warmed executable as data."""
    clear_compile_cache()
    d = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="eps")
    engine = ServeEngine(d, bucket_sizes=(4,))
    serve_rids(engine, [(r, COND, 2.0) for r in range(4)], spec)
    warmed = compile_cache_stats()
    assert warmed["misses"] == 1
    for i, s in enumerate((0.0, 0.7, 1.0, 1.5, 4.0)):
        serve_rids(engine, [(10 * i + r, COND * r, s) for r in range(4)],
                   spec)
    after = compile_cache_stats()
    assert after["misses"] == warmed["misses"], \
        "guidance sweep re-compiled the serving hot path"


def test_distinct_prediction_types_get_distinct_executors():
    """prediction type and guidance flag are statics: each combination
    owns a compile-cache entry (never silently shares a wrong graph)."""
    clear_compile_cache()
    plan = build_plan(SPEC)
    for pred in ("x0", "eps", "v"):
        sample(plan, Denoiser(NETS[pred], SCHED, prediction=pred),
               XT[:64], KEY)
    assert compile_cache_stats()["misses"] == 3


# ------------------------------------------------------- serve threading
def test_serve_per_request_cond_and_scale_in_one_bucket():
    """Requests differing only in cond values / scale share one bucket
    (one executor) yet produce distinct, rid-replayable samples."""
    clear_compile_cache()
    d = Denoiser(NETS["x0"], SCHED, prediction="x0", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="x0")
    engine = ServeEngine(d, bucket_sizes=(4,))
    got = serve_rids(engine, [(0, COND, 2.0), (1, -COND, 2.0),
                              (2, COND, 0.0), (3, COND, 2.0)], spec)
    assert engine.stats()["microbatches"] == 1
    assert compile_cache_stats()["misses"] == 1
    assert not (got[0] == got[1]).all()  # different cond
    assert not (got[0] == got[2]).all()  # different scale
    # replay: the same rid + cond + scale reproduces the same bytes even
    # when re-bucketed with different neighbours
    again = serve_rids(engine, [(0, COND, 2.0), (7, COND, 5.0)], spec)
    assert (got[0] == again[0]).all()


def test_serve_ragged_guided_bucket_matches_solo():
    """Masked pad lanes (zero cond, scale 1) never perturb real guided
    requests: ragged == solo, bitwise."""
    d = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="eps")
    engine = ServeEngine(d, bucket_sizes=(4,))
    ragged = serve_rids(engine, [(r, COND, 3.0) for r in range(3)], spec)
    assert engine.stats()["padded_slots"] == 1
    for r in range(3):
        solo = serve_rids(engine, [(r, COND, 3.0)], spec)
        assert (ragged[r] == solo[r]).all(), f"rid {r} diverged"


def test_serve_network_evals_accounting():
    d = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="eps")
    engine = ServeEngine(d, bucket_sizes=(4,))
    serve_rids(engine, [(r, COND, 2.0) for r in range(5)], spec)
    s = engine.stats()
    assert s["model_evals"] == 5 * spec.nfe
    assert s["network_evals"] == 2 * s["model_evals"]


def test_bucket_key_splits_on_cond_structure_not_values():
    r_a = Request(0, SPEC, (64, 2), cond=COND)
    r_b = Request(1, SPEC, (64, 2), cond=COND * 5, guidance_scale=9.0)
    r_c = Request(2, SPEC, (64, 2), cond=jnp.ones((3,)))   # other shape
    r_d = Request(3, SPEC, (64, 2), cond=None)             # unconditional
    assert bucket_key(r_a) == bucket_key(r_b)
    assert bucket_key(r_a) != bucket_key(r_c)
    assert bucket_key(r_a) != bucket_key(r_d)


def test_serve_guided_mesh_matches_unsharded():
    """The sharded path threads cond + per-lane scales with NamedSharding
    placements: a one-device mesh serves the same guided bytes as the
    unsharded engine."""
    from repro.launch.mesh import make_test_mesh
    d = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    spec = SPEC.replace(guidance=True, prediction="eps")
    mesh = make_test_mesh((1, 1), ("data", "model"))
    subs = [(r, COND * r, 2.0 + r) for r in range(3)]
    plain = serve_rids(ServeEngine(d, bucket_sizes=(4,)), subs, spec)
    shard = serve_rids(ServeEngine(d, bucket_sizes=(4,), mesh=mesh),
                       subs, spec)
    for r in range(3):
        np.testing.assert_allclose(plain[r], shard[r], rtol=1e-6,
                                   atol=1e-6)


# ------------------------------------------------------------ validation
def test_plain_model_fn_rejects_guidance_and_cond():
    plan = build_plan(SPEC.replace(guidance=True))
    with pytest.raises(ValueError, match="needs a Denoiser"):
        sample(plan, GMM2.model_fn(SCHED, "data"), XT[:32], KEY)
    with pytest.raises(ValueError, match="requires a Denoiser"):
        sample(build_plan(SPEC), GMM2.model_fn(SCHED, "data"), XT[:32],
               KEY, cond=COND)
    # a non-default scale must never be silently dropped
    with pytest.raises(ValueError, match="guidance_scale"):
        sample(build_plan(SPEC), GMM2.model_fn(SCHED, "data"), XT[:32],
               KEY, guidance_scale=2.0)
    d_unguided = Denoiser(NETS["eps"], SCHED, prediction="eps")
    with pytest.raises(ValueError, match="guidance_scale"):
        sample(build_plan(SPEC.replace(prediction="eps")), d_unguided,
               XT[:32], KEY, cond=COND, guidance_scale=3.0)


def test_spec_denoiser_mismatch_rejected():
    d = Denoiser(NETS["eps"], SCHED, prediction="eps", guidance=True)
    with pytest.raises(ValueError, match="guidance"):
        sample(build_plan(SPEC), d, XT[:32], KEY)  # spec.guidance False
    d2 = Denoiser(NETS["eps"], SCHED, prediction="eps")
    with pytest.raises(ValueError, match="prediction"):
        sample(build_plan(SPEC.replace(prediction="v")), d2, XT[:32], KEY)


# ------------------------------------------------- batched + per-request
def test_sample_batched_per_request_cond_and_scale():
    """The vmapped executor threads a [K]-leading cond and scale: each
    lane solves its own guided problem, matching unbatched solves."""
    d = Denoiser(NETS["x0"], SCHED, prediction="x0", guidance=True)
    plan = build_plan(SPEC.replace(guidance=True, prediction="x0"))
    K = 3
    keys = jax.random.split(KEY, K)
    xts = jnp.stack([XT[:64]] * K)
    conds = jnp.stack([COND, -COND, 2 * COND])
    scales = jnp.asarray([0.0, 1.0, 3.0])
    out = sample_batched(plan, d, xts, keys, cond=conds,
                         guidance_scale=scales)
    for i in range(K):
        one = sample(plan, d, xts[i], keys[i], cond=conds[i],
                     guidance_scale=float(scales[i]))
        np.testing.assert_allclose(np.asarray(out[i]), np.asarray(one),
                                   rtol=2e-5, atol=2e-5)


# ------------------------------------------ trajectory preview (SA fix)
def test_sa_pec_corrector_preview_reconstructs_from_eval_state():
    """Noise-parameterization preview regression: under PEC + corrector
    the model is evaluated at x_pred, but the carried state is the
    corrected x_next. The streamed x0 preview must be reconstructed from
    the state the eval actually saw — for the exact eps oracle that makes
    every preview equal the analytic posterior mean at that state (the
    old x_next-based reconstruction diverged by (x_next - x_pred)/alpha,
    unbounded at early steps)."""
    recorded = []

    def recording_eps(x, t):
        jax.debug.callback(
            lambda tv, xv: recorded.append((float(tv), np.asarray(xv))),
            t, x)
        return GMM2.eps_prediction(SCHED, x, t)

    n = 8
    s = make_sampler("sa", schedule=SCHED, n_steps=n, tau=0.4,
                     parameterization="noise", predictor_order=3,
                     corrector_order=3, denoise_final=False)
    _, traj = s.sample(recording_eps, XT[:64], KEY, trajectory=True)
    jax.block_until_ready(traj["x0"])
    jax.effects_barrier()
    assert len(recorded) == n + 1  # init eval + one per PEC step
    by_t = {round(tv, 6): xv for tv, xv in recorded}
    ts32 = np.asarray(s.plan.ts, np.float32)
    for i in range(n):
        t_next = ts32[i + 1]
        x_eval = by_t[round(float(t_next), 6)]
        want = GMM2.x0_prediction(SCHED, jnp.asarray(x_eval),
                                  jnp.float32(t_next))
        np.testing.assert_allclose(
            np.asarray(traj["x0"][i]), np.asarray(want), rtol=5e-3,
            atol=5e-3, err_msg=f"preview at step {i} is not the x0 "
            "posterior at the evaluated state")


# ------------------------------------------- weights as an argument
def _weighted_net(p, x, t, cond):
    h = x if cond is None else x + cond
    return 0.5 * jnp.tanh(h @ p["w"]) @ p["v"]


def _weights(seed):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w": 0.3 * jax.random.normal(k1, (2, 64)),
            "v": 0.3 * jax.random.normal(k2, (64, 2))}


def test_params_are_an_executor_argument():
    """A Denoiser built with ``params=`` hands its weights to the compiled
    executor as an argument: new weights under one model_key reuse the
    executable (one miss, one hit) and change the result, and the result
    equals the same network closing over its weights."""
    spec = SamplerSpec(name="sa", schedule=SCHED, n_steps=6, tau=0.5,
                       guidance=True)
    keys = jax.random.split(KEY, 4)
    xs = XT[:64].reshape(4, 16, 2)
    cond = jnp.broadcast_to(COND, (4, 2))
    p1, p2 = _weights(1), _weights(2)
    run = lambda den, **kw: sample_batched(
        build_plan(spec), den, xs, keys, cond=cond, guidance_scale=2.0,
        **kw)
    clear_compile_cache()
    a = run(Denoiser(_weighted_net, SCHED, "x0", True, params=p1),
            model_key="weights-arg")
    b = run(Denoiser(_weighted_net, SCHED, "x0", True, params=p2),
            model_key="weights-arg")
    stats = compile_cache_stats()
    assert stats["misses"] == 1 and stats["hits"] == 1, stats
    assert not bool(jnp.all(a == b))
    closed = Denoiser(lambda x, t, c: _weighted_net(p1, x, t, c), SCHED,
                      "x0", True)
    np.testing.assert_allclose(np.asarray(run(closed)), np.asarray(a),
                               rtol=1e-6, atol=1e-6)


def test_params_denoiser_serves_on_both_schedulers():
    """The step scheduler's compiled step function takes the weights as
    an argument too, and serves what the solve scheduler serves."""
    spec = SamplerSpec(name="sa", schedule=SCHED, n_steps=6, tau=0.5,
                       guidance=True)
    den = Denoiser(_weighted_net, SCHED, "x0", True, params=_weights(3))
    submits = [(r, COND, 1.5) for r in range(3)]
    solve = serve_rids(ServeEngine(den, bucket_sizes=(4,)), submits, spec,
                       (16, 2))
    step = serve_rids(ServeEngine(den, scheduler="step", lanes=2), submits,
                      spec, (16, 2))
    for r in solve:
        np.testing.assert_allclose(step[r], solve[r], rtol=1e-5, atol=1e-5)
