"""End-to-end DiT sampling hot path (PR: bf16 fused ring + sharded CFG
+ feature caching).

Covers: the tame contractive DiT fixture (the regime in which caching
quality deltas are meaningful at all); DeepCache-style ``denoise_cached``
exactness on refresh and bounded drift on reuse; ``feature_cache`` plan
arrays + spec validation; solve-level quality bounds for both cache
policies; the zero-miss compile-cache contract across tau x guidance x
threshold sweeps on a guided+cached Denoiser; and sharded classifier-free
guidance bitwise equivalence (in a subprocess so the fake-device count
doesn't leak into this suite).
"""

import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Denoiser, get_schedule
from repro.core.samplers import (SamplerSpec, Sampler, build_plan,
                                 clear_compile_cache, compile_cache_stats)
from repro.models.tame import tame_dit, tame_networks

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCHED = get_schedule("vp_linear")


def run_sub(code: str, n_dev: int = 8):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def tame_denoiser(n_layers=4, **den_kw):
    model, params, mu = tame_dit(n_layers=n_layers)
    network, cached = tame_networks(model, params, mu)
    return Denoiser(network, SCHED, prediction="x0", cached=cached,
                    **den_kw), model, params, mu


# --------------------------------------------------------- tame fixture
def test_tame_dit_is_contractive():
    """The fixture's whole point: Jacobian gain < 1 at every t, so a
    cache-induced perturbation DECAYS through the solve instead of being
    amplified by the rms_norm/adaLN feedback of a random net."""
    den, _, _, _ = tame_denoiser(n_layers=8)
    x = Sampler(SamplerSpec.from_nfe("sa", 6, schedule=SCHED)).init_noise(
        jax.random.PRNGKey(0), (2, 16, 8))
    v = jax.random.normal(jax.random.PRNGKey(1), x.shape)
    for t in (0.95, 0.5, 0.1):
        _, jv = jax.jvp(lambda h: den.network(h, jnp.float32(t), None),
                        (x,), (v,))
        gain = float(jnp.linalg.norm(jv) / jnp.linalg.norm(v))
        assert gain < 1.0, (t, gain)


# ----------------------------------------------- denoise_cached exactness
def test_denoise_cached_refresh_matches_denoise():
    _, model, params, _ = tame_denoiser()
    z = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 8))
    full = model.denoise(params, z, 0.5)
    aval = model.feature_shape(2, 16)
    feats0 = jnp.zeros(aval.shape, aval.dtype)
    # refresh=True (Python bool -> specialized graph) recomputes every
    # block: same math as denoise up to re-fusion of the feature write
    out, feats = model.denoise_cached(params, z, 0.5, feats=feats0,
                                      refresh=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full),
                               atol=1e-6, rtol=1e-6)
    assert float(jnp.max(jnp.abs(feats))) > 0  # features were written
    # reuse at the SAME input reproduces the full eval (shallow + deep
    # recompute, middle span replayed from the cached residual)
    out_c, feats_c = model.denoise_cached(params, z, 0.5, feats=feats,
                                          refresh=False)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(full),
                               atol=1e-5, rtol=1e-5)
    assert (np.asarray(feats_c) == np.asarray(feats)).all(), \
        "cached eval must pass feats through untouched"
    # traced refresh flag (lax.cond dispatch) agrees with both branches
    f = jax.jit(lambda z, fe, r: model.denoise_cached(params, z, 0.5,
                                                      feats=fe, refresh=r))
    for flag, want in ((True, out), (False, out_c)):
        got, _ = f(z, feats, jnp.asarray(flag))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------- feature-cache planning
def test_feature_cache_plan_arrays():
    base = SamplerSpec.from_nfe("sa", 9, schedule=SCHED, tau=0.4)
    plan = build_plan(dataclasses.replace(base, feature_cache=3))
    refresh = np.asarray(plan.arrays["fc_refresh"])
    assert (refresh == ((np.arange(len(refresh)) + 1) % 3 == 0)).all()
    assert not np.isfinite(plan.arrays["fc_thresh"])  # interval: unused
    plan_r = build_plan(dataclasses.replace(base,
                                            feature_cache=("residual", 0.07)))
    refresh_r = np.asarray(plan_r.arrays["fc_refresh"])
    assert refresh_r[0] and not refresh_r[1:].any()
    assert float(plan_r.arrays["fc_thresh"]) == pytest.approx(0.07)


def test_feature_cache_spec_validation():
    base = SamplerSpec.from_nfe("sa", 8, schedule=SCHED)
    with pytest.raises(ValueError, match="interval must be >= 1"):
        build_plan(dataclasses.replace(base, feature_cache=0))
    with pytest.raises(ValueError, match="history='ring'"):
        build_plan(dataclasses.replace(base, feature_cache=2,
                                       history="concat"))
    with pytest.raises(ValueError, match="corrector_order > 0"):
        build_plan(dataclasses.replace(base, corrector_order=0,
                                       feature_cache=("residual", 0.05)))
    with pytest.raises(ValueError, match="expected None"):
        build_plan(dataclasses.replace(base, feature_cache="yes"))


# ------------------------------------------------- solve-level quality
def test_feature_cache_interval_one_matches_uncached():
    """k=1 refreshes every step: the cached executor degenerates to the
    plain one up to re-fusion noise."""
    den, _, _, _ = tame_denoiser()
    spec0 = SamplerSpec.from_nfe("sa", 6, schedule=SCHED, tau=0.0)
    xT = Sampler(spec0).init_noise(jax.random.PRNGKey(3), (2, 16, 8))
    key = jax.random.PRNGKey(4)
    ref = Sampler(spec0).sample(den, xT, key)
    out = Sampler(dataclasses.replace(spec0, feature_cache=1)).sample(
        den, xT, key)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("fc", [2, ("residual", 0.05)])
def test_feature_cache_quality_bounded(fc):
    """On the contractive fixture both cache policies actually skip
    evals (output != uncached) while staying within a small relative
    deviation of the uncached solve — the ISSUE's bounded-quality-delta
    claim at test scale."""
    den, _, _, _ = tame_denoiser(n_layers=8)
    spec0 = SamplerSpec.from_nfe("sa", 8, schedule=SCHED, tau=0.0)
    xT = Sampler(spec0).init_noise(jax.random.PRNGKey(5), (2, 16, 8))
    key = jax.random.PRNGKey(6)
    ref = Sampler(spec0).sample(den, xT, key)
    out = Sampler(dataclasses.replace(spec0, feature_cache=fc)).sample(
        den, xT, key)
    rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
    assert 0.0 < rel < 0.05, rel


# ------------------------------------------ compile-cache contract (CFG)
def test_guided_cached_sweep_zero_misses():
    """tau, guidance scale, and the residual threshold are all plan/
    traced DATA: a sweep over all three on a guided+cached Denoiser
    shares ONE compilation."""
    den, _, _, _ = tame_denoiser(guidance=True)
    cond = 0.1 * jax.random.normal(jax.random.PRNGKey(7), (16, 8))
    clear_compile_cache()
    shape, key = (2, 16, 8), jax.random.PRNGKey(8)
    n = 0
    for tau in (0.0, 0.7):
        for s in (1.0, 3.0):
            for thresh in (0.02, 0.08):
                spec = SamplerSpec.from_nfe(
                    "sa", 6, schedule=SCHED, tau=tau, guidance=True,
                    feature_cache=("residual", thresh))
                smp = Sampler(spec)
                xT = smp.init_noise(jax.random.PRNGKey(9), shape)
                out = smp.sample(den, xT, key, cond=cond, guidance_scale=s,
                                 model_key="e2e-test-sweep")
                assert bool(jnp.all(jnp.isfinite(out)))
                n += 1
    stats = compile_cache_stats()
    assert stats["misses"] == 1, stats
    assert stats["hits"] == n - 1, stats


# --------------------------------------------------- sharded CFG (bitwise)
def test_sharded_cfg_bitwise_subprocess():
    """On a (cfg=2, data) mesh: guidance_scale=1.0 is BITWISE the
    unguided solve (the s-form ``(1-s) u + s c`` short-circuits), and the
    guided solve is BITWISE the doubled-lane data-parallel CFG — sharding
    cond/uncond across the cfg axis changes placement, never math."""
    run_sub("""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import get_smoke
from repro.core import Denoiser, get_schedule
from repro.core.samplers import SamplerSpec, Sampler
from repro.models import build_model, init_params
from repro.launch.mesh import auto_mesh_of
from repro.serve.sharding import auto_cfg_mesh

ndev = len(jax.devices())
assert ndev == 8, ndev
# adaLN-zero init makes blocks identity: perturb so cond != uncond
cfg = dataclasses.replace(get_smoke("dit-s"), n_layers=4, denoiser_cond=4)
model = build_model(cfg)
params = init_params(jax.random.PRNGKey(0), model.param_defs(), jnp.float32)
params = jax.tree.map(
    lambda p: p + 0.02 * jax.random.normal(jax.random.PRNGKey(1),
                                           p.shape, p.dtype), params)

def net(x, t, c):
    lane = x.ndim == 2
    if c is not None and lane and c.ndim == 1:
        c = c[None]
    x0 = model.denoise(params, x[None] if lane else x, t, c)
    return x0[0] if lane else x0

sched = get_schedule("vp_linear")
den_u = Denoiser(net, sched, prediction="x0", guidance=False)
den_g = Denoiser(net, sched, prediction="x0", guidance=True)
spec_u = SamplerSpec.from_nfe("sa", 8, schedule=sched, tau=0.0)
spec_g = dataclasses.replace(spec_u, guidance=True)
B, S, dz = ndev, 16, 8
cond = jnp.ones((B, 4), jnp.float32)
xT = Sampler(spec_g).init_noise(jax.random.PRNGKey(5), (B, S, dz))
keys = jax.vmap(jax.random.fold_in, (None, 0))(jax.random.PRNGKey(7),
                                               jnp.arange(B))
data = auto_mesh_of((ndev,), ("data",), jax.devices())
cfgm = auto_cfg_mesh()
assert cfgm is not None and cfgm.devices.shape == (2, ndev // 2)

# guided: cfg-sharded == doubled-lane data-parallel, bitwise
out_d = Sampler(spec_g).sample_sharded(den_g, xT, keys, mesh=data,
                                       cond=cond,
                                       guidance_scale=jnp.full((B,), 2.5))
out_c = Sampler(spec_g).sample_sharded(den_g, xT, keys, mesh=cfgm,
                                       cfg_axis="cfg", cond=cond,
                                       guidance_scale=jnp.full((B,), 2.5))
assert jnp.array_equal(out_d, out_c), float(jnp.max(jnp.abs(out_d - out_c)))

# s=1 on the cfg mesh == the unguided cond branch, bitwise
out_s1 = Sampler(spec_g).sample_sharded(den_g, xT, keys, mesh=cfgm,
                                        cfg_axis="cfg", cond=cond,
                                        guidance_scale=jnp.ones((B,)))
out_u = Sampler(spec_u).sample_sharded(den_u, xT, keys, mesh=data,
                                       cond=cond)
assert jnp.array_equal(out_s1, out_u), \
    float(jnp.max(jnp.abs(out_s1 - out_u)))
print("ok")
""")
