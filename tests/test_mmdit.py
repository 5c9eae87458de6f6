"""SD3 Medium's MMDiT (``models/mmdit.py``), rectified flow and the flow
velocity, on the CPU at a tiny size (2 blocks, the last pre-only; d 64;
4 heads of 16; 16 image tokens; 6 text tokens), against the plain
float32 reference of ``tests/mmdit_reference.py``; the chip benchmark's
own reference (``benchmarks/chip/families/mmdit.py``) against that one;
and the tree and count at the published widths, from abstract shapes.
"""

import dataclasses
import json
import re
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mmdit_reference as ref
from repro.configs import get_config, get_smoke
from repro.core import Denoiser, RectifiedFlowSchedule, convert_prediction
from repro.core import get_schedule, timestep_grid
from repro.core.samplers import SamplerSpec, build_plan
from repro.kernels.flash_attention import _block_sizes, flash_attention
from repro.launch.sample import as_conditioned_network
from repro.models import abstract_params, build_model
from repro.models.common import ParamDef
from repro.serve import ServeEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
from benchmarks.chip import modules, reference  # noqa: E402

CHIP = os.path.join(ROOT, "benchmarks", "chip")
FAMILY = modules.load(CHIP, "families", "mmdit")
CFG = dataclasses.replace(get_smoke("sd3-medium"), dtype=jnp.float32)
#: the tiny configuration as the benchmark's family reads it
MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "head_dim": 16,
         "d_ff": 256, "patch_size": 2, "in_channels": 4, "sample_size": 8,
         "latent_tokens": 16, "latent_dim": 16, "ctx_tokens": 6,
         "ctx_dim": 32, "pooled_dim": 24, "time_embed_dim": 256,
         "pos_embed_max_size": 12, "norm_eps": 1e-6}
REF_KW = dict(heads=4, max_size=12, base_size=4)


def _params(seed=1):
    """Every leaf random, biases and adaLN included, so that each path
    of the block is live."""
    defs = build_model(CFG).param_defs()
    leaves, tree = jax.tree.flatten(
        defs, is_leaf=lambda d: isinstance(d, ParamDef))
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        0.3 * jax.random.normal(k, d.shape)
        / np.sqrt(d.shape[-2] if len(d.shape) > 1 else 4.0)
        for d, k in zip(leaves, keys)])


def _inputs(B=2, seed=2):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    z = jax.random.normal(k[0], (B, 16, 16))
    cond = {"seq": jax.random.normal(k[1], (B, 6, 32)),
            "pooled": jax.random.normal(k[2], (B, 24))}
    return z, cond


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


# ------------------------------------------------------------ the network
@pytest.mark.parametrize("t", [0.999, 0.37, 0.001])
def test_denoise_equals_the_plain_reference(t):
    params = _params()
    z, cond = _inputs()
    with jax.default_matmul_precision("highest"):
        got = build_model(CFG).denoise(params, z, t, cond)
    want = ref.velocity(params, z, t, cond, **REF_KW)
    assert got.shape == z.shape and got.dtype == jnp.float32
    assert _rel(got, want) < 1e-5


def test_the_benchmarks_reference_equals_the_plain_reference():
    """``reference_pair`` gives x0 of the conditional branch and of the
    null prompt's, from the velocity: x0 = x - t u."""
    params = _params(3)
    z, cond = _inputs(seed=4)
    t = 0.41
    x0_c, x0_u = FAMILY.reference_pair(params, z, t, cond, MODEL)
    null = jax.tree.map(lambda n, c: jnp.broadcast_to(n, c.shape),
                        FAMILY.null_prompt(MODEL), cond)
    for got, c in ((x0_c, cond), (x0_u, null)):
        want = z - t * ref.velocity(params, z, t, c, **REF_KW)
        assert _rel(got, want) < 1e-5
    assert not np.allclose(x0_c, x0_u)
    assert all(np.abs(a).max() > 0.5
               for a in FAMILY.null_prompt(MODEL).values())


def test_the_context_stream_and_attention_are_named():
    """The text stream's work carries the ``context_stream`` scope and
    the joint attention the ``attention`` scope, each apart from the
    other, so that the trace readers find them."""
    model = build_model(CFG)
    z, cond = _inputs()
    text = jax.jit(model.denoise).lower(_params(), z, 0.5, cond) \
        .compile().as_text()
    names = re.findall(r'op_name="([^"]*)"', text)
    split = [set(re.split(r"[/;()]", n.split(" @")[0])) for n in names]
    assert any("context_stream" in s for s in split)
    assert any("attention" in s for s in split)
    assert not any({"context_stream", "attention"} <= s for s in split)


def test_published_widths_tree_and_count():
    """At the published widths, from abstract shapes (nothing allocated):
    the program's tree is the benchmark family's, and its size is the
    analytic count the configuration file writes."""
    with open(os.path.join(CHIP, "configs", "sd3-medium-1024.json")) as f:
        conf = json.load(f)
    cfg = get_config("sd3-medium")
    tree = abstract_params(build_model(cfg).param_defs())
    shapes = jax.tree.map(lambda a: tuple(a.shape), tree)
    assert shapes == FAMILY.shapes(conf["model"])
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert n == cfg.param_count() == conf["model"]["param_count"]
    assert abs(n - 2.03e9) < 0.01e9
    m = conf["model"]
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.d_ff,
            cfg.latent_dim) == (m["d_model"], m["n_heads"], m["head_dim"],
                                m["d_ff"], m["latent_dim"])


# ------------------------------------------- rectified flow, flow velocity
def test_rectified_flow_lam_and_its_inverse():
    s = RectifiedFlowSchedule()
    ts = np.linspace(1e-3, 0.999, 101)
    np.testing.assert_allclose(s.t_of_lam(s.lam(ts)), ts, rtol=1e-12)
    np.testing.assert_allclose(s.lam(ts), np.log((1 - ts) / ts), rtol=1e-12)
    assert np.all(np.diff(s.lam(ts)) < 0)
    np.testing.assert_allclose(s.alpha(ts), 1 - ts, rtol=1e-12)
    np.testing.assert_allclose(s.sigma(ts), ts, rtol=1e-12)
    assert get_schedule("rectified_flow") == s


@pytest.mark.parametrize("t_start,t_end", [(1.0, 0.001), (0.999, 0.0),
                                           (1.0, 0.0)])
def test_rectified_flow_refuses_its_end_points(t_start, t_end):
    with pytest.raises(ValueError, match="rectified flow"):
        timestep_grid(RectifiedFlowSchedule(), 10, t_start=t_start,
                      t_end=t_end)


def test_rectified_flow_sa_tables_are_finite():
    spec = SamplerSpec.from_nfe("sa", 20, schedule=RectifiedFlowSchedule(),
                                predictor_order=3, corrector_order=1,
                                tau=1.0, prediction="flow", guidance=True)
    tables = build_plan(spec).host["tables"]
    for name in ("ts", "decay", "noise", "corr_new", "pred", "corr"):
        assert np.all(np.isfinite(getattr(tables, name))), name
    lam = RectifiedFlowSchedule().lam(tables.ts)
    np.testing.assert_allclose(np.diff(lam), np.diff(lam)[0], rtol=1e-9)


@pytest.mark.parametrize("schedule", ["rectified_flow", "vp_linear",
                                      "vp_cosine"])
@pytest.mark.parametrize("other", ["x0", "eps", "v"])
def test_flow_round_trips(schedule, other):
    """x0, eps and v go to the flow velocity and back; the velocity is
    ``alpha' x0 + sigma' eps`` (rectified flow: eps - x0)."""
    sched = get_schedule(schedule)
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    x0 = jax.random.normal(k[0], (32, 4))
    eps = jax.random.normal(k[1], (32, 4))
    t = jnp.float32(0.37)
    a, s = sched.alpha_j(t), sched.sigma_j(t)
    x = a * x0 + s * eps
    truth = {"x0": x0, "eps": eps, "v": a * eps - s * x0}
    u = convert_prediction(truth[other], x, t, other, "flow", sched)
    want = sched.d_alpha_j(t) * x0 + sched.d_sigma_j(t) * eps
    np.testing.assert_allclose(u, want, rtol=1e-4, atol=1e-4)
    if schedule == "rectified_flow":
        np.testing.assert_allclose(u, eps - x0, rtol=1e-5, atol=1e-5)
    back = convert_prediction(u, x, t, "flow", other, sched)
    np.testing.assert_allclose(back, truth[other], rtol=1e-4, atol=1e-4)


# ------------------------------------------------------------- serving
@pytest.mark.parametrize("scheduler", ["solve", "step"])
def test_served_equals_the_reference_sampler(scheduler):
    """The tiny MMDiT behind a guided ``Denoiser`` (dict conds, the
    non-zero null prompt, flow velocity on rectified flow), served by
    ``ServeEngine``, equals the benchmark's reference sampler (SA-Solver
    tables by quadrature, its own backbone) in float32."""
    conf = {"model": MODEL, "program": {"arch": "sd3-medium",
                                        "schedule": "rectified_flow",
                                        "prediction": "flow"},
            "schedule": {"kind": "rectified_flow", "t_start": 0.999,
                         "t_end": 0.001, "grid": "logsnr"}}
    orig = FAMILY.program_config
    try:
        FAMILY.program_config = lambda c: dataclasses.replace(
            orig(c), dtype=jnp.float32)
        network, schedule, null = FAMILY.program(conf)
    finally:
        FAMILY.program_config = orig
    assert float(jnp.abs(null["seq"]).max()) > 0
    params = _params(5)
    den = Denoiser(network, schedule, prediction="flow", guidance=True,
                   params=params, null_cond=null)
    solver = {"n_steps": 5, "tau": 1.0, "predictor_order": 3,
              "corrector_order": 1}
    spec = SamplerSpec.from_nfe("sa", solver["n_steps"] + 1,
                                schedule=schedule, predictor_order=3,
                                corrector_order=1, tau=1.0,
                                prediction="flow", guidance=True)
    kw = dict(model_key=("test_mmdit", scheduler), noise_seed=11,
              solve_seed=12)
    engine = ServeEngine(den, scheduler="step", lanes=2, **kw) \
        if scheduler == "step" else ServeEngine(den, bucket_sizes=(2,), **kw)
    conds = FAMILY.conds({"seq_std": 1.0, "pooled_std": 1.0}, MODEL,
                         np.random.default_rng(7), 3)
    for rid, c in enumerate(conds):
        engine.submit(spec, (16, 16), rid=rid, cond=c, guidance_scale=7.0)
    served = {r.rid: np.asarray(r.x0) for r in engine.run()}
    want = reference.sample(
        params, MODEL, conf["schedule"], solver, family=FAMILY,
        rids=[0, 1, 2], conds=conds, scales=[7.0] * 3, noise_seed=11,
        solve_seed=12, tokens=16, data_dir=CHIP)
    for rid in range(3):
        assert _rel(served[rid], want[rid]) < 1e-4, rid


def test_conditioned_network_takes_one_lane_or_a_batch():
    model = build_model(CFG)
    net = as_conditioned_network(model, RectifiedFlowSchedule(), "x0")
    params = _params()
    z, cond = _inputs()
    batch = net(params, z, 0.3, cond)
    lane = net(params, z[1], 0.3, jax.tree.map(lambda c: c[1], cond))
    np.testing.assert_allclose(lane, batch[1], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        batch, z - 0.3 * model.denoise(params, z, 0.3, cond), rtol=1e-4,
        atol=1e-5)


# ------------------------------------------------------------ the kernel
@pytest.mark.parametrize("bq,bk", [(None, None), (8, 8)],
                         ids=["one-block", "blocked-ragged"])
def test_flash_attention_at_the_joint_layout(bq, bk):
    """The joint attention's case in interpret mode: non-causal, image
    and text tokens in one ragged length (16 + 6 = 22), every head in
    the merged columns, bfloat16 operands."""
    ks = jax.random.split(jax.random.PRNGKey(8), 3)
    q, k, v = (jax.random.normal(kk, (2, 22, 4, 16), jnp.float32)
               for kk in ks)
    out = flash_attention(q, k, v, causal=False, dtype=jnp.bfloat16,
                          bq=bq, bk=bk)
    merged = lambda a: a.reshape(2, 22, 64)
    want = jax.vmap(lambda a, b, c: ref.attention(a, b, c, 4))(
        merged(q), merged(k), merged(v))
    np.testing.assert_allclose(np.asarray(out, np.float32).reshape(2, 22, 64),
                               np.asarray(want), atol=4e-2, rtol=4e-2)


def test_block_sizes_at_the_joint_length_and_dits_unchanged():
    """DiT-XL/2's blocks at 256 and 1024 tokens stay as they were; at the
    joint 4250 tokens one KV block spans every key (the last block's
    4096 image queries too), with q blocks of 128 rows; where K and V
    would not fit in VMEM the keys come in blocks of 512."""
    assert _block_sizes(256, 256, 16 * 72) == (256, 256)
    assert _block_sizes(1024, 1024, 16 * 72) == (512, 1024)
    assert _block_sizes(4250, 4250, 24 * 64) == (128, 4250)
    assert _block_sizes(4096, 4250, 24 * 64) == (128, 4250)
    assert _block_sizes(16384, 16384, 24 * 64) == (256, 512)
