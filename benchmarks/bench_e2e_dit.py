"""End-to-end DiT sampling benchmark: the bf16 fused ring kernel,
sharded classifier-free guidance, and step-to-step feature caching, all
measured on the REAL registry executors driving a transformer denoiser.

    PYTHONPATH=src python benchmarks/bench_e2e_dit.py [--smoke]

Four claims are asserted (the PR's regression gate), one per section:

A. **bf16 fused ring kernel** — the fused-dual ring executor cuts
   ``cost_analysis()['bytes accessed']`` of the solve by >= 30% vs the
   concat-bf16 baseline (closing the f32/bf16 gap in the
   BENCH_RESULTS.json trajectory: bf16 was 19.5% before the bf16 tile
   banking). As in ``bench_hotpath``, XLA's bytes-accessed is the
   acceptance metric (asserted, on solver-only traffic with a trivial
   model at the DiT latent size) and the trip-count-aware per-step
   numbers from ``hlo_cost`` are the recorded physical-traffic view.
   The per-step view is *diluted* relative to the acceptance metric by
   traffic the two paths share — the per-step tau-noise RNG (threefry +
   erfinv; tau is traced data so it never specializes away) — and, at
   the rank-3 ``[B, S, dz]`` latent, by XLA loop-fusing the concat
   shift into the broadcast-multiply-reduce combine (the rank-1 dot
   cannot absorb operands like that), so both flat and rank-3 layouts
   are recorded. Attribution on the full DiT executor comes from the
   ``hlo_cost.region_bytes`` backbone/solver split (the Denoiser tags
   network ops with ``named_scope("backbone")``).

B. **sharded CFG** — the cond/uncond pair on a size-2 ``cfg`` mesh axis
   is (i) bitwise equal to the doubled-lane evaluation on a pure data
   mesh, (ii) bitwise equal to the unguided path at scale 1.0, and
   (iii) halves per-device network work: the cfg mesh runs each request
   at ONE lane per device where the doubled-lane path runs two, so
   per-partition backbone FLOPs drop by ~2x (asserted < 0.6x).

C. **feature caching** — DeepCache-style mid-block reuse
   (``SamplerSpec.feature_cache``) on a contractive 8-layer DiT
   (``repro.models.tame``) cuts backbone FLOPs >= 25% (trip-count-aware,
   refresh-vs-cached eval graphs weighted by the plan's refresh
   schedule) at a bounded quality delta (relative L2 vs the uncached
   solve < 0.05, and > 0 so the cache demonstrably engages).

D. **compile-cache contract** — a tau x guidance-scale x
   residual-threshold sweep over the guided + feature-cached executor
   costs exactly ONE compile: tau/threshold are plan data, the scale is
   traced data.

Every ``benchmarks.run`` invocation appends the metrics (wall time, HBM
bytes by region, backbone-eval counts) to ``BENCH_RESULTS.json``.
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_smoke
from repro.core import Denoiser, get_schedule
from repro.core import samplers
from repro.core.samplers import (SamplerSpec, Sampler, build_plan,
                                 get_family, sample_sharded)
from repro.core.samplers.base import warmup
from repro.launch.hlo_cost import analyze_compiled
from repro.launch.mesh import auto_mesh_of
from repro.models import build_model, init_params
from repro.models.tame import tame_dit, tame_networks

try:
    from .common import print_table  # python -m benchmarks.run
except ImportError:
    from common import print_table  # python benchmarks/bench_e2e_dit.py

SCHED = get_schedule("vp_linear")


def _trivial(x, t):
    return 0.97 * x  # isolates solver bookkeeping, as in bench_hotpath


def _spec(m: int, history: str, combine: str) -> SamplerSpec:
    return SamplerSpec(name="sa", schedule=SCHED, n_steps=m, tau=0.6,
                       predictor_order=3, corrector_order=3, mode="PEC",
                       history=history, combine=combine, precision="bf16")


def _compile_solver_only(history: str, combine: str, shape, m: int):
    """The bare registry executor (trivial model) at the e2e latent
    shape — solver bookkeeping is the only traffic."""
    plan = build_plan(_spec(m, history, combine))
    fam = get_family("sa")
    statics = plan.statics

    def run_fn(arrays, x, k):
        return fam.execute(statics, arrays, _trivial, x, k, False)

    proto = jax.random.PRNGKey(0)
    arrays_s = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), plan.arrays)
    x_s = jax.ShapeDtypeStruct(shape, jnp.float32)
    k_s = jax.ShapeDtypeStruct(proto.shape, proto.dtype)
    return jax.jit(run_fn).lower(arrays_s, x_s, k_s).compile()


def _xla_bytes(compiled) -> float:
    d = compiled.cost_analysis()
    d = d[0] if isinstance(d, list) else d  # list-of-dicts on older jax
    return float(d["bytes accessed"])


def _solver_only_per_step(history: str, combine: str, shape,
                          m_lo: int, m_hi: int) -> float:
    """Per-step HBM bytes of the bare executor, differenced across two
    step counts so init/final code cancels."""
    b_lo = analyze_compiled(_compile_solver_only(history, combine,
                                                 shape, m_lo)).bytes
    b_hi = analyze_compiled(_compile_solver_only(history, combine,
                                                 shape, m_hi)).bytes
    return (b_hi - b_lo) / (m_hi - m_lo)


def _dit_denoiser():
    """The standard smoke DiT-S behind the Denoiser adapter (x0 net)."""
    cfg = get_smoke("dit-s")
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(),
                         jnp.float32)

    def network(x, t, cond):
        lane = x.ndim == 2
        x0 = model.denoise(params, x[None] if lane else x, t)
        return x0[0] if lane else x0

    return cfg, Denoiser(network, SCHED, prediction="x0")


def _regions_per_step(den, variant: str, history: str, combine: str,
                      shape, m_lo: int, m_hi: int) -> dict:
    """Backbone/solver HBM bytes per step of the FULL DiT executor.

    The compile cache keys executors on everything *except* the step
    count and stores one AOT executable per key, so each (variant, m)
    pair gets its own ``model_key``."""
    per = {}
    for m in (m_lo, m_hi):
        plan = build_plan(_spec(m, history, combine))
        aot = warmup(plan, den, shape, jnp.float32,
                     model_key=("e2e-region", variant, m, shape))
        per[m] = analyze_compiled(aot).region_bytes
    return {k: (per[m_hi][k] - per[m_lo][k]) / (m_hi - m_lo)
            for k in ("backbone", "solver")}


def run(smoke: bool = False):
    metrics: dict = {}
    shape = (4, 32, 8) if smoke else (16, 64, 8)
    m_lo, m_hi = (4, 8) if smoke else (8, 16)

    # ---------------- A. bf16 fused ring kernel: HBM per step ----------
    variants = [("concat_bf16", "concat", "einsum"),
                ("fused_bf16", "ring", "fused")]
    flat = (int(np.prod(shape)),)
    rows = []
    for name, hist, comb in variants:
        metrics[f"{name}_xla_bytes"] = _xla_bytes(
            _compile_solver_only(hist, comb, flat, m_hi))
        for lay, sh in [("flat", flat), ("rank3", shape)]:
            b = _solver_only_per_step(hist, comb, sh, m_lo, m_hi)
            metrics[f"{name}_{lay}_solver_per_step"] = b
            rows.append([f"{name} {lay}{list(sh)}", b / 2**10])
    xla_drop = 1.0 - (metrics["fused_bf16_xla_bytes"]
                      / metrics["concat_bf16_xla_bytes"])
    metrics["fused_bf16_xla_drop"] = round(xla_drop, 4)
    drops = {}
    for lay in ("flat", "rank3"):
        drops[lay] = 1.0 - (metrics[f"fused_bf16_{lay}_solver_per_step"]
                            / metrics[f"concat_bf16_{lay}_solver_per_step"])
        metrics[f"fused_bf16_{lay}_solver_drop"] = round(drops[lay], 4)
    print_table("solver HBM per step at the DiT latent size "
                "(trivial model)", ["path", "KiB/step"], rows)
    print(f"cost_analysis() bytes-accessed drop, fused bf16 vs concat "
          f"bf16: {xla_drop:.1%} (claim: >= 30%); trip-aware per-step "
          f"drop {drops['flat']:.1%} flat, {drops['rank3']:.1%} rank-3 "
          "(RNG- and fusion-diluted — see module doc)")
    assert xla_drop >= 0.30, (
        f"fused bf16 ring path cuts cost_analysis() bytes by only "
        f"{xla_drop:.1%} vs concat bf16 (claimed >= 30%)")

    cfg, den = _dit_denoiser()
    rows = []
    for name, hist, comb in variants:
        reg = _regions_per_step(den, name, hist, comb, shape, m_lo, m_hi)
        metrics[f"{name}_e2e_backbone_per_step"] = reg["backbone"]
        metrics[f"{name}_e2e_solver_per_step"] = reg["solver"]
        rows.append([name, reg["backbone"] / 2**10, reg["solver"] / 2**10])
    e2e_drop = 1.0 - (metrics["fused_bf16_e2e_solver_per_step"]
                      / metrics["concat_bf16_e2e_solver_per_step"])
    metrics["fused_bf16_e2e_solver_drop"] = round(e2e_drop, 4)
    share = (metrics["fused_bf16_e2e_backbone_per_step"]
             / (metrics["fused_bf16_e2e_backbone_per_step"]
                + metrics["fused_bf16_e2e_solver_per_step"]))
    metrics["e2e_backbone_byte_share"] = round(share, 4)
    print_table(
        f"full DiT-S executor HBM per step, region split ({shape})",
        ["path", "backbone KiB/step", "solver KiB/step"], rows)
    print(f"e2e solver-region drop {e2e_drop:.1%} (diluted by shared "
          f"per-step tau RNG); backbone share of e2e bytes {share:.1%}")

    # ---------------- B. sharded CFG -----------------------------------
    ndev = len(jax.devices())
    if ndev < 2 or ndev % 2:
        raise AssertionError(
            f"sharded-CFG section needs an even device count >= 2, have "
            f"{ndev} (CI runs with --xla_force_host_platform_device_count=8)")
    from repro.serve.sharding import auto_cfg_mesh
    # a conditional DiT (the smoke config grows a denoiser_cond input):
    # adaLN-zero init makes blocks identity, so perturb the params to get
    # a network whose cond branch genuinely differs from uncond
    cfg_g = dataclasses.replace(get_smoke("dit-s"), n_layers=4,
                                denoiser_cond=4)
    model_g = build_model(cfg_g)
    params_g = init_params(jax.random.PRNGKey(0), model_g.param_defs(),
                           jnp.float32)
    params_g = jax.tree.map(
        lambda p: p + 0.02 * jax.random.normal(
            jax.random.PRNGKey(1), p.shape, p.dtype), params_g)

    def net_g(x, t, c):
        lane = x.ndim == 2
        if c is not None and lane and c.ndim == 1:
            c = c[None]
        x0 = model_g.denoise(params_g, x[None] if lane else x, t, c)
        return x0[0] if lane else x0

    den_g = Denoiser(net_g, SCHED, prediction="x0", guidance=True)
    den_u = Denoiser(net_g, SCHED, prediction="x0", guidance=False)

    B, S, dz = ndev, 16, 8
    spec_u = SamplerSpec.from_nfe("sa", 8, schedule=SCHED, tau=0.0)
    spec_g = dataclasses.replace(spec_u, guidance=True)
    plan_u, plan_g = build_plan(spec_u), build_plan(spec_g)
    xT = Sampler(spec_g).init_noise(jax.random.PRNGKey(5), (B, S, dz))
    cond = jnp.ones((B, 4), jnp.float32)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(7), jnp.arange(B))
    scales = jnp.full((B,), 2.5)
    data_mesh = auto_mesh_of((ndev,), ("data",), jax.devices())
    cfg_mesh = auto_cfg_mesh()

    out_d = sample_sharded(plan_g, den_g, xT, keys, mesh=data_mesh,
                           cond=cond, guidance_scale=scales)
    out_c = sample_sharded(plan_g, den_g, xT, keys, mesh=cfg_mesh,
                           cfg_axis="cfg", cond=cond, guidance_scale=scales)
    dev = float(jnp.abs(out_d - out_c).max())
    metrics["cfg_shard_max_abs_dev"] = dev
    assert jnp.array_equal(out_d, out_c), (
        f"sharded CFG deviates from doubled-lane CFG by {dev}")

    # the s = 1.0 combine claim is bitwise — (1-s)*u + s*c at s = 1
    # reproduces the cond branch exactly — and holds across meshes
    out_u = sample_sharded(plan_u, den_u, xT, keys, mesh=data_mesh,
                           cond=cond)
    out_s1 = sample_sharded(plan_g, den_g, xT, keys, mesh=cfg_mesh,
                            cfg_axis="cfg", cond=cond,
                            guidance_scale=jnp.ones((B,)))
    assert jnp.array_equal(out_s1, out_u), (
        "guided path at scale 1.0 is not bitwise the unguided path")
    print(f"sharded CFG: bitwise == doubled-lane ({B} requests, "
          f"{cfg_mesh.devices.shape} mesh); s=1.0 bitwise == unguided")

    # per-device work: doubled-lane on half the devices vs the cfg mesh
    # over all of them — same global batch, the cfg axis is parallelism
    # the data axis cannot reach (2 lanes/request/device -> 1)
    half = auto_mesh_of((ndev // 2,), ("data",), jax.devices()[:ndev // 2])
    cond_s = jax.ShapeDtypeStruct((4,), jnp.float32)
    fl = {}
    for tag, mesh, cax in [("lane_doubled", half, None),
                           ("cfg_sharded", cfg_mesh, "cfg")]:
        aot = warmup(plan_g, den_g, (S, dz), batch=B, mesh=mesh,
                     cfg_axis=cax, cond=cond_s,
                     model_key=("e2e-cfg-flops", tag))
        fl[tag] = analyze_compiled(aot).flops
    ratio = fl["cfg_sharded"] / fl["lane_doubled"]
    metrics["cfg_shard_flops_per_device_ratio"] = round(ratio, 4)
    metrics["cfg_shard_local_lanes"] = B // (ndev // 2)
    metrics["lane_doubled_local_lanes"] = 2 * B // (ndev // 2)
    print(f"per-device backbone flops, cfg-sharded / doubled-lane: "
          f"{ratio:.3f} (local lanes {metrics['cfg_shard_local_lanes']} "
          f"vs {metrics['lane_doubled_local_lanes']}; claim < 0.6)")
    assert ratio < 0.6, (
        f"cfg-sharded per-device flops ratio {ratio:.3f} (claimed < 0.6)")

    # ---------------- C. feature caching -------------------------------
    model_c, params_c, mu_c = tame_dit(n_layers=8)
    net_c, cached_c = tame_networks(model_c, params_c, mu_c)
    den_c = Denoiser(net_c, SCHED, prediction="x0", cached=cached_c)
    Bc, Sc = (2, 16) if smoke else (4, 32)
    nfe = 8 if smoke else 10
    spec0 = SamplerSpec.from_nfe("sa", nfe, schedule=SCHED, tau=0.0)
    xTc = Sampler(spec0).init_noise(jax.random.PRNGKey(8), (Bc, Sc, dz))
    kc = jax.random.PRNGKey(9)
    ref = Sampler(spec0).sample(den_c, xTc, kc)

    def eval_flops(refresh: bool) -> float:
        feats = cached_c.init(jnp.zeros((Bc, Sc, dz)))
        f = jax.jit(lambda x, fe: cached_c.call(
            x, jnp.float32(0.5), None, fe, refresh))
        comp = f.lower(
            jax.ShapeDtypeStruct((Bc, Sc, dz), jnp.float32),
            jax.ShapeDtypeStruct(feats.shape, feats.dtype)).compile()
        return analyze_compiled(comp).flops

    f_refresh, f_cached = eval_flops(True), eval_flops(False)
    metrics["fc_eval_flops_ratio"] = round(f_cached / f_refresh, 4)
    rows = []
    for fc in (2, ("residual", 0.05)):
        spec_fc = dataclasses.replace(spec0, feature_cache=fc)
        out = Sampler(spec_fc).sample(den_c, xTc, kc)
        rel = float(jnp.linalg.norm(out - ref) / jnp.linalg.norm(ref))
        slug = "interval" if fc == 2 else "residual"
        metrics[f"fc_{slug}_rel_dev"] = rel
        assert 0.0 < rel < 0.05, (
            f"feature_cache={fc}: rel dev {rel:.4f} outside (0, 0.05) — "
            "either the cache never engaged or quality is unbounded")
        refresh = np.asarray(build_plan(spec_fc).arrays["fc_refresh"])
        n_r = 1 + int(refresh.sum())     # the init eval always refreshes
        n_c = refresh.size - int(refresh.sum())
        rows.append([slug, n_r, n_c, rel])
        if slug == "interval":           # host-known refresh schedule
            red = 1.0 - (n_r * f_refresh + n_c * f_cached) / (
                (n_r + n_c) * f_refresh)
            metrics["fc_backbone_flop_reduction"] = round(red, 4)
            metrics["fc_refresh_evals"] = n_r
            metrics["fc_cached_evals"] = n_c
    print_table(
        f"feature caching, 8-layer contractive DiT, NFE={nfe} "
        f"(planned backbone evals)",
        ["policy", "refresh evals", "cached evals", "rel dev"], rows)
    red = metrics["fc_backbone_flop_reduction"]
    print(f"backbone flop reduction (interval k=2): {red:.1%} at rel dev "
          f"{metrics['fc_interval_rel_dev']:.2e} (claim: >= 25%, < 0.05)")
    assert red >= 0.25, (
        f"feature caching cuts backbone flops by only {red:.1%} "
        "(claimed >= 25%)")

    # ---------------- D. compile-cache contract ------------------------
    den_cg = Denoiser(net_c, SCHED, prediction="x0", guidance=True,
                      cached=cached_c)
    cond_c = 0.3 * jax.random.normal(jax.random.PRNGKey(10), (Bc, Sc, dz))
    samplers.clear_compile_cache()
    n_calls = 0
    for tau in (0.0, 0.6, 1.2):
        for s in (1.0, 2.5, 4.0):
            for thresh in (0.02, 0.08):
                spec_s = SamplerSpec.from_nfe(
                    "sa", nfe, schedule=SCHED, tau=tau, guidance=True,
                    feature_cache=("residual", thresh))
                Sampler(spec_s).sample(den_cg, xTc, kc, cond=cond_c,
                                       guidance_scale=s,
                                       model_key="e2e-sweep")
                n_calls += 1
    stats = samplers.compile_cache_stats()
    metrics["sweep_calls"] = n_calls
    metrics["sweep_misses"] = stats["misses"]
    print(f"tau x scale x threshold sweep ({n_calls} solves, guided + "
          f"cached executor): compile-cache misses = {stats['misses']}, "
          f"hits = {stats['hits']}")
    assert stats["misses"] == 1, (
        f"sweep recompiled: {stats['misses']} misses (expected 1)")

    # ---------------- E. wall time -------------------------------------
    if not smoke:
        spec_t = _spec(m_hi, "ring", "fused")
        sampler_t = Sampler(spec_t)
        xt = sampler_t.init_noise(jax.random.PRNGKey(11), shape)
        kt = jax.random.PRNGKey(12)
        jax.block_until_ready(
            sampler_t.sample(den, xt, kt, model_key="e2e-time"))
        t0 = time.perf_counter()
        runs = 0
        while time.perf_counter() - t0 < 0.6:
            jax.block_until_ready(
                sampler_t.sample(den, xt, kt, model_key="e2e-time"))
            runs += 1
        ms = (time.perf_counter() - t0) / max(runs, 1) * 1e3
        metrics["e2e_ms_per_solve"] = round(ms, 3)
        print(f"e2e DiT-S fused-bf16 solve ({shape}, {m_hi} steps): "
              f"{ms:.2f} ms")
    metrics["shape"] = list(shape)
    metrics["n_steps"] = m_hi
    return metrics


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: smaller shapes, skip wall-time loops")
    args = ap.parse_args()
    run(smoke=args.smoke)
    print("e2e DiT claims OK")
