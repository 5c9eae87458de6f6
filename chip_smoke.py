"""On-chip smoke test: the diffusion server, end to end, on a TPU.

Serves DiT-XL/2 at its published widths (``configs/dit_xl_2.full()``: 28
layers, d=1152, 16 heads of 72, latent 256 tokens x 16) on seeded random
f32 weights through ``ServeEngine``, as ``repro.launch.serve`` builds it:
an eps-prediction ``Denoiser`` under classifier-free guidance with
per-request conditioning, SA-Solver at NFE 20 and tau 1.0, 8 requests
per round.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a 2x2 host: the multi-chip paths

One chip: the compiled combine kernels against their jnp oracles, then
three serving phases (solve/einsum/f32, solve/fused/bf16, step/einsum/f32
with 8 lanes), plus solve/fused/f32 to compare against einsum. Each phase
serves a warm-up round (compiles, reported as set-up) and then a measured
round that must compile nothing. Four chips: sharded CFG on
``auto_cfg_mesh()`` and request sharding on ``auto_mesh()``, each against
its one-device counterpart.

Every check prints a line; any failure exits 1. JAX must report TPU
devices, else the script exits 2 naming what it found. The last stdout
line of a passing run is ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "dit-xl-2"
NFE, TAU, REQUESTS = 20, 1.0, 8
WEIGHT_NOISE = 0.02  # lets the zero-init output and adaLN gates carry signal
#: per-request CFG scales. The backbone computes in bf16, so paths that
#: differ by f32 rounding (combine order, scheduler) can flip a bf16
#: rounding of the network input: their samples then agree to the bf16
#: level (~1e-2) at scale 1.0, while a scale above 1 amplifies that gap
#: through the solve on random weights. Scale-1.0 requests are compared
#: across paths; the others must only be served finite.
SCALES = (1.0, 1.0, 1.0, 1.0, 1.5, 2.0, 2.5, 3.0)
COMPARED = [i for i, g in enumerate(SCALES) if g == 1.0]
DIT_TOL = 5e-2  # bf16-level agreement; a wrong coefficient is O(1)
F32_TOL = 1e-5  # f32 rounding, on a smooth f32 model or a single combine

FAILED: list[str] = []


def check(name: str, ok: bool, detail: str = "") -> None:
    print(f"check {name}: {'PASS' if ok else 'FAIL'} {detail}".rstrip(),
          flush=True)
    if not ok:
        FAILED.append(name)


def by_scale(errs: dict) -> str:
    """Per-request relative RMS gaps, labelled by CFG scale."""
    return "per request (scale, rel RMS): " + json.dumps(
        [[SCALES[r], float(f"{e:.6g}")] for r, e in sorted(errs.items())])


def rel_rms(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.sqrt(np.mean((a - b) ** 2))
                 / (np.sqrt(np.mean(b ** 2)) + 1e-30))


class CompileCounter:
    """Counts XLA backend compiles from its creation on (a process-wide
    ``jax.monitoring`` listener)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0

        def listen(name, *_, **__):
            if name == self.EVENT:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(listen)


def counters(compiles: CompileCounter) -> dict:
    from repro.core.samplers import compile_cache_stats, stepwise_cache_stats
    c, s = compile_cache_stats(), stepwise_cache_stats()
    return {"backend_compiles": compiles.n, "misses": c["misses"],
            "aot_fallbacks": c["aot_fallbacks"],
            "step_misses": s["misses"],
            "step_aot_fallbacks": s["aot_fallbacks"]}


# ------------------------------------------------------------- the model
def build(smoke: bool):
    """(cfg, guided eps Denoiser with weights as an argument, spec,
    per-request cond list, per-request guidance scales, latent shape)."""
    import jax
    import numpy as np
    from repro.core import Denoiser, get_schedule
    from repro.core.samplers import SamplerSpec
    from repro.launch.serve import build_denoiser_network
    schedule = get_schedule("vp_linear")
    cfg, network, params = build_denoiser_network(
        ARCH, None, smoke, schedule, "eps", weight_noise=WEIGHT_NOISE)
    den = Denoiser(network, schedule, prediction="eps", guidance=True,
                   params=params)
    # launch/serve.py's spec at the paper's NFE 20 / tau 1.0
    spec = SamplerSpec.from_nfe("sa", NFE, schedule=schedule,
                                predictor_order=3, corrector_order=1,
                                tau=TAU, prediction="eps", guidance=True)
    tokens = 32 if smoke else 256
    shape = (tokens, cfg.denoiser_latent)
    rng = np.random.default_rng(0)
    conds = [rng.normal(0.0, 0.5, cfg.denoiser_latent).astype(np.float32)
             for _ in range(REQUESTS)]
    jax.block_until_ready(params)
    return cfg, den, spec, conds, list(SCALES), shape


def serve_phase(name, engine, spec, shape, conds, scales, compiles):
    """Warm-up round (rids 100+), then a measured round (rids 0..7) that
    must compile nothing. ``conds``/``scales`` None: an unguided model.
    Returns {rid: x0} of the measured round."""
    import numpy as np

    def round_(base):
        for i in range(REQUESTS):
            kw = {} if conds is None else dict(cond=conds[i],
                                               guidance_scale=scales[i])
            engine.submit(spec, shape, rid=base + i, **kw)
        t0 = time.perf_counter()
        res = engine.run()
        return res, time.perf_counter() - t0

    warm, setup_s = round_(100)
    before = counters(compiles)
    res, serve_s = round_(0)
    after = counters(compiles)
    out = {}
    for r in warm + res:
        if r.status != "ok":
            check(f"{name} status", False, f"rid {r.rid}: {r.status} {r.error}")
            continue
        x = np.asarray(r.x0, np.float32)
        if r.rid < 100:
            out[r.rid] = x
        if not np.isfinite(x).all():
            check(f"{name} finite", False, f"rid {r.rid}")
    check(f"{name} served", sorted(out) == list(range(REQUESTS))
          and all(np.isfinite(x).all() for x in out.values()),
          f"{len(out)} ok finite of {REQUESTS}, x0 shape "
          f"{next(iter(out.values())).shape if out else None}")
    grew = {k: after[k] - before[k] for k in after if after[k] != before[k]}
    check(f"{name} no compiles after warm-up", not grew, str(grew or ""))
    summary = {"phase": name, "setup_s": setup_s, "serve_s": serve_s,
               "requests": REQUESTS, "requests_per_s": REQUESTS / serve_s,
               "x0_dtype": str(res[0].x0.dtype) if res else None}
    print("phase " + json.dumps(summary), flush=True)
    return out


# ----------------------------------------------------------- one chip
def kernel_phase():
    """The compiled combine kernels against their jnp oracles: the DiT
    latent batched over 8 lanes with per-lane coefficients (the step
    function's case), and a latent whose size is not a multiple of 128."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ref import sa_fused_update_ref, sa_update_ref
    from repro.kernels.sa_fused import sa_fused_update
    from repro.kernels.sa_update import sa_update
    P = 3

    def both(fused, single):
        """(x_pred, corr_base, single-row combine of row 0)."""
        return lambda x, b, xi, c: (*fused(x, b, xi, c),
                                    single(x, b, xi, c[0]))

    kernel = both(sa_fused_update, sa_update)
    oracle = both(sa_fused_update_ref, sa_update_ref)
    for shape, lanes in (((256, 16), 8), ((250, 16), None)):
        for dt in (jnp.float32, jnp.bfloat16):
            ks = jax.random.split(jax.random.PRNGKey(3), 4)
            lead = () if lanes is None else (lanes,)
            args = (jax.random.normal(ks[0], lead + shape, dt),
                    jax.random.normal(ks[1], lead + (P,) + shape, dt),
                    jax.random.normal(ks[2], lead + shape, dt),
                    jax.random.normal(ks[3], lead + (2, P + 2), jnp.float32))
            k_fn, o_fn = kernel, oracle
            if lanes is not None:  # per-lane coefficients, as in a step
                k_fn, o_fn = jax.vmap(kernel), jax.vmap(oracle)
            k_fn, o_fn = jax.jit(k_fn), jax.jit(o_fn)
            tag = (f"kernel {shape}{' x%d lanes' % lanes if lanes else ''} "
                   f"{jnp.dtype(dt).name}")
            hlo = k_fn.lower(*args).compile().as_text()
            check(f"{tag} tpu_custom_call", "tpu_custom_call" in hlo)
            got = [np.asarray(a, np.float32) for a in k_fn(*args)]
            # the oracle's f32 contraction at full f32 precision (the
            # MXU's default pass rounds its inputs to bf16)
            with jax.default_matmul_precision("highest"):
                want = [np.asarray(a, np.float32) for a in o_fn(*args)]
            default = [np.asarray(a, np.float32) for a in o_fn(*args)]
            err = lambda xs: max(float(np.max(np.abs(g - w)
                                              / (1.0 + np.abs(w))))
                                 for g, w in zip(xs, want))
            tol = F32_TOL if dt == jnp.float32 else 2e-2
            check(f"{tag} vs oracle", err(got) <= tol,
                  f"max scaled err {err(got):.3g}; oracle at default "
                  f"matmul precision {err(default):.3g}")


def one_chip(smoke: bool = False) -> None:
    """The serving phases on one chip; ``smoke`` swaps in the 2-layer
    config for a CPU rehearsal of the control flow."""
    import jax
    import numpy as np
    from repro.core.samplers import build_plan, warmup
    from repro.serve import ServeEngine

    compiles = CompileCounter()
    kernel_phase()

    t0 = time.perf_counter()
    cfg, den, spec, conds, scales, shape = build(smoke)
    n_par = sum(p.size for p in jax.tree.leaves(den.params))
    print(f"model {cfg.name}: {cfg.n_layers} layers d={cfg.d_model} "
          f"heads={cfg.n_heads}x{cfg.head_dim} latent={shape} "
          f"params={n_par} (f32), built in {time.perf_counter() - t0:.3f}s",
          flush=True)
    key = ("chip-smoke", cfg.name)

    def solve_engine():
        return ServeEngine(den, bucket_sizes=(REQUESTS,), model_key=key)

    specs = {
        "solve/einsum/f32": spec,
        "solve/fused/f32": spec.replace(combine="fused"),
        "solve/fused/bf16": spec.replace(combine="fused", precision="bf16"),
    }
    outs = {}
    for name, sp in specs.items():
        outs[name] = serve_phase(name, solve_engine(), sp, shape, conds,
                                 scales, compiles)
    step_eng = ServeEngine(den, scheduler="step", lanes=REQUESTS,
                           model_key=key)
    outs["step/einsum/f32"] = serve_phase(
        "step/einsum/f32", step_eng, spec, shape, conds, scales, compiles)

    # the fused executable really runs the Pallas kernel
    cond_proto = jax.ShapeDtypeStruct(conds[0].shape, conds[0].dtype)
    for name in ("solve/fused/f32", "solve/fused/bf16"):
        aot = warmup(build_plan(specs[name]), den, shape, batch=REQUESTS,
                     cond=cond_proto, model_key=key)
        check(f"{name} executable has tpu_custom_call",
              "tpu_custom_call" in aot.as_text())

    ref = outs["solve/einsum/f32"]
    for name in ("solve/fused/f32", "step/einsum/f32", "solve/fused/bf16"):
        got = outs[name]
        errs = {r: rel_rms(got[r], ref[r]) for r in sorted(ref) if r in got}
        worst = max((errs[r] for r in COMPARED if r in errs),
                    default=float("nan"))
        check(f"{name} vs solve/einsum/f32 at CFG scale 1",
              len(errs) == REQUESTS and worst <= DIT_TOL,
              f"worst relative RMS {worst:.3g} (limit {DIT_TOL:g}); "
              + by_scale(errs))
    stable_phases(shape, compiles)


def stable_phases(shape, compiles) -> None:
    """The solver path on a smooth f32 model (tests/test_serve.py's
    fusion-stable ``0.3 x cos t``), where only the solver's own rounding
    can separate two paths: the fused kernel's executable against the
    einsum one to f32 rounding, and tests/test_serve.py's contract that
    requests served through join/leave/lane-recycling continuous
    batching return exactly the bytes the solve scheduler returns."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import get_schedule
    from repro.core.samplers import SamplerSpec, build_plan, warmup
    from repro.serve import ServeEngine
    sched = get_schedule("vp_linear")
    spec_a = SamplerSpec(name="sa", schedule=sched, n_steps=8, mode="PECE",
                         tau=0.7)
    spec_b = SamplerSpec(name="sa", schedule=sched, n_steps=6, tau=0.4)

    def stable(x, t):
        return 0.3 * x * jnp.cos(t)

    outs = {}
    for combine in ("einsum", "fused"):
        sp = spec_a.replace(combine=combine)
        outs[combine] = serve_phase(
            f"stable solve/{combine}/f32",
            ServeEngine(stable, bucket_sizes=(REQUESTS,)), sp, shape,
            None, None, compiles)
    aot = warmup(build_plan(spec_a.replace(combine="fused")), stable, shape,
                 batch=REQUESTS)
    check("stable solve/fused/f32 executable has tpu_custom_call",
          "tpu_custom_call" in aot.as_text())
    worst = max(rel_rms(outs["fused"][r], outs["einsum"][r])
                for r in outs["einsum"])
    check("stable solve/fused/f32 vs solve/einsum/f32", worst <= F32_TOL,
          f"worst relative RMS {worst:.3g} (limit {F32_TOL:g})")

    specs = [spec_a] * 5 + [spec_b] * 3
    solve = ServeEngine(stable, bucket_sizes=(1, 2, 4))
    for r, sp in enumerate(specs):
        solve.submit(sp, shape, rid=r)
    ref = {res.rid: np.asarray(res.x0) for res in solve.run()}
    step = ServeEngine(stable, scheduler="step", lanes=4)
    for r, sp in enumerate(specs):
        step.submit(sp, shape, rid=r)
    out = {res.rid: res for res in step.run()}
    same = [r for r in ref if out[r].status == "ok"
            and np.array_equal(np.asarray(out[r].x0), ref[r])]
    check("stable model step == solve (bitwise, 8 requests, churn)",
          len(same) == len(specs), f"{len(same)}/{len(specs)} bitwise")


# --------------------------------------------------------- four chips
def four_chips(smoke: bool = False) -> None:
    """Sharded CFG and request sharding against their one-device
    counterparts, on two guided models: a smooth elementwise f32 network
    (placement must not change a bit) and the DiT (bf16 backbone: scale-1
    requests agree to the bf16 level, see ``SCALES``). x_T is reused
    across calls, so nothing is donated."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import Denoiser
    from repro.core.samplers import Sampler, build_plan, warmup
    from repro.serve import ServeEngine
    from repro.serve.sharding import auto_cfg_mesh, auto_mesh

    cfg, den, spec, conds, scales, shape = build(smoke)
    smooth = Denoiser(lambda x, t, c: 0.3 * (x + c) * jnp.cos(t),
                      spec.resolve_schedule(), prediction="eps",
                      guidance=True)
    smp = Sampler(spec)
    xT = smp.init_noise(jax.random.PRNGKey(5), (REQUESTS,) + shape)
    keys = jax.vmap(jax.random.fold_in, (None, 0))(jax.random.PRNGKey(7),
                                                   jnp.arange(REQUESTS))
    cond = jnp.asarray(np.stack(conds))
    g = jnp.asarray(scales, jnp.float32)
    data, cfgm = auto_mesh(), auto_cfg_mesh()
    print(f"meshes: data {dict(data.shape)}, cfg {dict(cfgm.shape)}",
          flush=True)

    def agree(name, got, ref, model):
        """smooth: bitwise. DiT: scale-1 requests within DIT_TOL."""
        got = {r: np.asarray(x, np.float32) for r, x in got.items()}
        ref = {r: np.asarray(x, np.float32) for r, x in ref.items()}
        same = len(got) == len(ref) == REQUESTS and all(
            np.array_equal(got[r], ref[r]) for r in ref)
        errs = {r: rel_rms(got[r], ref[r]) for r in ref if r in got}
        detail = by_scale(errs)
        if model == "smooth":
            check(f"{model}: {name} (bitwise)", same, detail)
        else:
            worst = max(errs.get(r, float("inf")) for r in COMPARED)
            check(f"{model}: {name} at CFG scale 1", worst <= DIT_TOL,
                  f"worst relative RMS {worst:.3g} (limit {DIT_TOL:g}), "
                  f"bitwise={same}; {detail}")

    def timed(label, fn):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn())
        print("phase " + json.dumps(
            {"phase": label, "first_call_s": time.perf_counter() - t0}),
            flush=True)
        return out

    for model, mf in (("smooth", smooth), ("DiT", den)):
        mkey = ("chip-smoke", model)
        kw = dict(cond=cond, guidance_scale=g, model_key=mkey)
        one = timed(f"{model}: one device, doubled-lane CFG",
                    lambda: smp.sample_batched(mf, xT, keys, **kw))
        lane = timed(f"{model}: data mesh, doubled-lane CFG",
                     lambda: smp.sample_sharded(mf, xT, keys, mesh=data,
                                                donate=False, **kw))
        sharded = timed(f"{model}: cfg mesh, sharded CFG",
                        lambda: smp.sample_sharded(mf, xT, keys, mesh=cfgm,
                                                   cfg_axis="cfg",
                                                   donate=False, **kw))
        devs = {sh.device for sh in sharded.addressable_shards}
        check(f"{model}: sharded CFG output spans {len(jax.devices())} "
              "devices", len(devs) == len(jax.devices()))
        agree("sharded CFG == data mesh doubled-lane", dict(enumerate(
            sharded)), dict(enumerate(lane)), model)
        agree("sharded CFG == one device doubled-lane", dict(enumerate(
            sharded)), dict(enumerate(one)), model)

        # request sharding through the engine against the unsharded one
        served = {}
        for label, mesh in (("unsharded", None), ("data-sharded", data)):
            eng = ServeEngine(mf, bucket_sizes=(REQUESTS,), mesh=mesh,
                              model_key=mkey)
            for i in range(REQUESTS):
                eng.submit(spec, shape, rid=i, cond=conds[i],
                           guidance_scale=scales[i])
            t0 = time.perf_counter()
            res = eng.run()
            print("phase " + json.dumps(
                {"phase": f"{model}: engine {label}",
                 "first_round_s": time.perf_counter() - t0}), flush=True)
            ok = {r.rid: r.x0 for r in res if r.status == "ok"
                  and bool(jnp.all(jnp.isfinite(r.x0)))}
            check(f"{model}: engine {label} served 8 ok finite",
                  len(ok) == REQUESTS)
            served[label] = ok
        agree("engine data-sharded == unsharded", served["data-sharded"],
              served["unsharded"], model)

    # both cfg halves evaluate: per-device FLOPs of the sharded-CFG
    # program are a quarter of the one-device program's (data=2 x cfg=2),
    # where a cfg axis that held no work would leave a half
    plan = build_plan(spec)
    cond_proto = jax.ShapeDtypeStruct(conds[0].shape, conds[0].dtype)
    flops = {}
    for label, mesh, cax in (("one", None, None), ("cfg", cfgm, "cfg")):
        aot = warmup(plan, den, shape, batch=REQUESTS, mesh=mesh,
                     cfg_axis=cax, cond=cond_proto,
                     model_key=("chip-smoke", "DiT"), donate=False)
        ca = aot.cost_analysis()
        ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        flops[label] = float(ca.get("flops", 0.0))
    ratio = flops["cfg"] / flops["one"] if flops["one"] else float("nan")
    check("DiT: sharded CFG per-device FLOPs ~ 1/4 of one device",
          0.2 <= ratio <= 0.3, f"ratio {ratio:.4f} ({flops})")


# --------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (needs 4 TPUs)")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.launch.serve import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the repo's package from "
              f"{os.path.join(ROOT, 'src')}: {e}", file=sys.stderr)
        return 2
    print(f"compile cache: {use_compile_cache()}", flush=True)
    devs = jax.devices()
    need = 4 if args.four_chips else 1
    if devs[0].platform != "tpu" or len(devs) < need:
        print(f"chip_smoke: needs {need} TPU device(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind}). No TPU, no result.",
              file=sys.stderr)
        return 2
    print(f"device: platform={devs[0].platform} "
          f"kind={devs[0].device_kind} count={len(devs)}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips()
    else:
        one_chip()
    print(f"total {time.perf_counter() - t0:.3f}s", flush=True)
    if FAILED:
        print(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
