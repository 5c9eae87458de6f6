"""Serving dispatcher: one driver for both serving workloads.

    # LM serving (batched prefill + decode against a KV/state cache):
    PYTHONPATH=src python -m repro.launch.serve --mode lm \
        --arch rwkv6-3b --smoke --batch 4 --prompt-len 32 --gen 16

    # Diffusion serving (the repro.serve engine: plan-keyed microbatching,
    # AOT-warmed buckets, optional mesh sharding + preview streaming);
    # --smoke swaps in the 2-layer config (CPU), without it the arch
    # runs at its published widths:
    PYTHONPATH=src python -m repro.launch.serve --mode diffusion --smoke \
        --arch dit-s --sampler sa --requests 12 --nfe 15 --tau 0.6 --stream

    # ... serving the backbone as a v-prediction checkpoint under
    # classifier-free guidance (denoiser adapter; scale is traced data):
    PYTHONPATH=src python -m repro.launch.serve --mode diffusion --smoke \
        --arch dit-s --prediction v --guidance-scale 3.0 --requests 8

    # ... with step-granular continuous batching — requests join and
    # leave running lane groups at step boundaries, and a masked early
    # exit retires converged lanes under the fixed compiled shape:
    PYTHONPATH=src python -m repro.launch.serve --mode diffusion --smoke \
        --scheduler step --lanes 8 --early-exit-tol 0.02 --requests 12

    # ... by quality tier — draft/standard/best resolve to step programs
    # at submit time; --tuned-artifact loads an autotuner winner
    # (python -m repro.launch.tune) as the "best" tier:
    PYTHONPATH=src python -m repro.launch.serve --mode diffusion --smoke \
        --quality-tier best --tuned-artifact artifacts/tune_nfe8.json

``--mode lm`` runs a real (reduced-config on CPU) decode loop: prefill
the prompt batch, then greedy-decode tokens one step at a time against
the cache — the same ``prefill``/``decode_step`` functions the dry-run
lowers at full scale. ``--mode diffusion`` drives
:class:`repro.serve.ServeEngine` over any registered sampler; with
``--sharded`` the request axis rides the ``data`` axis of a mesh over all
visible devices (run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to try it on CPU);
with one device it is an error. The CLI exits non-zero when any
request ends not-ok, unless ``--inject`` asked for faults.

JAX's persistent compilation cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else at the fixed ``<repo>/.jax_cache``.
"""

import argparse
import dataclasses
import math
import os
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from ..configs import get_config, get_smoke
from ..models import build_model, init_params

#: the checkout root (src/repro/launch/serve.py -> three levels up)
REPO_ROOT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its path:
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself, so
    nothing is configured here), else the fixed ``<repo>/.jax_cache`` —
    a fixed path, because a cache that moves never hits."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def serve_lm(args) -> None:
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(),
                         jnp.float32)

    B, S = args.batch, args.prompt_len
    s_max = S + args.gen
    embeds_mode = getattr(cfg, "input_mode", "tokens") == "embeds"
    key = jax.random.PRNGKey(1)
    if embeds_mode:
        batch = {"embeds": jax.random.normal(key, (B, S, cfg.d_model))}
    else:
        batch = {"tokens": jax.random.randint(key, (B, S), 0,
                                              cfg.vocab_size)}

    cache = model.init_cache(B, s_max)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step)

    t0 = time.perf_counter()
    logits, cache = prefill(params, batch, cache)
    logits = jax.block_until_ready(logits)
    t1 = time.perf_counter()

    tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
    out = [tok]
    for i in range(args.gen - 1):
        if embeds_mode:
            step_in = params["embed"][tok] if "embed" in params else \
                jnp.zeros((B, 1, cfg.d_model))
        else:
            step_in = tok
        logits, cache = decode(params, step_in, cache, S + i)
        tok = jnp.argmax(logits[:, -1, :], axis=-1)[:, None]
        out.append(tok)
    toks = jax.block_until_ready(jnp.concatenate(out, axis=1))
    t2 = time.perf_counter()
    print(f"arch={cfg.name} prefill {S} toks x{B}: {t1-t0:.3f}s; "
          f"decode {args.gen} steps: "
          f"{(t2-t1)/max(args.gen-1,1)*1e3:.1f} ms/tok")
    print("sample token ids:", toks[0][:12].tolist())


def build_denoiser_model_fn(arch: str, latent: int | None, smoke: bool):
    """(cfg, per-request model_fn) for any zoo member in denoiser mode.

    The engine's executors vmap over the request axis, so the returned
    closure sees one request ``(seq, dz)`` at a time and re-adds the
    backbone's batch axis.
    """
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if getattr(cfg, "denoiser_latent", None) is None:
        cfg = dataclasses.replace(cfg, denoiser_latent=latent or 8)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(),
                         jnp.float32)
    return cfg, lambda x, t: model.denoise(params, x[None], t)[0]


def build_denoiser_network(arch: str, latent: int | None, smoke: bool,
                           schedule, prediction: str, *,
                           weight_noise: float = 0.0):
    """(cfg, network, params) — the backbone re-expressed as an eps/x0/v
    ``(params, x, t, cond)`` network for ``Denoiser(..., params=params)``,
    with ``cond`` consumed as an input-space prompt (the zoo backbones
    are unconditional). Weights are seeded f32. ``weight_noise`` adds
    seeded Gaussian noise of std ``weight_noise / sqrt(fan_in)`` to every
    weight (the fan-in rule of the ``scaled`` init, so the noise's gain
    does not grow with width), so the zero-init output projection and
    adaLN gates carry signal on random weights."""
    from .sample import as_prediction_network
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if getattr(cfg, "denoiser_latent", None) is None:
        cfg = dataclasses.replace(cfg, denoiser_latent=latent or 8)
    model = build_model(cfg)

    def make(key, noise_key):
        params = init_params(key, model.param_defs(), jnp.float32)
        if not weight_noise:
            return params
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(noise_key, len(leaves))
        return treedef.unflatten([
            p + weight_noise / math.sqrt(p.shape[-2] if p.ndim >= 2
                                         else p.shape[-1])
            * jax.random.normal(k, p.shape, p.dtype)
            for p, k in zip(leaves, keys)])

    # one compiled program: op by op, every leaf's init is its own
    # dispatch and compile (79 s for DiT-XL/2 on a v5e)
    params = jax.jit(make)(jax.random.PRNGKey(0), jax.random.PRNGKey(1))
    return cfg, as_prediction_network(model, schedule, prediction), params


def serve_diffusion(args) -> None:
    import numpy as np

    from ..core import Denoiser, get_schedule
    from ..core.samplers import SamplerSpec
    from ..serve import (QualityTiers, ServeEngine, auto_mesh,
                         default_tiers)

    from ..serve.faults import FaultInjector, FaultPlan

    mesh = None
    if args.sharded:
        try:
            mesh = auto_mesh()
        except ValueError as e:
            raise SystemExit(f"--sharded: {e}")
    schedule = get_schedule("vp_linear")
    guidance = args.guidance_scale is not None
    cfg, network, params = build_denoiser_network(
        args.arch, args.latent, args.smoke, schedule, args.prediction)
    model_fn = Denoiser(network, schedule, prediction=args.prediction,
                        guidance=guidance, params=params)
    cond = None
    if args.cond_file is not None:
        cond = jnp.asarray(np.load(args.cond_file), jnp.float32)

    def show(res):
        if res.previews is not None:
            stds = [float(jnp.std(p)) for p in res.previews[:6]]
            print(f"  stream rid {res.rid}: x0-preview std per step "
                  f"{['%.2f' % s for s in stds]}...")

    tiers = None
    if args.quality_tier is not None:
        tiers = QualityTiers.from_artifact(args.tuned_artifact) \
            if args.tuned_artifact else default_tiers(
                family=args.tier_family, schedule=schedule)
        # tiers carry solver choices; the adapter fields
        # (prediction/guidance) come from the flags
        tiers = QualityTiers({
            name: dataclasses.replace(
                s, prediction=args.prediction, guidance=guidance)
            for name, s in tiers.specs.items()})
    injector = None
    if args.inject and not args.guard_interval:
        args.guard_interval = 4  # injecting NaNs without the guard
        # would let them reach results marked "ok"
    if args.inject:
        # a small deterministic chaos mix: one NaN'd lane, one raised
        # tick, one latency spike — seeded so reruns replay it exactly
        injector = FaultInjector(FaultPlan.seeded(
            0, n_ticks=max(2, args.requests), rids=range(args.requests)))
    degrade_ladder = None
    if args.degrade_ladder:
        degrade_ladder = [s.strip() for s in args.degrade_ladder.split(",")
                          if s.strip()]
    engine = ServeEngine(
        model_fn, bucket_sizes=tuple(args.bucket_sizes), mesh=mesh,
        stream=args.stream, on_result=show if args.stream else None,
        model_key=("denoiser", cfg.name, args.prediction, guidance),
        tiers=tiers, scheduler=args.scheduler, lanes=args.lanes,
        max_retries=args.max_retries, degrade_ladder=degrade_ladder,
        guard_interval=args.guard_interval, fault_injector=injector)
    if args.quality_tier is not None:
        spec, submit_kw = None, {"quality_tier": args.quality_tier}
    else:
        spec = SamplerSpec.from_nfe(
            args.sampler, args.nfe, schedule=schedule,
            predictor_order=3, corrector_order=1, tau=args.tau,
            prediction=args.prediction, guidance=guidance)
        submit_kw = {}
    shape = (args.seq, cfg.denoiser_latent)
    g_scale = 1.0 if args.guidance_scale is None else args.guidance_scale
    for _ in range(args.requests):
        engine.submit(spec, shape, cond=cond, guidance_scale=g_scale,
                      early_exit_tol=args.early_exit_tol, **submit_kw)
    if spec is None:
        spec = engine.tiers.resolve(args.quality_tier)
        print(f"quality tier {args.quality_tier!r} -> "
              f"{spec.name} NFE {spec.nfe}, {spec.n_steps} steps"
              + (" (tuned artifact)" if args.tuned_artifact else ""))

    results = engine.run()
    assert len(results) == args.requests
    nonfinite = [r.rid for r in results if r.status == "ok"
                 and not bool(jnp.all(jnp.isfinite(r.x0)))]
    if nonfinite:
        print(f"non-finite x0 in 'ok' results: rids {nonfinite}")
    bad = [r for r in results if r.status != "ok"]
    if bad or args.inject:
        h = engine.health()
        print(f"health: {h['status']} (completed={h['completed']}, "
              f"failed={h['failed']}, "
              f"failed_numerics={h['failed_numerics']}, "
              f"retries={h['retries']}, shed={h['shed']}, "
              f"quarantines={h['quarantines']})")
        for r in bad:
            print(f"  rid {r.rid}: {r.status} after {r.attempts} "
                  f"attempt(s)"
                  + (f" [{r.degraded_to}]" if r.degraded_to else "")
                  + (f" — {r.error}" if r.error else ""))
        if injector is not None:
            print(f"injected: {injector.fired}")
    s = engine.stats()
    mesh_desc = "none" if mesh is None else dict(mesh.shape)
    if args.scheduler == "step":
        print(f"\nserved {s['completed']} requests in {s['serve_s']:.2f}s "
              f"({s['joins']} lane joins, {s['migrations']} migrations, "
              f"{s['shed']} shed, {s['ticks']} ticks, "
              f"{s['warmups']} step-fn compiles)")
        print(f"{s['requests_per_s']:.2f} requests/s, "
              f"{s['model_evals_per_s']:.1f} model-evals/s "
              f"(sampler={args.sampler}, arch={cfg.name}, "
              f"prediction={args.prediction}, "
              f"guidance={args.guidance_scale if guidance else 'off'}, "
              f"early_exit_tol={args.early_exit_tol})")
        for label, b in s["buckets"].items():
            print(f"  bucket {label}: occupancy {b['occupancy']:.2f} "
                  f"({b['wasted_lane_steps']} wasted lane-steps over "
                  f"{b['ticks']} ticks)")
        print("stepwise cache:", s["stepwise_cache"])
    else:
        print(f"\nserved {s['requests']} requests in {s['serve_s']:.2f}s "
              f"over {s['microbatches']} microbatches ({s['padded_slots']} "
              f"padded lanes, {s['warmups']} bucket compiles, "
              f"mesh={mesh_desc})")
        print(f"{s['requests_per_s']:.2f} requests/s, "
              f"{s['model_evals_per_s']:.1f} model-evals/s, "
              f"{s['network_evals_per_s']:.1f} network-evals/s "
              f"(NFE={spec.nfe}, network NFE={spec.network_nfe} x real "
              f"requests only; sampler={args.sampler}, arch={cfg.name}, "
              f"prediction={args.prediction}, "
              f"guidance={args.guidance_scale if guidance else 'off'})")
        print("compile cache:", s["compile_cache"])
    if nonfinite or (bad and not args.inject):
        raise SystemExit(f"{len(bad) + len(nonfinite)} of {len(results)} "
                         "requests ended not-ok (see above)")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mode", default="lm", choices=["lm", "diffusion"])
    ap.add_argument("--arch", default=None,
                    help="zoo member (default: starcoder2-3b for lm, "
                    "dit-s for diffusion)")
    ap.add_argument("--smoke", action="store_true",
                    help="the arch's reduced test config instead of its "
                    "published widths")
    ap.add_argument("--batch", type=int, default=4)
    # lm
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    # diffusion
    ap.add_argument("--sampler", default="sa")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--seq", type=int, default=32)
    ap.add_argument("--latent", type=int, default=None)
    ap.add_argument("--nfe", type=int, default=15)
    ap.add_argument("--tau", type=float, default=0.6)
    ap.add_argument("--bucket-sizes", type=lambda s: [int(b) for b in
                    s.split(",")], default=[1, 2, 4, 8],
                    help="comma-separated microbatch lane counts")
    ap.add_argument("--stream", action="store_true",
                    help="stream per-step denoised previews")
    ap.add_argument("--scheduler", default="solve",
                    choices=["solve", "step"],
                    help="'solve' batches whole solves per microbatch; "
                    "'step' is the continuous batcher — requests join and "
                    "leave running batches at step boundaries")
    ap.add_argument("--lanes", type=int, default=8,
                    help="lane count per running batch (step scheduler)")
    ap.add_argument("--early-exit-tol", type=float, default=0.0,
                    help="masked early exit on the predictor-vs-corrector "
                    "residual (step scheduler; <=0 disables, keeping the "
                    "exact whole-solve trajectory)")
    ap.add_argument("--sharded", action="store_true",
                    help="place the request axis on a mesh data axis")
    ap.add_argument("--prediction", default="data",
                    choices=["data", "x0", "noise", "eps", "v"],
                    help="serve the backbone as this checkpoint "
                    "convention (denoiser adapter converts in-graph)")
    ap.add_argument("--guidance-scale", type=float, default=None,
                    help="classifier-free guidance scale for every "
                    "request (scale is traced data — per-request sweeps "
                    "reuse one executable)")
    ap.add_argument("--cond-file", default=None,
                    help=".npy per-request conditioning, broadcastable "
                    "to the latent")
    ap.add_argument("--quality-tier", default=None,
                    help="submit by tier name (draft|standard|best with "
                    "the default ladder) instead of --sampler/--nfe/--tau")
    ap.add_argument("--tuned-artifact", default=None,
                    help="repro.launch.tune JSON artifact; its searched "
                    "winner becomes the 'best' tier (and its feature-"
                    "cache winner, if recorded, the 'draft' tier)")
    ap.add_argument("--tier-family", default="sa",
                    help="sampler family the default tier ladder is "
                    "built over (a multistep-core family: sa, seeds, "
                    "dpmpp_multistep); ignored with --tuned-artifact")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="serve attempts beyond the first for a failed "
                    "request (guard trip or host fault); each retry "
                    "draws a fresh fold_in subkey")
    ap.add_argument("--degrade-ladder", default=None,
                    help="comma-separated retry fallback rungs: tier "
                    "names and/or 'tau0' (same spec at tau=0, the "
                    "deterministic ODE limit), e.g. 'standard,tau0'")
    ap.add_argument("--guard-interval", type=int, default=0,
                    help="per-lane finiteness check every N solver steps "
                    "(step scheduler; carried as data — no recompiles); "
                    "any non-zero value also enables the solve "
                    "scheduler's post-solve check. 0 disables")
    ap.add_argument("--inject", action="store_true",
                    help="chaos smoke: seeded fault mix (1 NaN lane, 1 "
                    "raised tick, 1 latency spike) through the serve "
                    "path; implies --guard-interval 4 if unset")
    args = ap.parse_args()
    use_compile_cache()
    if args.arch is None:
        args.arch = "starcoder2-3b" if args.mode == "lm" else "dit-s"
    if args.mode == "lm":
        serve_lm(args)
    else:
        serve_diffusion(args)


if __name__ == "__main__":
    main()
