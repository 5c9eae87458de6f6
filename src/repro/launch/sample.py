"""Diffusion sampling driver: any registered sampler over any backbone.

    PYTHONPATH=src python -m repro.launch.sample --arch dit-s --smoke \
        --sampler sa --batch 8 --seq 64 --nfe 20 --tau 1.0 \
        --prediction v --guidance-scale 3.0

This is the paper's technique as a first-class serving feature: the
backbone (any arch built with denoiser_latent) is the x0-prediction model
x_theta, and ``--sampler`` selects any entry in the plan/execute registry
(SA-Solver Algorithm 1 by default, or any baseline) at runtime without
code changes. ``--nfe`` is routed through ``SamplerSpec.from_nfe`` so the
model-evaluation budget means the same thing for every sampler and mode
(PEC: NFE = steps + 1, PECE: 2*steps + 1, DDIM-like: steps, Heun-like:
2*steps).

``--prediction`` re-expresses the backbone in any checkpoint convention
(eps / x0 / v — the zoo backbones are natively x0) and wraps it in the
:class:`repro.core.denoiser.Denoiser` adapter, which converts back to the
plan's parameterization in-graph — the round trip exercises exactly the
code path a real eps- or v-prediction checkpoint takes.
``--guidance-scale`` enables classifier-free guidance (cond/uncond fused
into one doubled-lane network eval; the scale is traced data), and
``--cond-file`` loads a ``.npy`` conditioning array threaded to the
network alongside ``x`` (the unconditional zoo backbones consume it as an
input-space prompt added to the latent). ``--cfg-shard`` places the
cond/uncond pair on a size-2 ``cfg`` mesh axis instead of doubling the
local batch (needs >=2 devices and guidance on). ``--program`` attaches a
per-step solver program (preset name, inline JSON, or ``@file.json``)
assigning per-interval orders, P/PEC/PECE mode, and tau — see the README
"Step programs" section. ``--feature-cache`` enables DeepCache-style
step-to-step reuse of the backbone's mid-block features (``K`` refreshes
every K-th solver step; ``residual:T`` refreshes when the free PECE
predictor-vs-corrector residual exceeds T) for backbones exposing
``denoise_cached``.
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, get_smoke
from ..core import Denoiser, convert_prediction, get_schedule
from ..core.denoiser import CachedNetwork
from ..core.programs import list_presets, parse_program
from ..core.samplers import SamplerSpec, Sampler, get_family, list_samplers
from ..models import build_model, init_params


def build_denoiser(arch: str, smoke: bool, latent: int | None):
    cfg = get_smoke(arch) if smoke else get_config(arch)
    if getattr(cfg, "denoiser_latent", None) is None:
        import dataclasses
        cfg = dataclasses.replace(cfg, denoiser_latent=latent or 16)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs(), jnp.float32)
    return cfg, model, params


def as_prediction_network(model, schedule, prediction: str):
    """Re-express an x0-prediction backbone as an eps/x0/v network with a
    cond input — the ``(params, x, t, cond) -> prediction`` contract a
    Denoiser built with ``params=`` wraps. ``cond`` (when given) is an
    input-space prompt added to the latent; the output is converted
    in-graph to ``prediction``."""

    def network(params, x, t, cond):
        h = x if cond is None else x + cond
        # per-lane executors (sample_batched / sample_sharded / serve)
        # call with an unbatched [S, dz] latent — re-rank for the model
        lane = h.ndim == 2
        x0 = model.denoise(params, h[None] if lane else h, t)
        x0 = x0[0] if lane else x0
        return convert_prediction(x0, x, t, "x0", prediction, schedule)

    return network


def as_conditioned_network(model, schedule, prediction: str):
    """The sibling of :func:`as_prediction_network` for a backbone that
    takes its own conditioning (``model.denoise(params, z, t, cond)``,
    predicting ``model.cfg.prediction``): ``cond`` goes to the backbone
    whole, and the output is converted in-graph to ``prediction``. The
    unconditional branch is the Denoiser's ``null_cond``."""

    def network(params, x, t, cond):
        lane = x.ndim == 2
        if lane:
            x1, cond = x[None], jax.tree.map(lambda c: c[None], cond)
        out = model.denoise(params, x1 if lane else x, t, cond)
        out = out[0] if lane else out
        return convert_prediction(out, x, t, model.cfg.prediction,
                                  prediction, schedule)

    return network


def as_cached_network(model, schedule, prediction: str):
    """The feature-cached twin of :func:`as_prediction_network`: a
    :class:`CachedNetwork` whose ``call`` threads the mid-block feature
    pytree through ``model.denoise_cached`` and whose ``init`` builds the
    zero cache for a latent. Rank-polymorphic like the plain network.
    Refuses backbones without the cached protocol."""
    for attr in ("denoise_cached", "feature_shape"):
        if not hasattr(model, attr):
            raise SystemExit(
                f"--feature-cache needs a backbone with {attr}(); "
                f"{type(model).__name__} has none")

    def call(params, x, t, cond, feats, refresh):
        h = x if cond is None else x + cond
        lane = h.ndim == 2
        x0, new = model.denoise_cached(
            params, h[None] if lane else h, t,
            feats=feats[None] if lane else feats, refresh=refresh)
        if lane:
            x0, new = x0[0], new[0]
        return convert_prediction(x0, x, t, "x0", prediction, schedule), new

    def init(x):
        lane = x.ndim == 2
        shape = (1, *x.shape) if lane else x.shape
        aval = model.feature_shape(shape[0], shape[1])
        feats = jnp.zeros(aval.shape, aval.dtype)
        return feats[0] if lane else feats

    return CachedNetwork(call=call, init=init)


def parse_feature_cache(text: str | None):
    """``"K"`` -> interval K; ``"residual:T"`` -> residual-gated with
    threshold T (the SamplerSpec.feature_cache encodings)."""
    if text is None:
        return None
    if text.startswith("residual:"):
        return ("residual", float(text.split(":", 1)[1]))
    return int(text)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="dit-s")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--latent", type=int, default=None)
    ap.add_argument("--sampler", default="sa", choices=list_samplers())
    ap.add_argument("--nfe", type=int, default=20)
    ap.add_argument("--tau", type=float, default=1.0)
    ap.add_argument("--predictor", type=int, default=3)
    ap.add_argument("--corrector", type=int, default=3)
    ap.add_argument("--mode", default="PEC", choices=["PEC", "PECE"])
    ap.add_argument("--program", default=None,
                    help="per-step solver program: a preset name "
                    f"({', '.join(list_presets())}), an inline JSON "
                    "object, or @path to a JSON file — assigns per-"
                    "interval predictor/corrector order, P/PEC/PECE "
                    "mode, and tau (shadows --tau/--predictor/"
                    "--corrector/--mode)")
    ap.add_argument("--grid", default="logsnr",
                    choices=["time", "logsnr", "karras"])
    ap.add_argument("--schedule", default="vp_linear")
    ap.add_argument("--prediction", default="data",
                    choices=["data", "x0", "noise", "eps", "v"],
                    help="network output convention the backbone is "
                    "served as (adapter converts in-graph)")
    ap.add_argument("--guidance-scale", type=float, default=None,
                    help="classifier-free guidance scale (enables the "
                    "guided executor; scale itself is traced data)")
    ap.add_argument("--cond-file", default=None,
                    help=".npy conditioning array, broadcastable to the "
                    "latent (seq, dz)")
    ap.add_argument("--combine", default="einsum",
                    choices=["einsum", "kernel", "fused"],
                    help="SA combine path: XLA einsum, the Pallas "
                    "sa_update kernel, or the dual-output fused "
                    "predictor+corrector kernel (one pass over the "
                    "history; ring layout)")
    ap.add_argument("--history", default="ring",
                    choices=["ring", "concat"],
                    help="SA evaluation-history layout (concat is the "
                    "legacy re-materializing baseline)")
    ap.add_argument("--precision", default="f32",
                    choices=["f32", "bf16"],
                    help="hot-loop precision policy: bf16 carries the "
                    "scan state/history in bfloat16 with f32 "
                    "accumulation")
    ap.add_argument("--feature-cache", default=None,
                    help="step-to-step backbone feature caching: an "
                    "integer K (refresh the mid-block cache every K-th "
                    "solver step) or residual:T (refresh when the free "
                    "PECE predictor-vs-corrector residual exceeds T)")
    ap.add_argument("--cfg-shard", action="store_true",
                    help="run classifier-free guidance with the cond/"
                    "uncond pair sharded over a size-2 'cfg' mesh axis "
                    "(needs --guidance-scale and >=2 devices) instead "
                    "of the fused doubled-lane eval")
    args = ap.parse_args()

    cfg, model, params = build_denoiser(args.arch, args.smoke, args.latent)
    dz = cfg.denoiser_latent
    schedule = get_schedule(args.schedule)
    guidance = args.guidance_scale is not None
    g_scale = 1.0 if args.guidance_scale is None else args.guidance_scale
    program = None
    if args.program is not None:
        if not get_family(args.sampler).full_programs:
            raise SystemExit(
                "--program needs a family that consumes full step "
                "programs (the multistep core: sa, seeds, "
                f"dpmpp_multistep); {args.sampler!r} only honors the "
                "tau track")
        # presets are stamped at the largest step count whose own cost
        # (PECE steps evaluate twice) fits --nfe; an explicit JSON
        # program dictates its own step count through from_nfe, which
        # re-checks the budget
        program = parse_program(args.program, args.nfe - 1, tau=args.tau,
                                nfe=args.nfe)
    fc = parse_feature_cache(args.feature_cache)
    spec = SamplerSpec.from_nfe(
        args.sampler, args.nfe,
        schedule=schedule, grid=args.grid,
        tau=args.tau, predictor_order=args.predictor,
        corrector_order=args.corrector, mode=args.mode,
        program=program,  # shadows the four fields above when set
        combine=args.combine, history=args.history,
        precision=args.precision,
        prediction=args.prediction, guidance=guidance,
        feature_cache=fc,
    )
    sampler = Sampler(spec)

    cond = None
    if args.cond_file is not None:
        cond = jnp.asarray(np.load(args.cond_file), jnp.float32)
    model_fn = Denoiser(
        as_prediction_network(model, schedule, args.prediction),
        schedule, prediction=args.prediction, guidance=guidance,
        cached=(as_cached_network(model, schedule, args.prediction)
                if fc is not None else None),
        params=params)

    mesh = None
    if args.cfg_shard:
        from ..serve.sharding import auto_cfg_mesh
        if not guidance:
            raise SystemExit("--cfg-shard needs --guidance-scale")
        try:
            mesh = auto_cfg_mesh()
        except ValueError as e:
            raise SystemExit(f"--cfg-shard: {e}")

    xT = sampler.init_noise(jax.random.PRNGKey(1), (args.batch, args.seq, dz))

    def run(seed: int):
        key = jax.random.PRNGKey(seed)
        if mesh is None:
            return sampler.sample(model_fn, xT, key, cond=cond,
                                  guidance_scale=g_scale)
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            key, jnp.arange(args.batch))
        batch_cond = None
        if cond is not None:
            batch_cond = jnp.broadcast_to(
                cond, (args.batch,) + tuple(cond.shape[-2:]))
        return sampler.sample_sharded(
            model_fn, xT, keys, mesh=mesh, data_axis="data",
            cfg_axis="cfg", cond=batch_cond,
            guidance_scale=jnp.full((args.batch,), g_scale))

    t0 = time.perf_counter()
    x0 = jax.block_until_ready(run(2))
    t1 = time.perf_counter()
    x0b = jax.block_until_ready(run(3))
    t2 = time.perf_counter()
    print(f"arch={cfg.name} latent={dz} sampler={args.sampler} "
          f"NFE={sampler.nfe} (network NFE={spec.network_nfe}) "
          f"(requested {args.nfe}) steps={spec.n_steps} "
          + (f"program={args.program}"  # the program shadows tau/P/C/mode
             if program is not None else
             f"tau={args.tau} P{args.predictor}C{args.corrector} "
             f"{args.mode}")
          + f" prediction={args.prediction} "
          f"guidance={g_scale if guidance else 'off'}"
          + (f" cfg_shard={mesh.devices.shape}" if mesh is not None else "")
          + (f" feature_cache={fc}" if fc is not None else ""))
    print(f"compile+run {t1-t0:.2f}s, steady {t2-t1:.2f}s; "
          f"out mean={float(jnp.mean(x0)):.4f} std={float(jnp.std(x0)):.4f} "
          f"finite={bool(jnp.all(jnp.isfinite(x0)))}")
    assert bool(jnp.all(jnp.isfinite(x0)))


if __name__ == "__main__":
    main()
