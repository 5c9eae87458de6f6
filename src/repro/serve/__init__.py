"""repro.serve — mesh-sharded, continuously-batched diffusion serving.

Turns the plan/execute sampler registry into a service: requests carrying
any registered :class:`~repro.core.samplers.SamplerSpec` are queued,
bucketed, AOT-warmed, and solved together on one device or across a mesh.

::

    submit(spec, shape)                          ServeResult(rid, x0,
         │                                          previews) ── on_result
         ▼                                              ▲
      queue ──▶ bucket by (spec, shape, dtype)          │ mask: pad lanes
                 │  FIFO chunks ≤ max bucket;           │ dropped
                 │  ragged tail -> smallest bucket,     │
                 │  masked pad lanes (PAD_RID)          │
                 ▼                                      │
      per-lane RNG: fold_in(seed, rid)                  │
      (bucket-independent -> re-bucketing               │
       never changes a request's sample)                │
                 │                                      │
                 ▼                                      │
      AOT warmup per bucket:                            │
      jit(run).lower(shapes).compile()                  │
      (zero trace/miss on the hot path;                 │
       tau & coefficient tables are traced              │
       data, so sweeps reuse executables)               │
                 │                                      │
       mesh? ──▶ sample_sharded ── requests on the ─────┤
         │       mesh "data" axis (NamedSharding),      │
         │       plan arrays replicated, x_T carry      │
         │       donated (donate_argnums)               │
         └─────▶ sample_batched ── single-device vmap ──┘

Knobs (:class:`ServeEngine`): ``bucket_sizes`` trade pad waste against
executable count (with a mesh they are rounded up to multiples of the
data-axis size); ``mesh``/``data_axis`` pick the placement
(``repro.launch.mesh.make_test_mesh`` for fake-device tests,
``make_production_mesh`` for pods); ``stream=True`` attaches per-step
denoised ``x0`` previews (the trajectory hook) to every result and fires
``on_result`` per microbatch; ``model_key`` names the model stably so
rebuilt engines over the same weights reuse live executors.

Quickstart::

    from repro.core.samplers import SamplerSpec
    from repro.serve import ServeEngine

    engine = ServeEngine(model_fn, bucket_sizes=(1, 2, 4, 8))
    spec = SamplerSpec.from_nfe("sa", 15, tau=0.6)
    rids = [engine.submit(spec, shape=(32, 8)) for _ in range(12)]
    results = engine.run()          # list[ServeResult], service order
    print(engine.stats())           # requests/s, model-evals/s (real
                                    # requests only), padded_slots, ...

Or name a quality tier instead of a spec — tiers resolve to full specs
at submit time (:mod:`repro.serve.tiers`), so they bucket, warm, and
sample exactly like explicit specs; autotuned programs load straight
from a search artifact::

    from repro.serve import QualityTiers, ServeEngine

    engine = ServeEngine(model_fn,
                         tiers=QualityTiers.from_artifact("tune.json"))
    engine.submit(None, shape=(32, 8), quality_tier="best")

Fault tolerance (:mod:`repro.serve.faults` + engine knobs): per-lane
in-graph numerical guards (``guard_interval`` — carried as data, so
toggling never recompiles), per-bucket containment (one bucket's fault
never aborts another's work), bounded retry-with-degradation
(``max_retries`` + ``degrade_ladder``; each retry folds its attempt
into the RNG streams, attempt 0 stays bitwise), consecutive-failure
quarantine with cooldown, a straggler watchdog, and a seeded chaos
harness (:class:`FaultPlan`/:class:`FaultInjector`) that exercises all
of it deterministically. Retry, quarantine and degradation are one
:class:`FaultPolicy` (:mod:`repro.serve.policy`) that both schedulers
share. ``ServeEngine.health()`` is the poll surface.

Drivers: ``python -m repro.launch.serve --mode diffusion`` (full CLI),
``examples/serve_diffusion.py`` (thin client),
``benchmarks/bench_serving.py`` (bucket/mesh throughput sweeps),
``benchmarks/bench_faults.py`` (goodput under an injected fault mix).
"""

from .batching import (MicroBatch, PAD_RID, Request, ServeResult,
                       bucket_key, bucket_label, choose_bucket, cond_struct,
                       fold_keys, form_microbatches, retry_fold)
from .continuous import ContinuousBatcher, RunningBatch
from .engine import ServeEngine
from .faults import Fault, FaultInjector, FaultPlan, poison_lane
from .policy import FaultPolicy
from .sharding import align_bucket_sizes, auto_mesh, data_axis_size
from .tiers import QualityTiers, default_tiers

__all__ = [
    "ContinuousBatcher",
    "Fault",
    "FaultInjector",
    "FaultPlan",
    "FaultPolicy",
    "MicroBatch",
    "PAD_RID",
    "QualityTiers",
    "Request",
    "RunningBatch",
    "ServeEngine",
    "ServeResult",
    "bucket_label",
    "align_bucket_sizes",
    "auto_mesh",
    "bucket_key",
    "choose_bucket",
    "cond_struct",
    "data_axis_size",
    "default_tiers",
    "fold_keys",
    "form_microbatches",
    "poison_lane",
    "retry_fold",
]
