"""The diffusion serve engine: queue -> microbatch -> compiled solve.

One :class:`ServeEngine` owns one model (``model_fn``), a FIFO request
queue, and the serving loop:

- ``submit()`` enqueues a request: any registered :class:`SamplerSpec`
  (sampler family, NFE, tau, ...) plus a latent shape — and, for
  Denoiser-backed engines, a per-request conditioning pytree and
  guidance scale. Requests with
  different specs/shapes coexist in the queue; the engine groups them by
  ``(spec, shape, dtype, cond structure)`` bucket (see
  :mod:`repro.serve.batching`) — conditioning *values* and the guidance
  scale are traced data and never split a bucket or recompile. The
  spec's ``precision`` ("f32" | "bf16") and ``history`` fields ride the
  bucket key like every other static: a bf16 request is AOT-warmed as
  its own bucket whose scan state and evaluation history live in
  bfloat16 (f32 accumulation in-kernel), halving the hot loop's HBM
  bytes for precision-tolerant traffic.
- ``step()`` serves the oldest bucket as one microbatch: ragged tails are
  padded with *masked* dummy lanes (never duplicated requests), each lane
  draws its initial noise and solve path from ``fold_in(seed, rid)`` so
  results are independent of bucketing, and the whole batch runs through
  one compiled executor — ``sample_sharded`` (requests on the mesh
  ``data`` axis, donated carry) when a mesh is configured, else
  ``sample_batched``.
- the first encounter of a bucket AOT-warms it:
  ``jit(run).lower(...).compile()`` via ``repro.core.samplers.warmup`` —
  after that the hot path never traces (``compile_cache_stats()`` shows
  zero misses across tau sweeps, since tau is traced data).
- ``stream=True`` threads the trajectory hook through: each
  :class:`ServeResult` carries the per-step denoised ``x0`` previews and
  the optional ``on_result`` callback fires as each microbatch completes
  (how a frontend streams previews while later buckets still solve).

Throughput accounting counts **real** requests only: ``model_evals`` is
``spec.nfe`` (guided, solver-level evaluations) per served request, and
``network_evals`` is ``spec.network_nfe`` — under classifier-free
guidance every guided evaluation is one fused network forward over a
*doubled* lane count, so a CFG bucket of B lanes drives 2B network lanes
(warmup compiles exactly that doubled-lane graph, and a padded slot
wastes two network lanes instead of one). Padded lanes are reported
separately as ``padded_slots`` (they cost compute but serve nobody).

Retry, quarantine, degradation, the guarded ``on_result`` callback and
the per-bucket lane-step counters live in one
:class:`~repro.serve.policy.FaultPolicy`, which the engine builds and
shares with the step scheduler; this module keeps the solve scheduler's
FIFO queue, microbatch dispatch and post-solve guard.

Each microbatch is a ``serve.microbatch`` profiler span (as in
:mod:`repro.serve.continuous`) with children ``serve.warm`` (only when
it compiles), ``serve.prepare`` (keys and the initial noise),
``serve.dispatch``, ``serve.sync`` and ``serve.harvest``.
"""

from __future__ import annotations

import time
from typing import Callable, Hashable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from ..core.denoiser import Denoiser
from ..core.samplers import (SamplerSpec, build_plan, compile_cache_stats,
                             sample_batched, sample_sharded, warmup)
from ..runtime import StragglerMonitor
from .batching import (MicroBatch, Request, ServeResult, bucket_label,
                       fold_keys, form_microbatches, retry_fold)
from .continuous import ContinuousBatcher
from .policy import FaultPolicy
from .sharding import align_bucket_sizes, data_axis_size
from .tiers import QualityTiers, default_tiers

__all__ = ["ServeEngine"]


class ServeEngine:
    """Mesh-sharded, continuously-microbatched diffusion sampling service.

    Args:
        model_fn: per-request model — a plain ``(x, t) -> x0_hat``
            closure speaking the plan's parameterization, or a
            :class:`repro.core.denoiser.Denoiser` wrapping a raw
            eps/x0/v-prediction network (with or without classifier-free
            guidance); the executor vmaps it over the request axis. Held
            strongly for the engine's lifetime.
        bucket_sizes: allowed microbatch lane counts; tails take the
            smallest that fits. With a mesh, sizes are rounded up to
            multiples of the data-axis size.
        mesh: optional ``jax.sharding.Mesh``; requests are split over
            ``data_axis``, plan arrays replicated.
        cfg_axis: optional name of a size-2 mesh axis carrying the CFG
            cond/uncond pair (see ``sharding.auto_cfg_mesh``). Requires
            a mesh and a guidance-enabled Denoiser model; numerically
            equivalent to the fused doubled-lane eval, but each device
            runs one branch at the local batch instead of both.
        stream: solve with the trajectory hook and attach per-step x0
            previews to every result.
        on_result: optional callback invoked with each ServeResult as its
            microbatch completes (streaming consumption).
        model_key: stable compile-cache token for ``model_fn`` (lets a
            re-built engine over the same weights reuse live executors).
        noise_seed / solve_seed: bases for the per-request ``fold_in``
            RNG streams (initial noise and solver path respectively).
        tiers: the :class:`~repro.serve.tiers.QualityTiers` map behind
            ``submit(..., quality_tier=...)``; defaults to
            :func:`~repro.serve.tiers.default_tiers`. Load an autotuned
            ladder with ``QualityTiers.from_artifact(path)``.
        max_retries: serve attempts beyond the first for a failed
            request (numerical-guard trip or host-side fault). Each
            retry folds its attempt count into the request's RNG
            streams (attempt 0 is bitwise the base stream) and may run
            degraded (see ``degrade_ladder``). 0 disables retries.
        degrade_ladder: per-retry quality fallback — a sequence of tier
            names (resolved through ``tiers``), the literal ``"tau0"``
            (same spec at tau=0, the deterministic ODE limit), or
            explicit :class:`SamplerSpec` s; attempt ``a`` runs at rung
            ``min(a-1, len-1)``. Empty/None retries at full quality.
        guard_interval: every N solver steps, an in-graph per-lane
            finiteness check on the full family state (step scheduler);
            a tripped lane is masked out and its request fails with
            ``status="failed_numerics"`` (or retries). The interval is
            carried as data — toggling or sweeping it never recompiles.
            Under the solve scheduler, any non-zero value enables a
            post-solve per-lane check on the final latent. 0 disables.
        retry_backoff: base seconds for exponential backoff before a
            host-fault retry (numerics retries re-enqueue immediately —
            the fresh subkey / degraded spec is the fix, not time).
        quarantine_after: consecutive failures of one bucket before it
            is quarantined (its pending work held, not dropped).
        quarantine_s: quarantine cooldown; after it elapses the next
            request through is the probe.
        watchdog: a :class:`repro.runtime.StragglerMonitor` observing
            per-tick (step scheduler) / per-microbatch (solve) wall
            times; defaults to a fresh monitor. ``shed_on_straggler``
            makes a straggler event shed deadline-bearing pending work
            (step scheduler only).
        fault_injector: a :class:`repro.serve.faults.FaultInjector`
            consulted before each dispatch — chaos testing only.
    """

    def __init__(self, model_fn: Callable, *,
                 bucket_sizes: Sequence[int] = (1, 2, 4, 8),
                 mesh=None, data_axis: str = "data",
                 cfg_axis: str | None = None,
                 stream: bool = False,
                 on_result: Callable[[ServeResult], None] | None = None,
                 model_key: Hashable | None = None,
                 noise_seed: int = 7, solve_seed: int = 8,
                 donate: bool | None = None,
                 tiers: QualityTiers | None = None,
                 scheduler: str = "solve", lanes: int = 8,
                 max_pending: int | None = None,
                 max_retries: int = 0,
                 degrade_ladder: Sequence | None = None,
                 guard_interval: int = 0,
                 retry_backoff: float = 0.05,
                 quarantine_after: int = 3,
                 quarantine_s: float = 1.0,
                 watchdog: StragglerMonitor | None = None,
                 shed_on_straggler: bool = False,
                 fault_injector=None):
        if not bucket_sizes:
            raise ValueError("need at least one bucket size")
        if scheduler not in ("solve", "step"):
            raise ValueError(
                f"scheduler={scheduler!r}; expected 'solve' "
                "(whole-solve microbatches) or 'step' (continuous "
                "batching at solver-step granularity)")
        if scheduler == "step" and mesh is not None:
            raise ValueError(
                "the step scheduler is single-device (one vmapped carry "
                "per running batch); use scheduler='solve' with a mesh")
        if cfg_axis is not None and mesh is None:
            raise ValueError(
                "cfg_axis needs a mesh (sharded CFG splits the cond/"
                "uncond pair across a size-2 mesh axis); without one the "
                "engine already runs the fused doubled-lane eval")
        self.model_fn = model_fn
        self.mesh = mesh
        self.data_axis = data_axis
        self.cfg_axis = cfg_axis
        if mesh is not None:
            bucket_sizes = align_bucket_sizes(
                bucket_sizes, data_axis_size(mesh, data_axis))
        self.bucket_sizes = tuple(sorted(set(int(b) for b in bucket_sizes)))
        self.stream = stream
        self.model_key = model_key
        self.donate = donate
        self.tiers = tiers if tiers is not None else default_tiers()
        self.scheduler = scheduler
        self.max_pending = max_pending
        self.guard_interval = int(guard_interval)
        self.policy = FaultPolicy(
            tiers=self.tiers, on_result=on_result, max_retries=max_retries,
            degrade_ladder=degrade_ladder, retry_backoff=retry_backoff,
            quarantine_after=quarantine_after, quarantine_s=quarantine_s)
        self.watchdog = watchdog if watchdog is not None \
            else StragglerMonitor()
        self.shed_on_straggler = shed_on_straggler
        self._inject = fault_injector
        self._noise_base = jax.random.PRNGKey(noise_seed)
        self._solve_base = jax.random.PRNGKey(solve_seed)
        self._queue: list[Request] = []
        self._next_rid = 0
        self._warmed: set[tuple] = set()
        self._stats = {
            "requests": 0, "microbatches": 0, "padded_slots": 0,
            "model_evals": 0, "network_evals": 0, "warmups": 0,
            "serve_s": 0.0,
        }
        self._batcher = None
        if scheduler == "step":
            self._batcher = ContinuousBatcher(
                model_fn, lanes=lanes, stream=stream, model_key=model_key,
                noise_seed=noise_seed, solve_seed=solve_seed,
                max_pending=max_pending, guard_interval=self.guard_interval,
                watchdog=self.watchdog,
                shed_on_straggler=shed_on_straggler,
                fault_injector=fault_injector, policy=self.policy)

    # ------------------------------------------------------------- intake
    def submit(self, spec: SamplerSpec | None, shape: Sequence[int],
               dtype="float32", rid: int | None = None, *,
               cond=None, guidance_scale: float = 1.0,
               quality_tier: str | None = None,
               priority: int = 0, deadline: float | None = None,
               early_exit_tol: float = 0.0,
               min_steps: int | None = None) -> int:
        """Enqueue one request; returns its rid (for RNG identity and
        result matching). An explicit ``rid`` makes a request replayable
        — the same rid always produces the same sample. ``cond`` is the
        request's conditioning pytree (engine model must be a Denoiser;
        only its shape/dtype structure affects bucketing) and
        ``guidance_scale`` its CFG scale (pure data: a scale sweep rides
        one warmed executable). Pass ``quality_tier`` ("draft" |
        "standard" | "best" with default tiers) with ``spec=None`` to let
        the engine's tier map pick the spec — resolution happens here, so
        tier requests bucket (and sample) exactly like explicit-spec
        requests.

        Scheduling knobs (honored by ``scheduler="step"``; the solve
        scheduler serves FIFO at full NFE and ignores them):
        ``priority`` (higher first), ``deadline`` (absolute
        ``time.monotonic()``; expired pending work is shed with
        ``status="shed"``), ``early_exit_tol`` (masked per-lane early
        exit on the predictor-vs-corrector residual; <= 0 disables —
        the disabled path is bitwise the solo solve), ``min_steps``
        (completed steps before an exit may fire; defaults to the spec's
        solver order)."""
        if quality_tier is not None:
            if spec is not None:
                raise ValueError(
                    "pass either spec or quality_tier, not both")
            spec = self.tiers.resolve(quality_tier)
        elif spec is None:
            raise ValueError("need a spec (or a quality_tier)")
        if rid is None:
            rid = self._next_rid
        self._next_rid = max(self._next_rid, rid + 1)
        # validate here, where the scale is still a host float: by serve
        # time it rides the executor as a traced per-lane array, so the
        # base layer's sync-free guard can no longer see its value
        guided = isinstance(self.model_fn, Denoiser) and \
            self.model_fn.guidance
        if not guided and float(guidance_scale) != 1.0:
            raise ValueError(
                "guidance_scale has no effect without a guidance-enabled "
                "Denoiser engine model — it would be silently dropped")
        if cond is not None:
            cond = jax.tree.map(jnp.asarray, cond)
        req = Request(
            rid=rid, spec=spec, shape=tuple(int(s) for s in shape),
            dtype=jnp.dtype(dtype).name, cond=cond,
            guidance_scale=float(guidance_scale),
            priority=int(priority), deadline=deadline,
            early_exit_tol=float(early_exit_tol), min_steps=min_steps)
        if self._batcher is not None:
            self._batcher.enqueue(req)  # admission control lives there
            return rid
        if self.max_pending is not None and \
                len(self._queue) >= self.max_pending:
            raise RuntimeError(
                f"admission control: {len(self._queue)} requests pending "
                f">= max_pending={self.max_pending}; drain with "
                "step()/run() or shed load upstream")
        self._queue.append(req)
        return rid

    def pending(self) -> int:
        if self._batcher is not None:
            return self._batcher.pending()
        return len(self._queue)

    def _eligible(self) -> tuple[list[Request], list[Request]]:
        """Split the queue into (servable now, held) — held requests are
        backed off or their bucket is quarantined."""
        now = time.monotonic()
        ok, held = [], []
        for r in self._queue:
            (held if self.policy.held(r, now) else ok).append(r)
        return ok, held

    def _serve_safe(self, mb: MicroBatch) -> list[ServeResult]:
        """Containment boundary: a fault anywhere in one microbatch's
        warmup or solve (model exception at trace time, injected
        failure, runtime error at the sync barrier) fails ONLY this
        bucket's requests — queue and other buckets are untouched."""
        try:
            with span("serve.microbatch"):
                return self._serve(mb)
        except Exception as err:
            return self.policy.fail(bucket_label(mb.key), mb.requests, err,
                                    numerics=False,
                                    requeue=self._queue.append)

    # ------------------------------------------------------------ serving
    def warmup_bucket(self, mb: MicroBatch) -> None:
        """AOT-compile this microbatch's executor if not already warm.

        The per-request cond prototype comes from the bucket's first
        request (all requests in a bucket share cond structure — it is
        part of the bucket key); under guidance the lowered graph already
        carries the doubled network lane count, so the CFG hot path never
        traces either."""
        ident = (mb.key, mb.size)
        if ident in self._warmed:
            return
        with span("serve.warm"):
            plan = build_plan(mb.spec)
            warmup(plan, self.model_fn, mb.shape, jnp.dtype(mb.dtype),
                   batch=mb.size, mesh=self.mesh, data_axis=self.data_axis,
                   cfg_axis=self.cfg_axis,
                   cond=mb.requests[0].cond, trajectory=self.stream,
                   model_key=self.model_key, donate=self.donate)
        self._warmed.add(ident)
        self._stats["warmups"] += 1

    def step(self) -> list[ServeResult]:
        """Serve one scheduling unit; [] when idle (or mid-solve).

        Under ``scheduler="solve"`` that is one whole microbatch (oldest
        bucket first); under ``"step"`` it is ONE solver step of one
        running batch — joins, leaves, and merges happen between calls.
        """
        if self._batcher is not None:
            return self._batcher.tick()
        if not self._queue:
            return []
        eligible, _ = self._eligible()
        if not eligible:
            return []  # everything is backed off / quarantined
        mb = form_microbatches(eligible, self.bucket_sizes)[0]
        taken = set(id(r) for r in mb.requests)
        self._queue = [r for r in self._queue if id(r) not in taken]
        return self._serve_safe(mb)

    def run(self) -> list[ServeResult]:
        """Drain the queue; results in service order (completion order
        under the step scheduler).

        Under the solve scheduler, microbatches are formed once per drain
        pass (linear in queue length, unlike repeated ``step()`` which
        regroups the remaining queue each call); requests submitted from
        ``on_result`` callbacks are picked up by the next pass.
        """
        if self._batcher is not None:
            return self._batcher.run()
        out: list[ServeResult] = []
        while self._queue:
            eligible, held = self._eligible()
            if not eligible:
                # everything is backed off or quarantined — sleep until
                # the earliest becomes admittable instead of spinning
                if not self.policy.wait(self._queue):
                    break
                continue
            self._queue = held  # retries from _serve_safe append here
            for mb in form_microbatches(eligible, self.bucket_sizes):
                out.extend(self._serve_safe(mb))
        return out

    def _serve(self, mb: MicroBatch) -> list[ServeResult]:
        self.warmup_bucket(mb)
        with span("serve.prepare"):
            spec, shape = mb.spec, mb.shape
            dtype = jnp.dtype(mb.dtype)
            plan = build_plan(spec)
            rids = mb.rids()

            t0 = time.perf_counter()
            noise_keys = fold_keys(self._noise_base, rids)
            solve_keys = fold_keys(self._solve_base, rids)
            attempts = [r.attempt for r in mb.requests] + [0] * mb.n_padded
            if any(attempts):  # retries draw fresh per-attempt subkeys;
                noise_keys = retry_fold(noise_keys, attempts)  # attempt 0
                solve_keys = retry_fold(solve_keys, attempts)  # is bitwise
            scale = spec.resolve_schedule().prior_scale(float(plan.ts[0]))
            x_T = jax.vmap(
                lambda k: scale * jax.random.normal(k, shape, dtype)
            )(noise_keys)
            if self._inject is not None:
                x_T = self._inject.on_solve(self._stats["microbatches"],
                                            mb, x_T)
            cond_b = mb.stacked_cond()
            g_scales = mb.scales()
        with span("serve.dispatch"):
            if self.mesh is not None:
                out = sample_sharded(
                    plan, self.model_fn, x_T, solve_keys, mesh=self.mesh,
                    data_axis=self.data_axis, cfg_axis=self.cfg_axis,
                    cond=cond_b,
                    guidance_scale=g_scales, trajectory=self.stream,
                    model_key=self.model_key, donate=self.donate)
            else:
                out = sample_batched(
                    plan, self.model_fn, x_T, solve_keys, cond=cond_b,
                    guidance_scale=g_scales,
                    trajectory=self.stream, model_key=self.model_key)
        with span("serve.sync"):
            if self.stream:
                x0, traj = out
                previews = jax.block_until_ready(traj["x0"])
            else:
                x0, previews = out, None
            x0 = jax.block_until_ready(x0)
        with span("serve.harvest"):
            dt = time.perf_counter() - t0
            self._stats["serve_s"] += dt
            self.watchdog.observe(self._stats["microbatches"], dt)

            n_real = len(mb.requests)
            self._stats["requests"] += n_real
            self._stats["microbatches"] += 1
            self._stats["padded_slots"] += mb.n_padded
            self._stats["model_evals"] += spec.nfe * n_real
            self._stats["network_evals"] += spec.network_nfe * n_real
            # per-bucket lane-step accounting, same shape of numbers as the
            # step scheduler: here every lane rides the full solve, so a
            # padded lane wastes n_steps lane-steps in one indivisible chunk
            label = bucket_label(mb.key)
            self.policy.count_lane_steps(label, spec.n_steps, mb.size,
                                         n_real)

            # post-solve numerical guard (the solve scheduler has no
            # in-graph per-step check — the whole solve is one dispatch —
            # so any non-zero guard_interval means "check the final latent")
            bad = np.zeros(n_real, bool)
            if self.guard_interval and n_real:
                flat = np.asarray(x0[:n_real], np.float32).reshape(n_real, -1)
                bad = ~np.isfinite(flat).all(axis=1)

            results = []
            for lane, req in enumerate(mb.requests):  # pad lanes dropped here
                if bad[lane]:
                    results.extend(self.policy.fail(
                        label, (req,),
                        ArithmeticError("non-finite final latent"),
                        numerics=True, requeue=self._queue.append))
                    continue
                results.append(self.policy.done(
                    label, req, x0[lane],
                    previews[lane] if previews is not None else None))
            return results

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Engine counters plus a compile-cache snapshot.

        ``model_evals`` counts guided (solver-level) evaluations and
        ``network_evals`` raw network forwards — 2x under classifier-free
        guidance — for real requests only (``spec.nfe`` /
        ``spec.network_nfe`` each); padded lanes show up in
        ``padded_slots``, never in throughput. ``buckets`` breaks lane
        occupancy down per bucket: ``lane_steps`` (compute spent),
        ``active_lane_steps`` (compute that served a request),
        ``wasted_lane_steps`` (padded / free lanes that computed anyway),
        and their ratio ``occupancy`` — the same accounting the step
        scheduler reports, so the two schedulers compare directly.
        Under ``scheduler="step"`` the counters come from the
        continuous batcher (``completed``, ``shed``, ``joins``,
        ``migrations``, ``ticks``, per-tick-exact ``model_evals``).
        """
        if self._batcher is not None:
            s = self._batcher.stats()
            s["compile_cache"] = compile_cache_stats()
            return s
        s = {**self._stats, **self.policy.stats()}
        s["straggler_events"] = len(self.watchdog.events)
        dt = s["serve_s"]
        s["requests_per_s"] = s["requests"] / dt if dt > 0 else 0.0
        s["model_evals_per_s"] = s["model_evals"] / dt if dt > 0 else 0.0
        s["network_evals_per_s"] = s["network_evals"] / dt if dt > 0 else 0.0
        s["compile_cache"] = compile_cache_stats()
        return s

    def health(self) -> dict:
        """Machine-readable health snapshot — no device sync, cheap
        enough for a poll loop. ``status`` is "degraded" while any
        bucket is quarantined, else "ok"; ``quarantined`` maps bucket
        labels to seconds of cooldown remaining."""
        if self._batcher is not None:
            return self._batcher.health()
        return {**self.policy.health(), "scheduler": "solve",
                "pending": len(self._queue),
                "active": 0,  # solve dispatches are synchronous
                "running_batches": 0, "shed": 0,
                "straggler_events": len(self.watchdog.events)}
