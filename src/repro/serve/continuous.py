"""Step-granular continuous batching: the LLM-style scheduler.

The solve-granular engine (``ServeEngine`` with ``scheduler="solve"``)
serves one bucket start-to-finish per dispatch: a straggler bucket blocks
the queue, and a lane freed at solve-end idles until the whole microbatch
returns. This module schedules at **solver-step** granularity instead,
over the step-function protocol in ``repro.core.samplers.stepwise``:

- every bucket key maps to one or more :class:`RunningBatch` es — a fixed
  ``lanes``-wide carry pytree plus its compiled ``StepFns`` — and one
  scheduler **tick** advances every lane of one batch by one solver step
  (round-robin over batches, so buckets interleave fairly instead of
  queueing behind each other),
- requests **join at step boundaries**: admission writes one lane of the
  carry (initial state, per-step ``fold_in`` RNG keys, early-exit knobs)
  while the other lanes are mid-solve; the compiled shape never changes,
- a lane whose request finishes (full solve or masked early exit) is
  **recycled** on the same tick — the next pending request with that
  bucket key joins into it,
- half-empty same-key batches are **merged** by migrating lanes
  (``StepFns.copy`` moves the whole carry slice — state, ring history,
  step index, RNG keys — so migration is bitwise-invisible to the moved
  request), and empty batches retire; their AOT-compiled step functions
  stay in the stepwise cache, so batch churn never recompiles,
- the pending queue is **priority/deadline ordered** — ``(-priority,
  deadline, arrival)`` — with admission control (``max_pending`` bounds
  the queue; ``submit`` raises when full) and deadline shedding (a
  pending request past its deadline returns ``status="shed"`` instead of
  occupying a lane).

Early exit rides the carry's residual channel: SA-Solver's
predictor-vs-corrector residual (free in PEC/PECE — both combines are
computed anyway) is compared against the request's ``early_exit_tol``
each tick, and a lane that satisfies it finishes early under the fixed
compiled shape. ``early_exit_tol <= 0`` disables the exit; the disabled
path through any join/leave/migration churn is bitwise-identical to the
request's solo ``sample_batched`` solve (asserted in
``tests/test_serve.py``).

Accounting is tick-exact: every tick charges ``lanes`` lane-steps to the
batch's bucket, split into active (a real request advanced) and wasted
(free/finished lanes that computed anyway — the price of the fixed
shape). ``stats()["buckets"]`` reports per-bucket occupancy; the
solve-granular engine reports the same shape of numbers, so
``benchmarks/bench_continuous.py`` compares the two schedulers
like-for-like.

Retry, quarantine, degradation, the guarded ``on_result`` callback and
those per-bucket counters live in the engine's
:class:`~repro.serve.policy.FaultPolicy` (``policy=``), shared with the
solve scheduler; this module keeps the pending queue, admission,
deadline and straggler shedding, the tick, harvest and merge.

Each tick is a ``serve.tick`` profiler span (``jax.profiler``'s
``TraceAnnotation``, a no-op with no profiler session) with children
``serve.admit`` (holding ``serve.new_batch`` and ``serve.join``, whose
arguments are the rid and ``queued_s``, the seconds since the request's
first enqueue), ``serve.dispatch``, ``serve.sync`` (the host blocked on
the device), ``serve.harvest`` and ``serve.merge``. They land on the
profiler's host plane, on the clock of the device ops.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Hashable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from ..core.denoiser import Denoiser
from ..core.samplers import (build_plan, fresh_carry, make_stepfns,
                             stepwise_cache_stats)
from ..runtime import StragglerMonitor
from .batching import Request, ServeResult, bucket_key, bucket_label
from .policy import FaultPolicy

__all__ = ["ContinuousBatcher", "RunningBatch"]


class RunningBatch:
    """One fixed-width carry mid-flight: ``lanes`` slots, each free or
    owned by a request at its own step index."""

    __slots__ = ("key", "plan", "fns", "arrays", "carry", "requests",
                 "previews", "scale", "M")

    def __init__(self, key, plan, fns, arrays, carry, lanes, scale, M):
        self.key = key
        self.plan = plan
        self.fns = fns
        self.arrays = arrays
        self.carry = carry
        self.requests: list[Request | None] = [None] * lanes
        self.previews: list[list] = [[] for _ in range(lanes)]
        self.scale = scale  # prior noise scale (host float)
        self.M = M

    @property
    def lanes(self) -> int:
        return len(self.requests)

    @property
    def n_active(self) -> int:
        return sum(r is not None for r in self.requests)

    def free_lanes(self) -> list[int]:
        return [i for i, r in enumerate(self.requests) if r is None]


class ContinuousBatcher:
    """The step-granular scheduler behind ``ServeEngine(scheduler="step")``.

    Single-device (the carry is one vmapped batch); the solve-granular
    scheduler remains the mesh path. See the module docstring for the
    scheduling model; ``policy`` decides retries and quarantine.
    """

    def __init__(self, model_fn: Callable, *, policy: FaultPolicy,
                 lanes: int = 8, stream: bool = False,
                 model_key: Hashable | None = None,
                 noise_seed: int = 7, solve_seed: int = 8,
                 max_pending: int | None = None,
                 guard_interval: int = 0,
                 watchdog: StragglerMonitor | None = None,
                 shed_on_straggler: bool = False,
                 fault_injector=None):
        if lanes < 1:
            raise ValueError("need at least one lane")
        self.model_fn = model_fn
        self.policy = policy
        self.lanes = int(lanes)
        self.stream = stream
        self.model_key = model_key
        self.max_pending = max_pending
        self.guard_interval = int(guard_interval)
        self.watchdog = watchdog if watchdog is not None \
            else StragglerMonitor()
        self.shed_on_straggler = shed_on_straggler
        self._inject = fault_injector
        self._noise_base = jax.random.PRNGKey(noise_seed)
        self._solve_base = jax.random.PRNGKey(solve_seed)
        self._pending: list[tuple] = []  # (sort_key, seq, Request)
        self._seq = 0
        self._rr = 0
        self._batches: list[RunningBatch] = []
        #: (shape, dtype, M, scale) -> jitted (rid, attempt) ->
        #: (x_T, step keys); one dispatch per join instead of a chain of
        #: eager RNG ops
        self._derive: dict[tuple, Callable] = {}
        self._network_factor = 2 if (isinstance(model_fn, Denoiser)
                                     and model_fn.guidance) else 1
        self._stats = {
            "requests": 0, "shed": 0, "joins": 0, "migrations": 0,
            "ticks": 0, "model_evals": 0, "network_evals": 0,
            "warmups": 0, "serve_s": 0.0, "straggler_sheds": 0,
        }
        self._shed_deadlines = False

    # ------------------------------------------------------------- intake
    def enqueue(self, req: Request) -> None:
        """Admit one request to the pending queue (priority/deadline
        ordered). Raises when admission control rejects it."""
        if self.max_pending is not None and \
                len(self._pending) >= self.max_pending:
            raise RuntimeError(
                f"admission control: {len(self._pending)} requests "
                f"pending >= max_pending={self.max_pending}; drain with "
                "tick()/run() or shed load upstream")
        if req.enqueued is None:
            req = dataclasses.replace(req, enqueued=time.monotonic())
        self._requeue(req)
        self._stats["requests"] += 1

    def _requeue(self, req: Request) -> None:
        """Queue ``req`` by ``(-priority, deadline, arrival)``; a retry
        comes back this way and keeps its first enqueue time."""
        dl = float("inf") if req.deadline is None else float(req.deadline)
        self._pending.append(((-int(req.priority), dl, self._seq), req))
        self._seq += 1

    def pending(self) -> int:
        return len(self._pending)

    def active(self) -> int:
        return sum(b.n_active for b in self._batches)

    # ---------------------------------------------------------- internals
    def _new_batch(self, req: Request) -> RunningBatch:
        with span("serve.new_batch"):
            key = bucket_key(req)
            spec = key[0]
            plan = build_plan(spec)
            fns = make_stepfns(plan, self.model_fn, req.shape, req.dtype,
                               self.lanes, cond=req.cond,
                               guidance_scale=req.guidance_scale,
                               stream=self.stream, model_key=self.model_key)
            arrays = fns.adapter.arrays(plan)
            carry = fresh_carry(plan, self.lanes, req.shape, req.dtype,
                                cond=req.cond, model_fn=self.model_fn,
                                guard_every=self.guard_interval)
            if not fns.warmed:
                fns.warm(arrays, carry, cond=req.cond)
                self._stats["warmups"] += 1
            scale = spec.resolve_schedule().prior_scale(float(plan.ts[0]))
            M = fns.adapter.n_steps_of(arrays)
            batch = RunningBatch(key, plan, fns, arrays, carry, self.lanes,
                                 scale, M)
            self._batches.append(batch)
            return batch

    def _derive_fn(self, batch: RunningBatch, req: Request) -> Callable:
        """Jitted rid -> (x_T, per-step keys) for one batch geometry.

        Identical derivations to the solve-granular path: noise and
        solve streams are pure in the rid, and the per-step key split
        matches what the whole-solve executor does internally — so a
        request's bytes are independent of lane, batch, and scheduler.
        The rid and retry attempt are traced arguments (one compile per
        geometry, reused across every join, batch churn, and retry).
        Attempt 0 is bitwise the base stream; a retry folds its attempt
        count in for a fresh subkey (the stream that just failed is
        never replayed)."""
        dkey = (req.shape, req.dtype, batch.M, batch.scale)
        fn = self._derive.get(dkey)
        if fn is None:
            shape, dtype = req.shape, jnp.dtype(req.dtype)
            scale, M = batch.scale, batch.M
            nb, sb = self._noise_base, self._solve_base

            def derive(rid, attempt):
                retry = attempt > 0
                nk = jax.random.fold_in(nb, rid)
                nk = jnp.where(retry, jax.random.fold_in(nk, attempt), nk)
                sk = jax.random.fold_in(sb, rid)
                sk = jnp.where(retry, jax.random.fold_in(sk, attempt), sk)
                x_T = scale * jax.random.normal(nk, shape, dtype)
                return x_T, jax.random.split(sk, M)

            fn = self._derive[dkey] = jax.jit(derive)
        return fn

    def _join(self, batch: RunningBatch, lane: int, req: Request) -> None:
        # arguments are built only while a profiler session records them
        with (span("serve.join", rid=req.rid,
                   queued_s=time.monotonic() - req.enqueued)
              if span.is_enabled() else span("serve.join")):
            spec = batch.key[0]
            x_T, keys = self._derive_fn(batch, req)(np.int32(req.rid),
                                                    np.int32(req.attempt))
            min_i = req.min_steps
            if min_i is None:
                min_i = max(int(spec.predictor_order),
                            int(spec.corrector_order))
            batch.carry = batch.fns.join(
                batch.arrays, batch.carry, lane, x_T, keys,
                float(req.early_exit_tol), int(min_i),
                float(req.guidance_scale), guard=self.guard_interval,
                cond=req.cond)
            batch.requests[lane] = req
            batch.previews[lane] = []
            self._stats["joins"] += 1

    def _admit(self) -> list:
        """Priority-ordered admission: shed expired, hold quarantined /
        backed-off retries, fill free lanes, open new batches for
        whatever has no lane. A request whose bucket fails to build or
        warm (e.g. a raising model fn at trace time) fails alone — the
        other buckets' work is untouched. Returns shed/failed results."""
        if not self._pending:
            return []
        now = time.monotonic()
        self._pending.sort(key=lambda e: e[0])
        shed_deadlines = self._shed_deadlines
        self._shed_deadlines = False
        results, held = [], []
        # snapshot: policy.fail() requeues retries onto self._pending, and
        # those must wait for the NEXT admission pass (backoff aside,
        # re-admitting a failing request in the same pass would loop)
        queue, self._pending = self._pending, []
        for sort_key, req in queue:
            if req.deadline is not None and now > float(req.deadline):
                self._stats["shed"] += 1
                results.append(self.policy.emit(ServeResult(
                    rid=req.rid, x0=None, status="shed")))
                continue
            if shed_deadlines and req.deadline is not None:
                # straggler watchdog fired: deadline-bearing work can't
                # meet its SLO behind a slow tick — shed it now instead
                # of letting it expire in the queue
                self._stats["shed"] += 1
                self._stats["straggler_sheds"] += 1
                results.append(self.policy.emit(ServeResult(
                    rid=req.rid, x0=None, status="shed")))
                continue
            if self.policy.held(req, now):
                held.append((sort_key, req))
                continue
            key = bucket_key(req)
            try:
                lane_home = None
                for b in self._batches:
                    if b.key == key:
                        free = b.free_lanes()
                        if free:
                            lane_home = (b, free[0])
                            break
                if lane_home is None:
                    b = self._new_batch(req)
                    lane_home = (b, 0)
                self._join(lane_home[0], lane_home[1], req)
            except Exception as err:
                results.extend(self.policy.fail(
                    bucket_label(key), (req,), err, numerics=False,
                    requeue=self._requeue))
        self._pending.extend(held)
        return results

    def _harvest(self, batch: RunningBatch, aux) -> list:
        """Collect finished + guard-tripped lanes after one step; frees
        them in place."""
        # one host round-trip per tick: the flags and step indices come
        # back together (each device_get is a sync barrier on the tick);
        # the numerical-guard trips ride the same fetch
        with span("serve.sync"):
            flags = jax.device_get(
                {k: aux[k] for k in ("finished", "stepped", "failed", "i")})
        with span("serve.harvest"):
            fin, stepped, bad = (flags["finished"], flags["stepped"],
                                 flags["failed"])
            if self.stream:
                for lane, req in enumerate(batch.requests):
                    if req is not None and stepped[lane]:
                        batch.previews[lane].append(aux["x0"][lane])
            if not fin.any() and not bad.any():
                return []
            steps = flags["i"]
            label = bucket_label(batch.key)
            results = []
            for lane, req in enumerate(batch.requests):
                if req is None:
                    continue
                if bad[lane]:
                    # in-graph guard tripped: the lane was already masked
                    # out; free it and retry/fail the request
                    results.extend(self.policy.fail(
                        label, (req,), ArithmeticError(
                            f"non-finite state at step {int(steps[lane])}"),
                        numerics=True, requeue=self._requeue))
                    batch.requests[lane] = None
                    batch.previews[lane] = []
                    continue
                if not fin[lane]:
                    continue
                previews = None
                if self.stream:
                    previews = jnp.stack(batch.previews[lane])
                results.append(self.policy.done(
                    label, req, batch.carry["x_final"][lane], previews,
                    n_steps=int(steps[lane])))
                batch.requests[lane] = None
                batch.previews[lane] = []
            return results

    def _merge(self) -> None:
        """Fold same-key half-empty batches together (migrating each
        lane's full carry slice) and retire empties."""
        by_key: dict[tuple, list[RunningBatch]] = {}
        for b in self._batches:
            by_key.setdefault(b.key, []).append(b)
        retired = []
        for key, group in by_key.items():
            group.sort(key=lambda b: b.n_active)
            i, j = 0, len(group) - 1
            while i < j:
                src, dst = group[i], group[j]
                free = dst.free_lanes()
                movable = [(l, r) for l, r in enumerate(src.requests)
                           if r is not None]
                if len(movable) > len(free):
                    break  # smallest doesn't fit in the fullest's gaps
                for (src_lane, req), dst_lane in zip(movable, free):
                    dst.carry = dst.fns.copy(dst.carry, src.carry,
                                             dst_lane, src_lane)
                    dst.requests[dst_lane] = req
                    dst.previews[dst_lane] = src.previews[src_lane]
                    self._stats["migrations"] += 1
                retired.append(src)
                i += 1
        pending_keys = {bucket_key(r) for _, r in self._pending}
        for b in self._batches:
            if b.n_active == 0 and b.key not in pending_keys \
                    and b not in retired:
                retired.append(b)
        if retired:
            self._batches = [b for b in self._batches if b not in retired]
            self._rr = 0

    def _contain(self, batch: RunningBatch, err: Exception) -> list:
        """One bucket's tick raised: fail ONLY that batch's in-flight
        requests (retry path included) and drop the batch — its carry
        may hold a poisoned dispatch. The compiled step functions stay
        cached, so a post-quarantine probe re-warms nothing."""
        results = self.policy.fail(
            bucket_label(batch.key),
            [r for r in batch.requests if r is not None], err,
            numerics=False, requeue=self._requeue)
        self._batches.remove(batch)
        self._rr = 0
        return results

    # ------------------------------------------------------------ serving
    def tick(self) -> list:
        """One scheduler tick: admit, advance one batch, harvest, merge.

        Per-tick execution is containment-wrapped: an exception (model
        fault, injected failure, runtime error surfacing at the tick's
        sync barrier) fails only the stepped batch's requests; every
        other batch and the pending queue are untouched. Returns the
        results completed this tick (possibly empty).
        """
        t0 = time.perf_counter()
        with span("serve.tick"):
            with span("serve.admit"):
                results = self._admit()
            if not self._batches:
                self._stats["serve_s"] += time.perf_counter() - t0
                return results
            self._rr %= len(self._batches)
            batch = self._batches[self._rr]
            self._rr += 1
            n_active = batch.n_active
            tick_no = self._stats["ticks"]
            try:
                if self._inject is not None:
                    self._inject.on_tick(tick_no, batch)
                with span("serve.dispatch"):
                    batch.carry, aux = batch.fns.step(batch.arrays,
                                                      batch.carry)
                self._stats["ticks"] += 1
                evals = batch.fns.adapter.evals_per_tick * n_active
                self._stats["model_evals"] += evals
                self._stats["network_evals"] += evals * self._network_factor
                self.policy.count_lane_steps(bucket_label(batch.key), 1,
                                             batch.lanes, n_active)
                results.extend(self._harvest(batch, aux))
            except Exception as err:
                results.extend(self._contain(batch, err))
            if results or self._pending:
                with span("serve.merge"):
                    self._merge()
            dt = time.perf_counter() - t0
            self._stats["serve_s"] += dt
            # watchdog: injected latency, a straggling device, or a slow
            # host all show up as a per-tick wall-time outlier
            if self.watchdog.observe(tick_no, dt) and self.shed_on_straggler:
                self._shed_deadlines = True
            return results

    def run(self) -> list:
        """Drain pending + running work; results in completion order."""
        out = []
        while self._pending or self._batches:
            got = self.tick()
            out.extend(got)
            if got or self._batches:
                continue
            if not self._pending:
                break
            # pending-only: everything is backed off or quarantined —
            # sleep until the earliest becomes admittable instead of
            # spinning (quarantine cooldowns are wall-clock)
            if not self.policy.wait(r for _, r in self._pending):
                break
        return out

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        s = {**self._stats, **self.policy.stats()}
        dt = s["serve_s"]
        s["requests_per_s"] = s["completed"] / dt if dt > 0 else 0.0
        s["model_evals_per_s"] = s["model_evals"] / dt if dt > 0 else 0.0
        s["stepwise_cache"] = stepwise_cache_stats()
        s["straggler_events"] = len(self.watchdog.events)
        return s

    def health(self) -> dict:
        """Machine-readable health snapshot (no device sync)."""
        return {**self.policy.health(), "scheduler": "step",
                "pending": len(self._pending), "active": self.active(),
                "running_batches": len(self._batches),
                "shed": self._stats["shed"],
                "straggler_events": len(self.watchdog.events)}
