"""The serving layer's fault policy and lane-step accounting.

Both schedulers — ``ServeEngine`` under ``scheduler="solve"``
(:mod:`repro.serve.engine`) and :class:`~repro.serve.continuous.
ContinuousBatcher` under ``"step"`` — keep only how they queue, dispatch
and harvest. What happens to a request that fails is decided here, once,
by the one :class:`FaultPolicy` an engine builds:

- a failed request retries up to ``max_retries`` times: each retry folds
  its attempt count into the request's RNG streams, runs at the next rung
  of ``degrade_ladder``, and after a host-side fault (not a numerics
  trip) waits ``retry_backoff * 2**attempt`` seconds; past the budget it
  ends as a ``"failed"`` or ``"failed_numerics"`` :class:`ServeResult`;
- ``quarantine_after`` consecutive failures of one bucket hold its
  pending work (never drop it) for ``quarantine_s``; the first request
  through after the cooldown is the probe, and any success ends the
  streak;
- every result leaves through :meth:`FaultPolicy.emit`, which keeps a
  raising ``on_result`` callback from losing other results.

A scheduler hands :meth:`FaultPolicy.fail` its own ``requeue``: the FIFO
list under "solve", the ``(-priority, deadline, arrival)`` queue under
"step". Both schedulers also charge their lane-steps per bucket here, so
``stats()["buckets"]`` means the same under either.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterable, Sequence

from ..core.samplers import SamplerSpec
from .batching import Request, ServeResult, bucket_key, bucket_label

__all__ = ["FaultPolicy"]


class FaultPolicy:
    """Retry, quarantine and degradation for one engine's requests, with
    the counters both schedulers report. The options are those of
    :class:`~repro.serve.engine.ServeEngine` of the same names; ``tiers``
    resolves the ladder's tier names."""

    def __init__(self, *, tiers, on_result: Callable | None = None,
                 max_retries: int = 0,
                 degrade_ladder: Sequence | None = None,
                 retry_backoff: float = 0.05,
                 quarantine_after: int = 3,
                 quarantine_s: float = 1.0):
        self.tiers = tiers
        self.on_result = on_result
        self.max_retries = int(max_retries)
        self.degrade_ladder = tuple(degrade_ladder) if degrade_ladder \
            else ()
        self.retry_backoff = float(retry_backoff)
        self.quarantine_after = int(quarantine_after)
        self.quarantine_s = float(quarantine_s)
        self.counts = {
            "completed": 0, "failed": 0, "failed_numerics": 0,
            "retries": 0, "degraded": 0, "quarantines": 0,
            "callback_errors": 0,
        }
        #: bucket label -> ticks, lane_steps, active_lane_steps,
        #: wasted_lane_steps
        self.buckets: dict[str, dict] = {}
        #: bucket label -> consecutive failures (reset by any success)
        self._fail_streak: dict[str, int] = {}
        #: bucket label -> monotonic time the quarantine lifts
        self._quarantine: dict[str, float] = {}
        self._callback_errs: list[str] = []

    # ------------------------------------------------------------ results
    def emit(self, res: ServeResult) -> ServeResult:
        if self.on_result is not None:
            try:
                self.on_result(res)
            except Exception as e:  # a user callback must not lose
                self.counts["callback_errors"] += 1  # other results
                self._callback_errs.append(repr(e))
                del self._callback_errs[:-8]
        return res

    def done(self, label: str, req: Request, x0, previews=None,
             n_steps: int | None = None) -> ServeResult:
        """``req`` was served from bucket ``label``: emit its result and
        end the bucket's failure streak."""
        if req.degraded_to is not None:
            self.counts["degraded"] += 1
        res = self.emit(ServeResult(
            rid=req.rid, x0=x0, previews=previews, n_steps=n_steps,
            attempts=req.attempt + 1, degraded_to=req.degraded_to))
        self.counts["completed"] += 1
        self._fail_streak.pop(label, None)
        return res

    # ------------------------------------------------------------ failure
    def fail(self, label: str, reqs: Iterable[Request], err, *,
             numerics: bool,
             requeue: Callable[[Request], None]) -> list[ServeResult]:
        """One failure of bucket ``label``, which held ``reqs``: count it
        towards quarantine, then pass each request's retry to ``requeue``
        or, past the retry budget, emit its failure. Returns the failures.
        """
        n = self._fail_streak.get(label, 0) + 1
        self._fail_streak[label] = n
        if n >= self.quarantine_after:
            self._quarantine[label] = time.monotonic() + self.quarantine_s
            self._fail_streak[label] = 0
            self.counts["quarantines"] += 1
        out = []
        for req in reqs:
            if req.attempt < self.max_retries:
                self.counts["retries"] += 1
                attempt = req.attempt + 1
                spec, rung = self._degrade(req, attempt)
                # numerics failures retry immediately (a fresh fold_in
                # subkey / degraded spec is the fix); host-side faults
                # back off exponentially to ride out transient breakage
                not_before = 0.0 if numerics else time.monotonic() \
                    + self.retry_backoff * (2 ** req.attempt)
                requeue(dataclasses.replace(
                    req, spec=spec, attempt=attempt, not_before=not_before,
                    degraded_to=rung))
                continue
            status = "failed_numerics" if numerics else "failed"
            self.counts[status] += 1
            out.append(self.emit(ServeResult(
                rid=req.rid, x0=None, status=status,
                attempts=req.attempt + 1, degraded_to=req.degraded_to,
                error=f"{type(err).__name__}: {err}")))
        return out

    def _degrade(self, req: Request, attempt: int):
        """The retry's spec and rung label. Attempt ``a`` runs at rung
        ``min(a-1, len(ladder)-1)``; an empty ladder retries unchanged.
        A rung is a tier name (resolved through ``tiers``), the literal
        ``"tau0"`` (the deterministic ODE limit: the same spec at tau=0,
        program dropped) or an explicit ``SamplerSpec``."""
        if not self.degrade_ladder:
            return req.spec, req.degraded_to
        entry = self.degrade_ladder[min(attempt - 1,
                                        len(self.degrade_ladder) - 1)]
        if isinstance(entry, SamplerSpec):
            return entry, f"spec:{entry.name}/{entry.n_steps}"
        if entry == "tau0":
            return req.spec.replace(tau=0.0, program=None), "tau0"
        return self.tiers.resolve(entry), entry

    # --------------------------------------------------------- admission
    def held(self, req: Request, now: float) -> bool:
        """True while ``req`` is backed off or its bucket quarantined. A
        check after the cooldown lifts the quarantine: that request is
        the probe."""
        if req.not_before > now:
            return True
        label = bucket_label(bucket_key(req))
        until = self._quarantine.get(label)
        if until is None:
            return False
        if now >= until:
            del self._quarantine[label]
            return False
        return True

    def wait(self, held: Iterable[Request]) -> bool:
        """Sleep towards the earliest time a request of ``held`` becomes
        admittable (backoff expiry or quarantine lift), at most 50 ms so
        new work is seen; False if none ever will."""
        wake = float("inf")
        for req in held:
            w = req.not_before
            until = self._quarantine.get(bucket_label(bucket_key(req)))
            if until is not None:
                w = max(w, until)
            wake = min(wake, w)
        if wake == float("inf"):
            return False
        wait = wake - time.monotonic()
        if wait > 0:
            time.sleep(min(wait, 0.05))
        return True

    # --------------------------------------------------------- accounting
    def count_lane_steps(self, label: str, steps: int, lanes: int,
                         active: int) -> None:
        """Charge ``steps`` solver steps of a ``lanes``-wide batch, of
        which ``active`` lanes served a request, to bucket ``label``."""
        b = self.buckets.get(label)
        if b is None:
            b = self.buckets[label] = {
                "ticks": 0, "lane_steps": 0, "active_lane_steps": 0,
                "wasted_lane_steps": 0}
        b["ticks"] += steps
        b["lane_steps"] += lanes * steps
        b["active_lane_steps"] += active * steps
        b["wasted_lane_steps"] += (lanes - active) * steps

    def stats(self) -> dict:
        """The part of ``stats()`` both schedulers share: the counters,
        the last 8 callback errors, and per-bucket lane-steps with their
        ``occupancy``."""
        buckets = {}
        for label, b in self.buckets.items():
            b = dict(b)
            b["occupancy"] = (b["active_lane_steps"] / b["lane_steps"]
                              if b["lane_steps"] else 0.0)
            buckets[label] = b
        return {**self.counts,
                "callback_error_messages": list(self._callback_errs),
                "buckets": buckets}

    def health(self) -> dict:
        """The part of ``health()`` both schedulers share. ``status`` is
        "degraded" while any bucket is quarantined; ``quarantined`` maps
        bucket labels to seconds of cooldown remaining."""
        now = time.monotonic()
        quarantined = {label: round(until - now, 6)
                       for label, until in self._quarantine.items()
                       if until > now}
        c = self.counts
        return {
            "status": "degraded" if quarantined else "ok",
            "quarantined": quarantined,
            "consecutive_failures": dict(self._fail_streak),
            "completed": c["completed"],
            "failed": c["failed"],
            "failed_numerics": c["failed_numerics"],
            "retries": c["retries"],
            "degraded_results": c["degraded"],
            "quarantines": c["quarantines"],
            "callback_errors": c["callback_errors"],
        }
