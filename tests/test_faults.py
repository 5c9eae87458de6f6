"""Fault tolerance: numerical guards, containment, retry, quarantine.

Covers: the in-graph per-lane numerical guard (a NaN'd lane fails alone
— its neighbours' bytes stay bitwise-identical to solo solves — and
toggling/sweeping the guard interval never recompiles, since the
interval is carry DATA); per-bucket containment in BOTH schedulers (a
model fn that raises at trace time fails only its own bucket's
requests); bounded retry with per-attempt ``fold_in`` subkeys and the
tau->0 degradation ladder; consecutive-failure quarantine with cooldown
+ recovery probe; the straggler watchdog counter; guarded ``on_result``
callbacks; ``AsyncCheckpointer.close()`` surfacing worker errors; the
``health()`` snapshot; seeded :class:`FaultPlan` determinism; and the
feature-cached draft tier resolving bitwise-identically to its explicit
spec (ROADMAP: tiers spanning eval cost).
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer
from repro.core import get_schedule
from repro.core.samplers import (SamplerSpec, clear_compile_cache,
                                 clear_stepwise_cache, compile_cache_stats,
                                 stepwise_cache_stats)
from repro.runtime import InjectedFailure
from repro.serve import (Fault, FaultInjector, FaultPlan, ServeEngine,
                         default_tiers, poison_lane)

SCHED = get_schedule("vp_linear")
SPEC = SamplerSpec(name="sa", schedule=SCHED, n_steps=8, mode="PECE",
                   tau=0.7)
SHAPE = (16, 2)


# fusion-stable model (see tests/test_serve.py): bitwise assertions are
# about the fault machinery adding NOTHING, not about XLA re-fusion
def STABLE(x, t):
    return 0.3 * x * jnp.cos(t)


def step_engine(**kw):
    kw.setdefault("scheduler", "step")
    kw.setdefault("lanes", 4)
    return ServeEngine(STABLE, **kw)


def solo_refs(rids, spec=SPEC, shape=SHAPE):
    eng = ServeEngine(STABLE, bucket_sizes=(1,))
    for r in rids:
        eng.submit(spec, shape, rid=r)
    return {res.rid: np.asarray(res.x0) for res in eng.run()}


# ------------------------------------------------------- numerical guard
def test_guard_trips_nan_and_isolates_lanes():
    """Acceptance: NaN injected into one lane mid-solve -> that request
    alone fails with status="failed_numerics"; every other lane of the
    same running batch returns bytes bitwise-identical to its solo
    solve."""
    rids = [0, 1, 2, 3]
    ref = solo_refs(rids)
    inj = FaultInjector(FaultPlan((Fault("nan", tick=3, rid=1),)))
    eng = step_engine(guard_interval=2, fault_injector=inj)
    for r in rids:
        eng.submit(SPEC, SHAPE, rid=r)
    out = {res.rid: res for res in eng.run()}
    assert len(out) == 4
    assert out[1].status == "failed_numerics"
    assert out[1].x0 is None and out[1].attempts == 1
    assert "non-finite" in out[1].error
    for r in (0, 2, 3):
        assert out[r].status == "ok"
        assert (np.asarray(out[r].x0) == ref[r]).all(), f"rid {r}"
    assert inj.fired and inj.fired[0][0] == "nan"
    s = eng.stats()
    assert s["failed_numerics"] == 1 and s["completed"] == 3


def test_guard_interval_is_data_zero_cache_miss():
    """The guard interval rides the carry as data: serving with the
    guard off, then at two different intervals, shares ONE compiled step
    family — and (fault-free) all three produce identical bytes."""
    clear_stepwise_cache()
    outs = []
    for guard in (0, 3, 1):
        eng = step_engine(guard_interval=guard)
        for r in range(3):
            eng.submit(SPEC, SHAPE, rid=r)
        outs.append({res.rid: np.asarray(res.x0) for res in eng.run()})
    s = stepwise_cache_stats()
    assert s["misses"] == 1, s
    for got in outs[1:]:
        for r in range(3):
            assert (got[r] == outs[0][r]).all(), f"rid {r}"


def test_solve_scheduler_post_solve_guard_and_retry():
    """Solve scheduler: a NaN'd initial lane is caught by the post-solve
    check, retried on a fresh fold_in subkey, and succeeds — while the
    healthy lanes of the faulted microbatch return bitwise the fault-free
    bytes, with zero extra compiles (the retry pads into the same bucket
    size)."""
    clean = ServeEngine(STABLE, bucket_sizes=(4,))
    for r in range(4):
        clean.submit(SPEC, SHAPE, rid=r)
    ref = {res.rid: np.asarray(res.x0) for res in clean.run()}

    clear_compile_cache()
    inj = FaultInjector(FaultPlan((Fault("nan", tick=0, rid=2),)))
    eng = ServeEngine(STABLE, bucket_sizes=(4,), guard_interval=1,
                      max_retries=1, fault_injector=inj)
    for r in range(4):
        eng.submit(SPEC, SHAPE, rid=r)
    out = {res.rid: res for res in eng.run()}
    assert out[2].status == "ok" and out[2].attempts == 2
    assert bool(np.isfinite(np.asarray(out[2].x0)).all())
    # the retry folds the attempt into the RNG: new, finite draw
    assert not (np.asarray(out[2].x0) == ref[2]).all()
    for r in (0, 1, 3):
        assert out[r].attempts == 1
        assert (np.asarray(out[r].x0) == ref[r]).all(), f"rid {r}"
    assert compile_cache_stats()["misses"] == 1
    assert eng.stats()["retries"] == 1


# ------------------------------------------------- containment (buckets)
def _model_raising_on(seq_len):
    def model(x, t):
        if x.shape[0] == seq_len:  # trace-time fault, one bucket only
            raise RuntimeError("backbone rejected this geometry")
        return STABLE(x, t)
    return model


@pytest.mark.parametrize("scheduler", ["solve", "step"])
def test_raising_bucket_does_not_abort_others(scheduler):
    """A model fn that raises for one bucket's geometry fails ONLY that
    bucket's requests; the other bucket completes bitwise-normally."""
    ref = solo_refs([0, 1])
    kw = {"scheduler": scheduler}
    if scheduler == "step":
        kw["lanes"] = 4
    eng = ServeEngine(_model_raising_on(9), bucket_sizes=(1, 2, 4), **kw)
    eng.submit(SPEC, SHAPE, rid=0)
    eng.submit(SPEC, (9, 2), rid=5)   # the poisoned bucket
    eng.submit(SPEC, SHAPE, rid=1)
    out = {res.rid: res for res in eng.run()}
    assert set(out) == {0, 1, 5}
    assert out[5].status == "failed"
    assert "backbone rejected" in out[5].error
    for r in (0, 1):
        assert out[r].status == "ok"
        assert (np.asarray(out[r].x0) == ref[r]).all(), f"rid {r}"
    assert eng.stats()["failed"] == 1


@pytest.mark.parametrize("scheduler", ["solve", "step"])
def test_retry_succeeds_after_transient_raise(scheduler):
    """A one-shot injected host failure: every in-flight request of the
    faulted dispatch retries (with backoff) and completes on attempt 2."""
    inj = FaultInjector(FaultPlan((Fault("raise", tick=0),)))
    kw = {"scheduler": scheduler}
    if scheduler == "step":
        kw["lanes"] = 4
    eng = ServeEngine(STABLE, bucket_sizes=(4,), max_retries=2,
                      retry_backoff=0.01, fault_injector=inj, **kw)
    for r in range(3):
        eng.submit(SPEC, SHAPE, rid=r)
    out = {res.rid: res for res in eng.run()}
    assert len(out) == 3
    fired = [f for f in inj.fired if f[0] == "raise"]
    assert len(fired) == 1
    for r in range(3):
        assert out[r].status == "ok", out[r]
        assert bool(np.isfinite(np.asarray(out[r].x0)).all())
    s = eng.stats()
    assert s["failed"] == 0
    if scheduler == "solve":
        # solve dispatches whole microbatches: all 3 retried together
        assert s["retries"] == 3
        assert all(out[r].attempts == 2 for r in range(3))
    else:
        # the step scheduler retries whatever was in flight at the tick
        assert s["retries"] >= 1
        assert any(out[r].attempts == 2 for r in range(3))


# the solve injector counts completed microbatches, the step one ticks:
# each plan below reaches the request on its first two attempts
LADDER_NANS = {"solve": (0, 1), "step": (2, 6)}


@pytest.mark.parametrize("scheduler", ["solve", "step"])
def test_degradation_ladder_tau0_after_repeated_numerics(scheduler):
    """Two NaN faults chase the same rid across retries: attempt 1
    degrades to tau=0 (rung 0 of the ladder) and attempt 3 completes
    there — all under ONE compiled executor (tau is data)."""
    clear_stepwise_cache()
    clear_compile_cache()
    inj = FaultInjector(FaultPlan(tuple(
        Fault("nan", tick=t, rid=0) for t in LADDER_NANS[scheduler])))
    eng = step_engine(scheduler=scheduler, guard_interval=1,
                      max_retries=2, degrade_ladder=("tau0",),
                      fault_injector=inj)
    eng.submit(SPEC, SHAPE, rid=0)
    (res,) = eng.run()
    assert res.status == "ok"
    assert res.attempts == 3
    assert res.degraded_to == "tau0"
    assert bool(np.isfinite(np.asarray(res.x0)).all())
    assert len([f for f in inj.fired if f[0] == "nan"]) == 2
    s = eng.stats()
    assert s["retries"] == 2 and s["failed_numerics"] == 0
    assert s["degraded"] == 1
    if scheduler == "step":
        assert s["stepwise_cache"]["misses"] == 1, s["stepwise_cache"]
    else:
        assert s["compile_cache"]["misses"] == 1, s["compile_cache"]


def test_degraded_tau0_matches_explicit_tau0_submission():
    """The ladder's tau0 rung is the same spec at tau=0/program=None —
    a degraded retry must land in that spec's bucket, and an explicit
    tau0 submission of the same rid+attempt reproduces it exactly."""
    inj = FaultInjector(FaultPlan((Fault("nan", tick=1, rid=7),)))
    eng = step_engine(guard_interval=1, max_retries=1,
                      degrade_ladder=("tau0",), fault_injector=inj)
    eng.submit(SPEC, SHAPE, rid=7)
    (res,) = eng.run()
    assert res.status == "ok" and res.degraded_to == "tau0"
    # no public API submits at attempt=1, so drive the batcher directly
    from repro.serve import Request
    ref_eng = step_engine()
    ref_eng._batcher.enqueue(dataclasses.replace(
        Request(rid=7, spec=SPEC.replace(tau=0.0, program=None),
                shape=SHAPE), attempt=1))
    (ref,) = ref_eng.run()
    assert (np.asarray(res.x0) == np.asarray(ref.x0)).all()


# --------------------------------------------------- quarantine/watchdog
# a failed microbatch does not count, so both solve raises are at 0
QUARANTINE_RAISES = {"solve": (0, 0), "step": (0, 1)}


@pytest.mark.parametrize("scheduler", ["solve", "step"])
def test_quarantine_after_consecutive_failures_then_recovery(scheduler):
    """Two consecutive injected failures quarantine the bucket; the
    pending retry is HELD (not dropped) through the cooldown and the
    post-cooldown probe completes it."""
    inj = FaultInjector(FaultPlan(tuple(
        Fault("raise", tick=t) for t in QUARANTINE_RAISES[scheduler])))
    eng = step_engine(scheduler=scheduler, max_retries=3,
                      retry_backoff=0.01, quarantine_after=2,
                      quarantine_s=0.1, fault_injector=inj)
    eng.submit(SPEC, SHAPE, rid=0)
    t0 = time.monotonic()
    (res,) = eng.run()
    assert res.status == "ok" and res.attempts == 3
    assert len([f for f in inj.fired if f[0] == "raise"]) == 2
    s = eng.stats()
    assert s["quarantines"] == 1
    assert time.monotonic() - t0 >= 0.1  # sat out the cooldown
    h = eng.health()
    assert h["status"] == "ok" and h["quarantined"] == {}


HEALTH_KEYS = {
    "status", "scheduler", "pending", "active", "running_batches",
    "quarantined", "consecutive_failures", "completed", "failed",
    "failed_numerics", "retries", "degraded_results", "shed",
    "quarantines", "callback_errors", "straggler_events"}
SHARED_STATS = {
    "requests", "completed", "model_evals", "network_evals", "warmups",
    "serve_s", "failed", "failed_numerics", "retries", "degraded",
    "quarantines", "callback_errors", "callback_error_messages",
    "straggler_events", "requests_per_s", "model_evals_per_s", "buckets",
    "compile_cache"}
STATS_KEYS = {
    "solve": SHARED_STATS | {"microbatches", "padded_slots",
                             "network_evals_per_s"},
    "step": SHARED_STATS | {"shed", "joins", "migrations", "ticks",
                            "straggler_sheds", "stepwise_cache"}}
BUCKET_KEYS = {"ticks", "lane_steps", "active_lane_steps",
               "wasted_lane_steps", "occupancy"}


def test_health_snapshot_both_schedulers():
    for scheduler in ("solve", "step"):
        eng = ServeEngine(STABLE, scheduler=scheduler)
        h = eng.health()
        assert h["status"] == "ok" and h["scheduler"] == scheduler
        for k in ("pending", "quarantined", "consecutive_failures",
                  "completed", "failed", "failed_numerics", "retries",
                  "quarantines", "callback_errors", "straggler_events"):
            assert k in h, k
        # each scheduler reports exactly its own keys, before and after
        # it has served a bucket
        assert set(h) == HEALTH_KEYS
        assert set(eng.stats()) == STATS_KEYS[scheduler]
        eng.submit(SPEC, SHAPE, rid=0)
        eng.run()
        assert set(eng.health()) == HEALTH_KEYS
        s = eng.stats()
        assert set(s) == STATS_KEYS[scheduler]
        (bucket,) = s["buckets"].values()
        assert set(bucket) == BUCKET_KEYS
    # a quarantined bucket flips status to degraded with time remaining
    eng = ServeEngine(_model_raising_on(9), quarantine_after=1,
                      quarantine_s=30.0)
    eng.submit(SPEC, (9, 2), rid=0)
    (res,) = eng.run()
    assert res.status == "failed"
    h = eng.health()
    assert h["status"] == "degraded"
    (remaining,) = h["quarantined"].values()
    assert 0 < remaining <= 30.0


def test_watchdog_sees_injected_latency():
    """An injected latency spike shows up as a straggler event (the
    monitor needs warmup ticks + patience, so give it a long solve)."""
    from repro.runtime import StragglerMonitor
    big = SPEC.replace(n_steps=30)
    warm = step_engine()  # populate the global stepwise cache so the
    warm.submit(big, SHAPE, rid=0)  # watched run has no compile-time
    warm.run()  # outlier polluting the monitor's EMA
    spike = Fault("latency", tick=20, seconds=0.25)
    inj = FaultInjector(FaultPlan((spike,)))
    # fast-adapting EMA: the watched run's tick 0 still jit-compiles the
    # per-engine rid->keys derivation, and the default alpha would let
    # that outlier inflate the variance past the injected spike
    eng = step_engine(
        fault_injector=inj,
        watchdog=StragglerMonitor(alpha=0.3, z_thresh=3.0, patience=1,
                                  warmup_steps=5))
    for r in range(4):
        eng.submit(big, SHAPE, rid=r)
    out = eng.run()
    assert len(out) == 4 and all(r.status == "ok" for r in out)
    assert any(f[0] == "latency" for f in inj.fired)
    assert eng.stats()["straggler_events"] >= 1


# ------------------------------------------------------ result callbacks
def test_on_result_callback_errors_do_not_lose_results():
    calls = []

    def cb(res):
        calls.append(res.rid)
        raise ValueError("frontend fell over")

    for scheduler in ("solve", "step"):
        eng = ServeEngine(STABLE, scheduler=scheduler, on_result=cb)
        for r in range(3):
            eng.submit(SPEC, SHAPE, rid=r)
        out = eng.run()
        assert len(out) == 3 and all(r.status == "ok" for r in out)
        s = eng.stats()
        assert s["callback_errors"] == 3
        assert any("frontend fell over" in m
                   for m in s["callback_error_messages"])
    assert sorted(calls) == [0, 0, 1, 1, 2, 2]


# -------------------------------------------------------- chaos plumbing
def test_fault_validation_and_seeded_determinism():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault("explode")
    with pytest.raises(ValueError, match="target rid or lane"):
        Fault("nan")
    p1 = FaultPlan.seeded(42, n_ticks=50, rids=range(8),
                          nan=2, raises=1, latency=1)
    p2 = FaultPlan.seeded(42, n_ticks=50, rids=range(8),
                          nan=2, raises=1, latency=1)
    assert p1 == p2
    assert len(p1.faults) == 4
    assert sorted(f.kind for f in p1.faults) == \
        ["latency", "nan", "nan", "raise"]
    p3 = FaultPlan.seeded(43, n_ticks=50, rids=range(8),
                          nan=2, raises=1, latency=1)
    assert p1 != p3


def test_poison_lane_touches_only_target():
    from repro.core.samplers import build_plan, fresh_carry
    carry = fresh_carry(build_plan(SPEC), 4, SHAPE, "float32",
                        model_fn=STABLE)
    before = [np.asarray(l) for l in jax.tree.leaves(carry["inner"])]
    poisoned = poison_lane(carry, 2)
    after = [np.asarray(l) for l in jax.tree.leaves(poisoned["inner"])]
    assert len(before) == len(after) and len(after) > 0
    for b, a in zip(before, after):
        if not np.issubdtype(a.dtype, np.floating):
            assert (a == b).all()
            continue
        assert np.isnan(a[2]).all()
        mask = np.arange(a.shape[0]) != 2
        assert (a[mask] == b[mask]).all()


def test_injected_failure_raises_through_on_tick():
    inj = FaultInjector(FaultPlan((Fault("raise", tick=0, bucket="sa/"),)))

    class _B:  # minimal RunningBatch stand-in
        key = (SPEC, SHAPE, "float32", None)
        requests = [None]
        carry = None
    with pytest.raises(InjectedFailure):
        inj.on_tick(0, _B())
    inj.on_tick(1, _B())  # spent: fires at most once


# -------------------------------------------------- checkpointer close()
def test_async_checkpointer_close_surfaces_worker_error(tmp_path):
    """A write error after the last save() must not vanish with the
    daemon thread: close() is the shutdown barrier and must raise."""
    blocker = tmp_path / "not_a_dir"
    blocker.write_text("file where the checkpoint dir should go")
    ck = AsyncCheckpointer(str(blocker / "ckpt"))
    ck.save(0, {"w": jnp.ones((2, 2))})
    with pytest.raises(RuntimeError, match="async checkpoint failed"):
        ck.close()


def test_async_checkpointer_clean_close(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path / "ckpt"))
    ck.save(0, {"w": jnp.ones((2, 2))})
    ck.close()  # no error to surface
    assert not ck._thread.is_alive()


# ------------------------------------------------ feature-cached tiers
def test_feature_cached_draft_tier_bitwise_equals_explicit_spec():
    """ROADMAP (tiers span eval cost): default_tiers(feature_cache=...)
    turns draft into the cached-eval preset, and a quality_tier="draft"
    request is bitwise the explicit resolved-spec submission — tier
    resolution happens at submit time, before bucketing and RNG."""
    from test_e2e_dit import tame_denoiser
    den, _, _, _ = tame_denoiser()
    tiers = default_tiers(schedule=SCHED, feature_cache=2)
    assert tiers.resolve("draft").feature_cache == 2
    assert tiers.resolve("standard").feature_cache is None

    e_tier = ServeEngine(den, tiers=tiers)
    e_tier.submit(None, shape=(2, 16, 8), quality_tier="draft")
    (r_tier,) = e_tier.run()
    e_spec = ServeEngine(den)
    e_spec.submit(tiers.resolve("draft"), shape=(2, 16, 8))
    (r_spec,) = e_spec.run()
    assert r_tier.rid == r_spec.rid
    assert bool(jnp.all(r_tier.x0 == r_spec.x0))
    assert bool(jnp.all(jnp.isfinite(r_tier.x0)))
