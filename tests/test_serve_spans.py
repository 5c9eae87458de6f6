"""Profiler spans of the serving loop and the ``attention`` name scope.

Covers: the ``serve.*`` host spans of both schedulers nest as the
modules document (recorded through a stand-in for ``TraceAnnotation``);
``serve.join`` carries the rid and the seconds the request queued, a
retry keeps its first enqueue time, and with no profiler session no span
builds arguments; the spans land on a real profiler's host plane; and
the compiled DiT solve puts ``named_scope("attention")`` on the S^2
score and value products and the softmax, and leaves the q/k/v/o
projections outside it.
"""

import dataclasses
import glob
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core import Denoiser, get_schedule
from repro.core.samplers import SamplerSpec, build_plan, warmup
from repro.serve import (Fault, FaultInjector, FaultPlan, FaultPolicy,
                         Request, ServeEngine, continuous, default_tiers,
                         engine)

SCHED = get_schedule("vp_linear")
SPEC = SamplerSpec(name="sa", schedule=SCHED, n_steps=6, tau=0.7)
SHAPE = (16, 2)


def STABLE(x, t):
    return 0.3 * x * jnp.cos(t)


STEP_PARENTS = {
    "serve.tick": None, "serve.admit": "serve.tick",
    "serve.new_batch": "serve.admit", "serve.join": "serve.admit",
    "serve.dispatch": "serve.tick", "serve.sync": "serve.tick",
    "serve.harvest": "serve.tick", "serve.merge": "serve.tick",
}
SOLVE_PARENTS = {
    "serve.microbatch": None, "serve.warm": "serve.microbatch",
    "serve.prepare": "serve.microbatch",
    "serve.dispatch": "serve.microbatch", "serve.sync": "serve.microbatch",
    "serve.harvest": "serve.microbatch",
}


@pytest.fixture
def spans(monkeypatch):
    """A stand-in for ``TraceAnnotation`` in both schedulers: each span
    entered is logged as (name, parent name, arguments, monotonic time
    at entry). ``Span.enabled`` plays the profiler session."""

    class Span:
        enabled = True
        log: list = []
        stack: list = []

        def __init__(self, name, **args):
            self.name, self.args = name, args

        @classmethod
        def is_enabled(cls):
            return cls.enabled

        def __enter__(self):
            parent = self.stack[-1] if self.stack else None
            self.log.append((self.name, parent, self.args,
                             time.monotonic()))
            self.stack.append(self.name)

        def __exit__(self, *exc):
            self.stack.pop()

    monkeypatch.setattr(continuous, "span", Span)
    monkeypatch.setattr(engine, "span", Span)
    return Span


def test_step_scheduler_spans_nest(spans):
    eng = ServeEngine(STABLE, scheduler="step", lanes=2)
    for r in range(3):  # the third opens a second batch
        eng.submit(SPEC, SHAPE, rid=r)
    assert len(eng.run()) == 3
    assert {(n, p) for n, p, _, _ in spans.log} == set(STEP_PARENTS.items())
    names = [n for n, _, _, _ in spans.log]
    assert names.count("serve.tick") == eng.stats()["ticks"]
    assert names.count("serve.new_batch") == 2
    joins = [a for n, _, a, _ in spans.log if n == "serve.join"]
    assert [a["rid"] for a in joins] == [0, 1, 2]
    assert all(a["queued_s"] >= 0 for a in joins)


def test_solve_scheduler_spans_nest(spans):
    eng = ServeEngine(STABLE, bucket_sizes=(2,))
    for r in range(3):
        eng.submit(SPEC, SHAPE, rid=r)
    assert len(eng.run()) == 3
    assert {(n, p) for n, p, _, _ in spans.log} == \
        set(SOLVE_PARENTS.items())
    names = [n for n, _, _, _ in spans.log]
    # the second microbatch reuses the first one's executable
    assert names.count("serve.microbatch") == 2
    assert names.count("serve.warm") == 1
    assert names.count("serve.sync") == 2


def test_no_span_arguments_without_a_profiler_session(spans):
    spans.enabled = False
    eng = ServeEngine(STABLE, scheduler="step", lanes=2)
    for r in range(3):
        eng.submit(SPEC, SHAPE, rid=r)
    eng.run()
    joins = [a for n, _, a, _ in spans.log if n == "serve.join"]
    assert len(joins) == 3 and all(a == {} for a in joins)


def test_a_retry_keeps_its_first_enqueue_time(spans):
    """rid 7 goes non-finite at tick 1 and joins again: its second join
    reports the queue time since the first enqueue, not since the
    retry's."""
    inj = FaultInjector(FaultPlan((Fault("nan", tick=1, rid=7),)))
    eng = ServeEngine(STABLE, scheduler="step", lanes=4, guard_interval=1,
                      max_retries=1, fault_injector=inj)
    eng.submit(SPEC, SHAPE, rid=7)
    (res,) = eng.run()
    assert res.status == "ok" and res.attempts == 2
    (q1, t1), (q2, t2) = [(a["queued_s"], t) for n, _, a, t in spans.log
                          if n == "serve.join"]
    assert q2 - q1 == pytest.approx(t2 - t1, abs=5e-3)
    assert q2 > q1


def test_enqueue_stamps_once():
    b = continuous.ContinuousBatcher(
        STABLE, lanes=2,
        policy=FaultPolicy(tiers=default_tiers(), max_retries=1))
    b.enqueue(Request(rid=0, spec=SPEC, shape=SHAPE))
    b.enqueue(Request(rid=1, spec=SPEC, shape=SHAPE, enqueued=12.5))
    first, given = [req for _, req in b._pending]
    assert first.enqueued is not None and given.enqueued == 12.5
    assert b.policy.fail("sa", (first,), ArithmeticError("nan"),
                         numerics=True, requeue=b._requeue) == []
    retry = b._pending[-1][1]
    assert retry.attempt == 1 and retry.enqueued == first.enqueued


def _host_events(trace_dir):
    from jax.profiler import ProfileData
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(ev.name, dict(ev.stats)) for ev in line.events
                        if ev.name.startswith("serve.")]
    return out


def test_spans_land_on_the_profilers_host_plane(tmp_path):
    eng = ServeEngine(STABLE, scheduler="step", lanes=2)
    for r in range(3):
        eng.submit(SPEC, SHAPE, rid=r)
    with jax.profiler.trace(str(tmp_path)):
        eng.run()
    events = _host_events(str(tmp_path))
    assert {n for n, _ in events} == set(STEP_PARENTS)
    joins = [a for n, a in events if n == "serve.join"]
    assert sorted(a["rid"] for a in joins) == [0, 1, 2]
    assert all(a["queued_s"] >= 0 for a in joins)


# ----------------------------------------------------- the device scope
_OP = re.compile(r'op_name="([^"]*)"')
_PROJ = ("bsd,dhk->bshk", "bshk,hkd->bsd")
_S2 = ("bskgd,btkd->bkgst", "bkgst,btkd->bskgd")


def _in_attention(op_name):
    return "attention" in re.split(r"[/();]", op_name)


@pytest.mark.parametrize("tokens", [16, 512])  # direct and q-chunked
def test_attention_scope_holds_the_s2_ops_only(tokens):
    from repro.configs import dit_xl_2
    from repro.launch.sample import as_prediction_network
    from repro.models import build_model, init_params
    cfg = dataclasses.replace(dit_xl_2.smoke(), n_layers=1)
    model = build_model(cfg)
    params = init_params(jax.random.PRNGKey(0), model.param_defs())
    den = Denoiser(as_prediction_network(model, SCHED, "eps"), SCHED,
                   prediction="eps", guidance=True, params=params)
    spec = SamplerSpec.from_nfe("sa", 4, schedule=SCHED, prediction="eps",
                                guidance=True)
    dz = cfg.denoiser_latent
    text = warmup(build_plan(spec), den, (tokens, dz), batch=2,
                  cond=jax.ShapeDtypeStruct((dz,), jnp.float32)).as_text()
    # full op names (a reducer's own computation carries a short one)
    ops = [op for op in _OP.findall(text) if op.startswith("jit(")]
    att = [op for op in ops if _in_attention(op)]
    assert att and all("backbone" in op for op in att)
    # the S^2 products and the softmax inside, nothing else that
    # multiplies: the q/k/v/o projections and the MLP stay outside
    assert all(_in_attention(op) for op in ops
               if any(e in op for e in _S2) or op.endswith("reduce_max"))
    assert any(op.endswith("exp") for op in att)
    assert not any(p in op for op in att for p in _PROJ)
    assert all(any(e in op for e in _S2) for op in att
               if op.endswith("dot_general"))
    assert any(p in op for op in ops for p in _PROJ)
