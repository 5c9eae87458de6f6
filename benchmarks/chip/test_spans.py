"""Tests of ``spans.py`` and the per-layer metrics that read the program's
own spans and scopes, on the CPU.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_spans.py

The traces are 1 s windows of the tiny cells of ``testdata/BENCHMARK.json``
(the step cell and the backlog cell) recorded on a TPU v5e with the
``serve.*`` spans and the ``attention`` scope. The expected numbers were
worked out from each trace by a separate walk over ``ProfileData``; PR
12's trace, which has neither, reads as nothing.
"""

from __future__ import annotations

import gzip
import json
import os
import statistics
import types

import pytest

from benchmarks.chip import counts, modules, run, spans, trace

TESTDATA = os.path.join(run.HERE, "testdata")
NEW = ("loop_host_ms.tick", "loop_host_ms.microbatch", "queue_wait_p95_s",
       "attention_roofline")


def _unpack(tmp_path, stem):
    for src, dst in ((f"{stem}.xplane.pb.gz", "t.xplane.pb"),
                     (f"{stem}.scopes.json.gz", "scopes.json")):
        with gzip.open(os.path.join(TESTDATA, src)) as f:
            (tmp_path / dst).write_bytes(f.read())
    return str(tmp_path)


@pytest.fixture
def recorded(tmp_path, monkeypatch):
    """Unpack a recorded trace where the readers look for a run's."""
    def use(stem):
        d = _unpack(tmp_path, stem)
        monkeypatch.setattr(spans, "TRACE_DIR", d)
        return d
    return use


def _run(forwards):
    with open(os.path.join(TESTDATA, "dit-tiny.json")) as f:
        model = json.load(f)["model"]
    return types.SimpleNamespace(
        trace={}, forwards=forwards, peaks=counts.peaks("TPU v5 lite"),
        cell=types.SimpleNamespace(
            config={"model": model},
            family=modules.load(run.HERE, "families", "dit")))


def _read(name, r):
    return run.reader(run.HERE, name)(r)


# the S^2 FLOPs of one tiny forward: 2 layers, 16 tokens, 4 heads of 16
TINY_S2 = 2 * 2 * 2 * 16 * 16 * 4 * 16


def test_readers_on_the_recorded_step_cell(recorded):
    recorded("spans.step")
    r = _run(forwards=1520)
    assert _read("loop_host_ms.tick", r) == pytest.approx(1.09179, rel=1e-9)
    # six joins: two at 59 us, four at 10.2-11.1 ms
    assert _read("queue_wait_p95_s", r) == \
        pytest.approx(0.0109930424999991, rel=1e-9)
    assert _read("loop_host_ms.microbatch", r) is None
    assert spans.scope_seconds("attention") == \
        pytest.approx(0.000183312, rel=1e-9)
    assert _read("attention_roofline", r) == pytest.approx(
        100 * 1520 * TINY_S2 / (0.000183312 * 197e12), rel=1e-9)


def test_readers_on_the_recorded_backlog_cell(recorded):
    recorded("spans.backlog")
    r = _run(forwards=12480)
    assert _read("loop_host_ms.microbatch", r) == \
        pytest.approx(21.832341, rel=1e-9)
    assert _read("loop_host_ms.tick", r) is None
    assert _read("queue_wait_p95_s", r) is None
    assert _read("attention_roofline", r) == pytest.approx(
        100 * 12480 * TINY_S2 / (0.001505073 * 197e12), rel=1e-9)
    assert 0 < _read("attention_roofline", r) < 100


def test_a_trace_without_spans_or_scope_reads_nothing(tmp_path,
                                                      monkeypatch):
    """PR 12's recorded trace: a program that emits no ``serve.*`` span
    and has no ``attention`` scope, as the parent commit's, gives every
    new metric None and raises nothing."""
    monkeypatch.setattr(spans, "TRACE_DIR", _unpack(tmp_path, "c1tiny"))
    r = _run(forwards=1000)
    assert spans.program_spans()[1] == []
    assert spans.scope_seconds("attention") == 0.0
    assert {n: _read(n, r) for n in NEW} == dict.fromkeys(NEW)
    assert all(_read(n, types.SimpleNamespace(trace=None, forwards=1))
               is None for n in NEW)


@pytest.mark.parametrize("stem, top, children", [
    ("spans.step", "serve.tick",
     {"serve.admit", "serve.dispatch", "serve.sync", "serve.harvest",
      "serve.merge"}),
    ("spans.backlog", "serve.microbatch",
     {"serve.prepare", "serve.dispatch", "serve.sync", "serve.harvest"}),
])
def test_spans_nest_and_cover_the_harness_step(tmp_path, stem, top,
                                               children):
    """Every ``step()`` of the harness is one top span whose children
    account for its time (median residual under 0.5 ms), and the top
    spans cover over 95% of the harness's ``step`` time."""
    d = _unpack(tmp_path, stem)
    (w0, w1), got = spans.program_spans(d)
    tops = [s for s in got if s.name == top]
    assert tops and all(s.parent is None for s in tops)
    assert {s.name for s in got if s.parent is not None and
            s.parent.name == top} == children
    if top == "serve.tick":
        assert {s.parent.name for s in got
                if s.name in ("serve.join", "serve.new_batch")} == \
            {"serve.admit"}
    resid = [s.seconds - sum(c.seconds for c in got if c.parent is s)
             for s in tops]
    assert 0 <= statistics.median(resid) < 5e-4
    _, host = trace.read(trace.find_xplane(d))
    steps = trace.union((s, e) for n, s, e in host
                        if n == "step" and w0 <= s and e <= w1)
    covered = trace.intersect(steps, trace.union(
        (s.start, s.end) for s in tops))
    assert trace.length(covered) > 0.95 * trace.length(steps)


def test_report_gives_each_gap_to_the_innermost_span(tmp_path):
    rep = spans.report(_unpack(tmp_path, "spans.step"))
    gaps = rep["idle_gaps"]
    # the longest gaps wait for arrivals, outside any tick; the rest sit
    # inside a tick, over the host's part of it
    assert gaps[0]["span"] is None and gaps[0]["harness"] == "wait"
    assert gaps[0]["seconds"] == pytest.approx(0.330157, abs=1e-6)
    inside = [g for g in gaps if g["span"]]
    assert inside and all(g["span"].startswith("serve.tick")
                          and g["harness"] == "step" for g in inside)
    assert rep["spans"]["serve.tick"]["n"] == 95
    assert rep["spans"]["serve.join"]["n"] == 6
    assert rep["longest"][0]["span"] == "serve.tick"
    # the idle time, split among the spans over it: the harness's loop
    # between its own spans is all that is left out
    by_span = rep["idle_by_span"]
    idle = rep["window_s"] - rep["busy_s"]
    assert 0.99 * idle < sum(by_span.values()) <= idle
    assert max(by_span, key=by_span.get) == "(harness) wait"
    assert by_span["serve.join"] == pytest.approx(0.015204819, rel=1e-6)


def test_interval_difference_and_overlap():
    a = [(0, 10), (20, 30)]
    assert spans._minus(a, [(2, 4), (8, 22), (25, 26)]) == \
        [(0, 2), (4, 8), (22, 25), (26, 30)]
    assert spans._minus(a, []) == a
    assert spans._overlap(a, [0, 20], 5, 25) == 10


def test_innermost_takes_the_deepest_span_over_half_the_gap():
    tick = spans.Span("serve.tick", 0, 100, None, {})
    sync = spans.Span("serve.sync", 10, 60, tick, {})
    assert spans.innermost([tick, sync], 20, 60) is sync
    assert spans.innermost([tick, sync], 40, 100) is tick
    assert spans.innermost([tick, sync], 90, 200) is None


@pytest.mark.parametrize("op_name, inside", [
    ("jit(run)/vmap(backbone)/vmap()/while/body/closed_call/attention/div",
     True),
    ("jit(run)/backbone/attention/while/body/closed_call/checkpoint/"
     "bkgst,btkd->bskgd/dot_general @attention.py:139 _sdpa_block", True),
    ("jit(run)/backbone/transpose;attention/transpose", True),
    ("jit(run)/vmap(attention)/exp", True),
    ("jit(run)/backbone/bsd,dhk->bshk/dot_general @attention.py:195 "
     "gqa_forward", False),
    ("jit(run)/backbone/attention_free/mul", False),
])
def test_scope_is_a_segment_of_the_op_name(op_name, inside):
    assert spans._in_scope("attention", op_name) is inside


def test_the_command_line(tmp_path, capsys):
    assert spans.main([_unpack(tmp_path, "spans.backlog")]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("window 1.004")
    assert any(line.split()[0] == "serve.microbatch" and "n     39" in line
               for line in out[1:] if line.strip())
    assert "serve.warm" not in json.loads(out[-1])["spans"]
