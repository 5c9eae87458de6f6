"""The program's own spans and name scopes in a traced benchmark run.

    python3 -m benchmarks.chip.spans [<trace dir>]

The server marks its serving loop with ``serve.*`` host spans
(``serve/continuous.py``, ``serve/engine.py``) and its S^2 attention
with the ``attention`` name scope (``models/attention.py``). A
``--trace 1`` run of ``run.py`` leaves the profiler trace and the op
names of the executables it warmed (``scopes.json``) in ``.bench_trace``;
this module reads them with ``trace.py``'s functions, for the per-layer
metrics and by hand. Host spans and device ops share the profiler's
clock, so each device-idle gap can be given to the program phase that
was running over it. A program without these spans or scopes reads as
none: no span, zero seconds, no metric.

From the command line it prints, for the window of the harness: each
span name's count and times, the device's idle time by the innermost
span over it, the longest idle gaps with the innermost ``serve.*`` span
over each, and the longest top-level spans with their children and the
device's busy share under them.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import json
import os
import re
import statistics
import sys

from benchmarks.chip import trace
from benchmarks.chip.run import TRACE_DIR
from benchmarks.chip.stats import quantile

PREFIX = "serve."
#: the span whose time is the host blocked on the device
SYNC = "serve.sync"


@dataclasses.dataclass
class Span:
    name: str
    start: int                  # ns, the profiler's clock
    end: int
    parent: "Span | None"
    args: dict

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9

    @property
    def depth(self) -> int:
        return 0 if self.parent is None else self.parent.depth + 1

    def path(self) -> str:
        up = self.parent.path() + " > " if self.parent else ""
        return up + self.name


@functools.lru_cache(maxsize=2)
def _read(path: str, mtime_ns: int):
    """(window as (start, end) ns, the ``serve.*`` spans inside it)."""
    from jax.profiler import ProfileData
    window, spans = None, []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = []
            for ev in line.events:
                if ev.name == "window" and window is None:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                elif ev.name.startswith(PREFIX):
                    evs.append((ev.start_ns, -ev.duration_ns, ev.name,
                                dict(ev.stats)))
            stack: list[Span] = []
            for start, neg, name, args in sorted(evs, key=lambda e: e[:2]):
                while stack and stack[-1].end <= start:
                    stack.pop()
                sp = Span(name, start, start - neg,
                          stack[-1] if stack else None, args)
                stack.append(sp)
                spans.append(sp)
    if window is None:
        raise ValueError(f"no 'window' host span in {path}")
    w0, w1 = window
    return window, [s for s in spans if w0 <= s.start and s.end <= w1]


def program_spans(trace_dir: str | None = None):
    """(window, spans): the harness's ``window`` as (start, end) in ns
    and the ``serve.*`` spans that lie inside it, each with its parent
    (the ``serve.*`` span around it on the same thread) and arguments,
    in order of start."""
    path = trace.find_xplane(trace_dir or TRACE_DIR)
    return _read(path, os.stat(path).st_mtime_ns)


def host_ms(name: str, trace_dir: str | None = None) -> float | None:
    """Median over the window's spans called ``name`` of the host's part
    of each, in ms: its duration less that of its ``serve.sync``
    children. None when the program emitted no such span."""
    _, spans = program_spans(trace_dir)
    sync: dict[int, float] = {}
    for s in spans:
        if s.name == SYNC and s.parent is not None:
            sync[id(s.parent)] = sync.get(id(s.parent), 0.0) + s.seconds
    host = [s.seconds - sync.get(id(s), 0.0) for s in spans
            if s.name == name]
    return 1e3 * statistics.median(host) if host else None


def arg_quantile(name: str, arg: str, q: float,
                 trace_dir: str | None = None) -> float | None:
    """The q-quantile of argument ``arg`` over the window's spans called
    ``name`` (``stats.quantile``); None when there are none."""
    _, spans = program_spans(trace_dir)
    vals = [s.args[arg] for s in spans if s.name == name and arg in s.args]
    return quantile(vals, q) if vals else None


def _in_scope(scope: str, op_name: str) -> bool:
    """Whether ``scope`` is one segment of an op name, also as a
    transform wraps it (``vmap(attention)``) or XLA joins two names
    (``transpose;attention``); the source line after `` @`` is not
    read."""
    return scope in re.split(r"[/;()]", op_name.split(" @")[0])


def _scopes(trace_dir: str) -> list[dict[str, str]]:
    with open(os.path.join(trace_dir, "scopes.json")) as f:
        return json.load(f)


def scope_seconds(scope: str, trace_dir: str | None = None) -> float:
    """Device seconds, over the window and averaged over the chips, of
    the ops whose op name carries the name scope ``scope``.
    ``trace.reduce`` counts as ``backbone_s`` the ops whose op name
    matches its backbone pattern; it is handed op names in which the
    ops of ``scope`` read ``backbone`` and every other op reads none."""
    trace_dir = trace_dir or TRACE_DIR
    marked = [{inst: "backbone" if _in_scope(scope, op) else ""
               for inst, op in d.items()} for d in _scopes(trace_dir)]
    return trace.reduce(trace.find_xplane(trace_dir),
                        op_scopes=marked)["backbone_s"]


# --------------------------------------------------------- by hand
def innermost(spans, lo: int, hi: int) -> Span | None:
    """The deepest span that covers more than half of [lo, hi)."""
    best = None
    for s in spans:
        if min(s.end, hi) - max(s.start, lo) > (hi - lo) / 2 and \
                (best is None or s.depth > best.depth):
            best = s
    return best


def _overlap(iv, starts, lo: int, hi: int) -> int:
    """How much of [lo, hi) the merged, sorted intervals ``iv`` cover
    (``starts``: their starts)."""
    i, n = max(bisect.bisect_right(starts, lo) - 1, 0), 0
    while i < len(iv) and iv[i][0] < hi:
        n += max(0, min(iv[i][1], hi) - max(iv[i][0], lo))
        i += 1
    return n


def _minus(a, b):
    """Merged, sorted intervals ``a`` less merged, sorted ``b``."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def idle_by_span(spans, idle, harness) -> dict[str, float]:
    """Device-idle seconds given to the innermost span at each instant:
    a span's own time (less its children's) that the device idled. Idle
    time outside every ``serve.*`` span goes to the harness span over
    it, as ``(harness) <name>``."""
    starts = [s for s, _ in idle]
    kids: dict[int, list] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(id(s.parent), []).append(s)
    out: dict[str, float] = {}
    for s in spans:
        own = _overlap(idle, starts, s.start, s.end) - sum(
            _overlap(idle, starts, c.start, c.end)
            for c in kids.get(id(s), ()))
        out[s.name] = out.get(s.name, 0.0) + own * 1e-9
    rest = _minus(idle, trace.union((s.start, s.end) for s in spans
                                    if s.parent is None))
    rest_starts = [s for s, _ in rest]
    for name, s, e in harness:
        key = "(harness) " + name
        out[key] = out.get(key, 0.0) + \
            _overlap(rest, rest_starts, s, e) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def report(trace_dir: str, top: int = 10) -> dict:
    """What the command line prints, as a dict."""
    path = trace.find_xplane(trace_dir)
    ops, host = trace.read(path, _scopes(trace_dir))
    (w0, w1), spans = program_spans(trace_dir)
    busy = trace.union(trace.clip([(s, e) for evs in ops.values()
                                   for _, s, e, _ in evs], w0, w1))
    gaps, prev = [], w0
    for s, e in busy + [(w1, w1)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    harness = [h for h in host if h[0] != "window"]
    idle = list(gaps)
    gaps.sort(key=lambda g: g[0] - g[1])
    leaves = [(s, e, label) for evs in ops.values()
              for label, s, e, _ in evs]
    by_end = sorted((e, label) for _, e, label in leaves)
    by_start = sorted((s, label) for s, _, label in leaves)
    ends = [e for e, _ in by_end]
    starts = [s for s, _ in by_start]

    def last_before(t):
        i = bisect.bisect_right(ends, t) - 1
        return by_end[i][1] if i >= 0 else None

    def first_after(t):
        j = bisect.bisect_left(starts, t)
        return by_start[j][1] if j < len(starts) else None

    names: dict[str, list[float]] = {}
    for s in spans:
        names.setdefault(s.name, []).append(s.seconds)
    out = {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": trace.length(busy) * 1e-9,
        "spans": {n: {"n": len(v), "total_s": sum(v),
                      "median_ms": 1e3 * statistics.median(v),
                      "max_ms": 1e3 * max(v)} for n, v in names.items()},
        "idle_by_span": idle_by_span(spans, idle, harness),
        "idle_gaps": [],
        "longest": [],
    }
    for lo, hi in gaps[:top]:
        sp = innermost(spans, lo, hi)
        out["idle_gaps"].append({
            "seconds": (hi - lo) * 1e-9, "at_s": (lo - w0) * 1e-9,
            "span": sp.path() if sp else None,
            "harness": trace._cover(harness, lo, hi),
            "op_before": last_before(lo), "op_after": first_after(hi)})
    roots = sorted((s for s in spans if s.parent is None),
                   key=lambda s: s.start - s.end)[:top]
    for r in roots:
        kids: dict[str, float] = {}
        for s in spans:
            if s.parent is r:
                kids[s.name] = kids.get(s.name, 0.0) + s.seconds
        dev = trace.length(trace.clip(busy, r.start, r.end)) * 1e-9
        out["longest"].append({
            "span": r.name, "seconds": r.seconds,
            "at_s": (r.start - w0) * 1e-9, "children_s": kids,
            "device_busy_share": dev / r.seconds if r.seconds else None})
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    rep = report(argv[0] if argv else TRACE_DIR)
    print(f"window {rep['window_s']:.3f} s, device busy "
          f"{rep['busy_s']:.3f} s")
    for n, v in sorted(rep["spans"].items()):
        print(f"  {n:18s} n {v['n']:6d}  total {v['total_s']:9.4f} s  "
              f"median {v['median_ms']:9.4f} ms  max {v['max_ms']:9.3f} ms")
    print("device idle, by the innermost span over it:")
    for n, v in rep["idle_by_span"].items():
        print(f"  {n:26s} {v:10.6f} s")
    print("longest device-idle gaps:")
    for g in rep["idle_gaps"]:
        print(f"  {g['seconds']:.6f} s at {g['at_s']:.4f} s: "
              f"{g['span'] or 'no serve.* span'} (harness {g['harness']}); "
              f"after {g['op_before']}, before {g['op_after']}")
    print("longest top-level spans:")
    for r in rep["longest"]:
        kids = ", ".join(f"{k} {1e3 * v:.3f} ms"
                         for k, v in r["children_s"].items())
        print(f"  {r['span']} {r['seconds']:.6f} s at {r['at_s']:.4f} s: "
              f"{kids}; device busy {r['device_busy_share']!r}")
    print(json.dumps(rep))
    return 0


if __name__ == "__main__":
    sys.exit(main())
