"""Reduction of a profiler trace (``*.xplane.pb``) to device numbers.

Reads the trace with ``jax.profiler.ProfileData`` alone. Device time is
the union of the intervals of the operations on each TPU's ``XLA Ops``
line. The trace names an operation by its HLO instruction only; its op
name, and with it the program's own ``backbone`` name scope, comes from
the metadata of the compiled executables the harness hands in. An
operation counts as a collective when its name is one of XLA's. Idle
gaps between operations are attributed to the harness's host span
(``intake``, ``step``, ``results``, ``wait``) that covers most of the
gap.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPANS = ("intake", "step", "results", "wait")
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|send|recv", re.I)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two merged, sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


_INSTR = re.compile(
    r'^\s*(?:ROOT\s+)?%([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"'
    r'(?:[^}]*?stack_frame_id=(\d+))?', re.M)
_TABLE_ROW = re.compile(r'^(\d+) (?:"(.*)"|\{(.*)\})$')
_FIELD = re.compile(r"(\w+)=(\d+)")


def _frames(text: str) -> dict[str, str]:
    """Stack frame id -> ``file:line function`` of its innermost source
    location, from the tables at the head of an HLO module's text."""
    tables: dict[str, dict[str, object]] = {}
    section = None
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            section = tables.setdefault(line, {})
        elif section is not None and (m := _TABLE_ROW.match(line)):
            section[m[1]] = m[2] if m[2] is not None else \
                dict(_FIELD.findall(m[3]))
        elif line.startswith(("HloModule", "ENTRY", "%")):
            section = None
            if line.startswith(("ENTRY", "%")):
                break
    files, fns = tables.get("FileNames", {}), tables.get("FunctionNames", {})
    locs = tables.get("FileLocations", {})
    out = {}
    for fid, frame in tables.get("StackFrames", {}).items():
        loc = locs.get(frame.get("file_location_id")) \
            if isinstance(frame, dict) else None
        if isinstance(loc, dict):
            path = str(files.get(loc.get("file_name_id"), "?"))
            out[fid] = (f"{os.path.basename(path)}:{loc.get('line')} "
                        f"{fns.get(loc.get('function_name_id'), '?')}")
    return out


def scopes(hlo_texts) -> list[dict[str, str]]:
    """Per compiled executable (its ``as_text()``): instruction name ->
    the op name its metadata carries, where the program's name scopes
    (``backbone``) appear, followed by the source line that made it
    (``.../dot_general @attention.py:112 _attend``) where the module
    records one."""
    out = []
    for t in hlo_texts:
        frames = _frames(t)
        out.append({inst: f"{op} @{frames[fid]}" if fid in frames else op
                    for inst, op, fid in _INSTR.findall(t)})
    return out


#: the program's ``backbone`` name scope, also as a transform wraps it
#: (``vmap(backbone)`` where the scope is entered under ``vmap``)
_BACKBONE = re.compile(r"(?:^|/)(?:\w+\()*backbone\)*(?:/|$)")
#: op-name segments that name a transform or control flow, not an op
_WRAPPER = re.compile(r"^(?:\w+\(.*\)|while|body|cond|closed_call|"
                      r"checkpoint|backbone)$")


def _op_tail(op_name: str) -> str:
    """The last two segments of an op name that are not transforms or
    control flow: ``bsd,dhk->bshk/dot_general @transformer.py:271 ...``."""
    segs = [s for s in op_name.split("/") if not _WRAPPER.match(s)]
    return "/".join(segs[-2:])


def _instruction(name: str) -> str:
    """The instruction name of an op whose trace name is its HLO text."""
    return name.split(" = ")[0].lstrip("%") if " = " in name else name


def _module_label(name: str) -> str:
    """``jit_run(123456789)`` -> ``jit_run#6789``."""
    base, _, rest = name.partition("(")
    return f"{base}#{rest.rstrip(')')[-4:]}" if rest else base


def read(path: str, op_scopes=()):
    """(device ops per chip, host spans). Ops are the leaf ops of each
    chip's ``XLA Ops`` line (control flow such as a ``while`` spans the
    ops it runs and is left out), as (label, start_ns, end_ns,
    in_backbone) lists keyed by plane name. An op's executable is the
    ``XLA Modules`` event around it; its op name comes from the entry of
    ``op_scopes`` that knows most of that executable's instructions.
    Spans are (name, start_ns, end_ns) of the harness's host spans and
    its ``window`` anchor."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name)
                             for ev in lines[MODULES_LINE].events) \
                if MODULES_LINE in lines else []
            evs = sorted(((ev.start_ns, -ev.duration_ns, ev.name)
                          for ev in lines[OPS_LINE].events)) \
                if OPS_LINE in lines else []
            by_module: dict[str, list] = defaultdict(list)
            m = 0
            for i, (start, neg, name) in enumerate(evs):
                end = start - neg
                if i + 1 < len(evs) and evs[i + 1][0] < end:
                    continue  # a while or call around the ops it runs
                while m < len(modules) and modules[m][1] <= start:
                    m += 1
                mod = modules[m][2] if m < len(modules) \
                    and modules[m][0] <= start else ""
                by_module[mod].append((_instruction(name), start, end))
            leaves = ops.setdefault(plane.name, [])
            for mod, mod_ops in by_module.items():
                names = {n for n, _, _ in mod_ops}
                known = max(op_scopes, key=lambda d: len(names & d.keys()),
                            default={})
                for inst, start, end in mod_ops:
                    op_name = known.get(inst, "")
                    in_bb = bool(_BACKBONE.search(op_name))
                    label = f"{_module_label(mod)}/{inst}"
                    if op_name:
                        label += (" (backbone " if in_bb else " (") + \
                            _op_tail(op_name) + ")"
                    leaves.append((label, start, end, in_bb))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS or ev.name == "window":
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return ops, spans


def reduce(path: str, demand=None, op_scopes=(), top: int = 10) -> dict:
    """Device numbers over the harness's ``window`` span; ``op_scopes``
    as ``read`` takes them.

    ``demand``: host-clock intervals (seconds, relative to the window's
    opening) in which work was pending or running; busy and idle are then
    also given over them. Returns seconds, averaged over the chips."""
    ops, spans = read(path, op_scopes)
    if not ops:
        raise ValueError(f"no {OPS_LINE!r} line on a {DEVICE_PREFIX}* plane "
                         f"in {path}")
    windows = [s for s in spans if s[0] == "window"]
    if not windows:
        raise ValueError("no 'window' host span in the trace")
    _, w0, w1 = windows[0]
    host = [s for s in spans if s[0] != "window"]
    chips = len(ops)
    busy = bb = coll = total = 0.0
    demand_busy = 0.0
    per_op: dict[str, float] = defaultdict(float)
    gaps: list[tuple[float, float, float]] = []
    demand_ns = None
    if demand is not None:
        demand_ns = union((w0 + s * 1e9, w0 + e * 1e9) for s, e in demand)
        demand_ns = clip(demand_ns, w0, w1)
    for plane, evs in ops.items():
        iv = union(clip([(s, e) for _, s, e, _ in evs], w0, w1))
        busy += length(iv)
        if demand_ns is not None:
            demand_busy += length(intersect(iv, demand_ns))
        for name, s, e, in_bb in evs:
            d = max(0.0, min(e, w1) - max(s, w0))
            total += d
            per_op[name] += d
            if in_bb:
                bb += d
            elif COLLECTIVE.search(name):
                coll += d
        prev = w0
        for s, e in iv + [(w1, w1)]:
            if s > prev:
                gaps.append((s - prev, prev, s))
            prev = max(prev, e)
    ns = 1e-9 / chips
    out = {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy * ns,
        "op_s": total * ns,
        "backbone_s": bb * ns,
        "collective_s": coll * ns,
        "device_ops": [[n, t * ns] for n, t in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_cover(host, lo, hi), g * 1e-9] for g, lo, hi in
                      sorted(gaps, key=lambda g: -g[0])[:top]],
    }
    if demand_ns is not None:
        out["demand_s"] = length(demand_ns) * 1e-9
        out["demand_busy_s"] = demand_busy * ns
    return out


def _cover(host, lo: float, hi: float) -> str:
    """The host span that overlaps [lo, hi) the most."""
    best, name = 0.0, "none"
    for n, s, e in host:
        o = min(e, hi) - max(s, lo)
        if o > best:
            best, name = o, n
    return name
