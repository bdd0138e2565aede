#!/usr/bin/env python3
"""The program's own spans in a traced run: DRAGON's ``dragon.*`` host
events, on the profiler's clock, for the per-layer metrics that read them.

The newest ``.xplane.pb`` under the trace directory (``run.py``'s
``.trace``) is parsed once per process.  Each span keeps its name, start and
end (ns), its args (the event's stats), its thread and its self time: its
duration less what the spans nested directly inside it, on its thread,
cover.  ``idle_gaps`` names the longest device-idle gaps by the innermost
``dragon.`` span open at each gap's midpoint.

    python3 benchmarks/chip/program_spans.py [trace_dir]

prints, as JSON, each span name's count, total and self seconds, and the
ten longest idle gaps so named.
"""
from __future__ import annotations

import json
import sys
from collections import defaultdict
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
import trace_reduce as T  # noqa: E402

TRACE_DIR = HERE / ".trace"
PREFIX = "dragon."
NO_SPAN = "no dragon span"

_parsed: dict = {}  # (path, mtime_ns) -> the parse of that file


def _nest(events: list, thread) -> list[dict]:
    """Spans of one thread, each with its self time."""
    spans, stack = [], []
    for name, s, e, args in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        sp = dict(name=name, start_ns=s, end_ns=e, args=args, thread=thread, self_ns=e - s)
        while stack and stack[-1]["end_ns"] <= s:
            stack.pop()
        if stack:
            stack[-1]["self_ns"] -= e - s
        stack.append(sp)
        spans.append(sp)
    return spans


def _parse(path: Path) -> dict:
    from jax.profiler import ProfileData

    spans, busy = [], []
    for pi, plane in enumerate(ProfileData.from_file(str(path)).planes):
        if T._is_device(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.start_ns, e.end_ns) for e in line.events]
            if ops or modules:
                busy.append(T._union(ops or modules))
        elif plane.name.startswith("/host:"):
            for li, line in enumerate(plane.lines):
                events = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                          for e in line.events if e.name.startswith(PREFIX)]
                spans += _nest(events, (pi, li))
    return {"spans": spans, "busy": busy}


def load(trace_dir=None) -> dict | None:
    """``{"spans": [...], "busy": [...]}`` of the newest trace, or None when
    there is none: ``busy`` is each device's merged op intervals."""
    path = T.find_xplane(TRACE_DIR if trace_dir is None else trace_dir)
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _parsed:
        _parsed.clear()
        _parsed[key] = _parse(path)
    return _parsed[key]


def named(*names: str, trace_dir=None) -> list[dict]:
    """The spans called any of ``names`` ([] with no trace)."""
    got = load(trace_dir)
    return [s for s in got["spans"] if s["name"] in names] if got else []


def seconds(spans: list[dict]) -> float:
    """Summed duration of ``spans``."""
    return sum(s["end_ns"] - s["start_ns"] for s in spans) / 1e9


def totals(trace_dir=None) -> dict:
    """Per span name: count, total seconds, self seconds."""
    out = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0})
    for s in (load(trace_dir) or {"spans": []})["spans"]:
        t = out[s["name"]]
        t["count"] += 1
        t["total_s"] += (s["end_ns"] - s["start_ns"]) / 1e9
        t["self_s"] += s["self_ns"] / 1e9
    return dict(out)


def idle_gaps(top: int = 10, trace_dir=None) -> list:
    """The ``top`` longest gaps between device operations, longest first, as
    ``[name, seconds, args]``: the innermost ``dragon.`` span open at the
    gap's midpoint, on any thread, or ``NO_SPAN``."""
    got = load(trace_dir)
    if not got:
        return []
    gaps = [(m[i][1], m[i + 1][0]) for m in got["busy"] for i in range(len(m) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        open_ = [sp for sp in got["spans"] if sp["start_ns"] <= mid <= sp["end_ns"]]
        inner = min(open_, key=lambda sp: sp["end_ns"] - sp["start_ns"]) if open_ else None
        out.append([inner["name"] if inner else NO_SPAN, (e - s) / 1e9,
                    inner["args"] if inner else {}])
    return out


if __name__ == "__main__":
    where = sys.argv[1] if len(sys.argv) > 1 else None
    print(json.dumps({"totals": totals(where), "idle_gaps": idle_gaps(trace_dir=where)},
                     default=str))
