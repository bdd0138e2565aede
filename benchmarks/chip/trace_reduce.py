"""Reduce a JAX profiler trace (``.xplane.pb``) to the benchmark's device
numbers: device busy time, per-program device time, the device operations
that took longest, and the longest idle gaps named by the host span that was
open during each.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices in the trace.  Programs are the XLA
modules the device executed, by their jit names; operations are the XLA ops
inside them.  Host spans are the benchmark's own ``TraceAnnotation`` events
(names starting ``bench.``) on the host planes.
"""
from __future__ import annotations

from collections import defaultdict
from pathlib import Path

SPAN_PREFIX = "bench."


def find_xplane(trace_dir) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _is_device(name: str) -> bool:
    return name.startswith("/device:") and not name.startswith("/device:CUSTOM")


def load(path):
    """(device planes, host spans) of a trace: each device plane as
    ``{"ops": [(name, start_ns, end_ns)], "modules": [...]}``; host spans as
    ``[(name, start_ns, end_ns)]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans = [], []
    for plane in pd.planes:
        if _is_device(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.name, e.start_ns, e.end_ns) for e in line.events]
                elif line.name == "XLA Modules":
                    modules += [(e.name, e.start_ns, e.end_ns) for e in line.events]
            if ops or modules:
                devices.append({"name": plane.name, "ops": ops, "modules": modules})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [
                    (e.name, e.start_ns, e.end_ns) for e in line.events
                    if e.name.startswith(SPAN_PREFIX)
                ]
    return devices, spans


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ")[0]


def module_name(event_name: str) -> str:
    """``jit_fn(12345)`` -> ``jit_fn``: the jit name without its id."""
    return event_name.split("(")[0]


def reduce(path, window_s: float, top: int = 10) -> dict | None:
    """Device numbers of one traced window, or None when no device ran."""
    devices, spans = load(path)
    if not devices:
        return None
    busy, per_module, per_op = [], defaultdict(float), defaultdict(float)
    gaps = []
    for d in devices:
        timed = d["ops"] or d["modules"]
        merged = _union([(s, e) for _, s, e in timed])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for name, s, e in d["modules"]:
            per_module[module_name(name)] += (e - s) / 1e9 / len(devices)
        for name, s, e in d["ops"]:
            per_op[op_name(name)] += (e - s) / 1e9 / len(devices)
        gaps += [(merged[i][1], merged[i + 1][0]) for i in range(len(merged) - 1)]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        # the innermost benchmark span open at the gap's midpoint
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[1] <= mid <= sp[2]]
        name = min(open_, key=lambda sp: sp[2] - sp[1])[0] if open_ else "no bench span"
        named.append([name, (e - s) / 1e9])
    busy_s = sum(busy) / len(busy)
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "modules": dict(per_module),
        "device_ops": sorted(([k, v] for k, v in per_op.items()), key=lambda kv: -kv[1])[:top],
        "idle_gaps": named,
    }
