"""Reduction of a profiler trace (``.xplane.pb``) to what the metrics read.

Reads the trace with ``jax.profiler.ProfileData`` alone.  The measured
window is the host annotation ``bench.window``; everything is clipped to
it.  On each TPU plane the ``XLA Ops`` line holds one event per executed
HLO instruction (its name starts ``%<instruction> = ...``) and the
``XLA Modules`` line one per program execution (``<module>(<id>)``); the
device's busy time is the union of the op intervals.  Idle gaps are
labelled with the host activity that overlaps them most: the host
planes' own events (runtime calls, transfers, the benchmark's
annotations).
"""
from __future__ import annotations

import glob
import os
import re

WINDOW = "bench.window"
_INSTR = re.compile(r"^%?([^\s=]+) = ")
TOP = 10


def find(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def instruction(event_name: str) -> str:
    """HLO instruction name of an ``XLA Ops`` event."""
    m = _INSTR.match(event_name)
    return m.group(1) if m else event_name


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merged, sorted ``(start, end)`` intervals."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(a: float, b: float, w0: float, w1: float):
    a, b = max(a, w0), min(b, w1)
    return (a, b) if b > a else None


def reduce(path: str) -> dict:
    """Window, busy time, per-instruction and per-module device time, and
    the breakdown of one trace.  Times in seconds, from ns in the file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    host: list[tuple[str, float, float]] = []
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    a = ev.start_ns
                    b = a + ev.duration_ns
                    if ev.name == WINDOW:
                        window = (a, b)
                    elif not ev.name.startswith("$") and b > a:
                        host.append((ev.name, a, b))
    if window is None:
        raise ValueError(f"{path}: no {WINDOW!r} annotation")
    if not devices:
        raise ValueError(f"{path}: no TPU plane")
    w0, w1 = window
    ops: dict[str, list[float]] = {}
    modules: dict[str, list[tuple[float, float]]] = {}
    busy_ns = 0.0
    busy_any: list[tuple[float, float]] = []
    for plane in devices:
        spans = []
        for line in plane.lines:
            if line.name == "XLA Ops":
                for ev in line.events:
                    c = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                              w0, w1)
                    if c is None:
                        continue
                    spans.append(c)
                    rec = ops.setdefault(instruction(ev.name), [0, 0.0])
                    rec[0] += 1
                    rec[1] += (c[1] - c[0]) * 1e-9
            elif line.name == "XLA Modules":
                for ev in line.events:
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    if a >= w0 and b <= w1:
                        name = ev.name.split("(")[0]
                        modules.setdefault(name, []).append(
                            (a * 1e-9, (b - a) * 1e-9))
        merged = union(spans)
        busy_ns += sum(b - a for a, b in merged)
        busy_any += merged
    busy = union(busy_any)
    gaps, prev = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > prev:
            gaps.append((prev, a))
        prev = max(prev, b)
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = []
    for a, b in gaps[:TOP]:
        best, label = 0.0, "no host event"
        for name, ha, hb in host:
            ov = min(b, hb) - max(a, ha)
            if ov > best:
                best, label = ov, name
        idle.append([label, (b - a) * 1e-9])
    top = sorted(ops.items(), key=lambda kv: -kv[1][1])[:TOP]
    return {
        "window": (w0 * 1e-9, w1 * 1e-9),
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / len(devices),
        "devices": len(devices),
        "ops": {k: (v[0], v[1]) for k, v in ops.items()},
        "modules": modules,
        "breakdown": {"device_ops": [[k, v[1]] for k, v in top],
                      "idle_gaps": idle},
    }
