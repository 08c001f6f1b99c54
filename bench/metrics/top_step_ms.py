"""Model step: device time of the costliest schedule step per execution
of the served program, in milliseconds.

The served programs trace every step of the compiled schedule under
``jax.named_scope(<step>)`` (``core/plan.py``), so each HLO instruction's
``op_name`` names its step (``jit(inner)/s2b0/jit(jpeg_conv_pallas)/
reshape``).  :func:`step_map` reads that from a compiled program's text;
:func:`step_times` takes, over the executions of the served modules that
lie wholly in the trace's window, the union of each step's ``XLA Ops``
intervals (a union: a ``while`` loop's body ops are events of their
own) and the share of the modules' device time that no step claims.

``read`` divides each step's time by the number of executions and
returns the largest.  It reads ``run.trace["steps"]``, the result of
:func:`step_times`; the harness does not yet compute it (it passes no
compiled text and no op intervals to the readers), so this metric is
not listed in ``BENCHMARK.json`` and reads nothing until it does."""
from __future__ import annotations

import bisect
import re

from bench import xplane

_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%(\S+) = .*\bop_name="([^"]*)"')


def step_map(hlo_text: str, steps) -> dict[str, str]:
    """Instruction name -> step, for every instruction of a compiled
    program's text whose ``op_name`` has one of ``steps`` as a path
    component.  A program traced without step scopes maps nothing."""
    steps = set(steps)
    out = {}
    for line in hlo_text.splitlines():
        m = _OP_NAME.match(line)
        if m is None:
            continue
        step = next((c for c in m.group(2).split("/") if c in steps), None)
        if step is not None:
            out[m.group(1)] = step
    return out


def _length(intervals) -> float:
    return sum(b - a for a, b in xplane.union(intervals)) * 1e-9


def step_times(path: str, step_maps: dict) -> dict:
    """Per-step device time in the window of the trace at ``path``.

    ``step_maps`` is module name -> :func:`step_map` of its text.
    Returns the number of executions of those modules wholly in the
    window, their device time (union of their op intervals), each step's
    (``by_step``), the time some step claims, and the instructions no
    step claims with their summed time, in seconds."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == xplane.WINDOW:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
    if window is None:
        raise ValueError(f"{path}: no {xplane.WINDOW!r} annotation")
    w0, w1 = window
    executions = 0
    in_module: list = []
    by_step: dict[str, list] = {}
    unclaimed: dict[str, float] = {}
    for plane in devices:
        runs = []
        for line in plane.lines:
            if line.name == "XLA Modules":
                for ev in line.events:
                    a, b = ev.start_ns, ev.start_ns + ev.duration_ns
                    module = ev.name.split("(")[0]
                    if module in step_maps and a >= w0 and b <= w1:
                        runs.append((a, b, module))
        runs.sort()
        executions += len(runs)
        starts = [r[0] for r in runs]
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                a = ev.start_ns
                i = bisect.bisect_right(starts, a) - 1
                if i < 0 or a >= runs[i][1]:
                    continue
                span = (a, min(a + ev.duration_ns, runs[i][1]))
                in_module.append(span)
                name = xplane.instruction(ev.name)
                step = step_maps[runs[i][2]].get(name)
                if step is None:
                    unclaimed[name] = (unclaimed.get(name, 0.0)
                                       + (span[1] - span[0]) * 1e-9)
                else:
                    by_step.setdefault(step, []).append(span)
    return {
        "executions": executions,
        "module_s": _length(in_module),
        "by_step": {k: _length(v) for k, v in by_step.items()},
        "claimed_s": _length([s for v in by_step.values() for s in v]),
        "unclaimed": dict(sorted(unclaimed.items(), key=lambda kv: -kv[1])),
    }


def read(run):
    steps = (run.trace or {}).get("steps")
    if not steps or not steps["executions"] or not steps["by_step"]:
        return None
    per = {k: v / steps["executions"] * 1e3
           for k, v in steps["by_step"].items()}
    top = max(per, key=per.get)
    claimed = steps["claimed_s"] / steps["module_s"]
    worst = list(steps["unclaimed"].items())[:5]
    run.note(f"top_step_ms {top} over {steps['executions']} executions; "
             f"ms per execution {per}; steps claim {100 * claimed!r}% of "
             f"the served modules' device time; unclaimed (s) {worst}")
    return per[top]
