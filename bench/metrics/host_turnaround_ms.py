"""Scheduler: the worker's host turnaround between batches, in
milliseconds.

For consecutive batches k, k+1 whose ``device/launch`` span starts in
the window, the time from the end of batch k's ``device/read`` (the
logits are on the host, so the device has finished k) to the start of
batch k+1's ``device/launch``.  With one batch in flight this is the
interval in each batch in which the chip has nothing queued.  A pair
whose batch k left no ``read`` (its executable raised) is skipped.

The note splits the mean by the time the program's own spans cover in
those intervals: ``scheduler/complete``, ``scheduler/batch-form``,
``device/stack``, ``device/pad/stage``; "uncovered" is the rest (GIL
waits, the worker's condition wait).  A program without ``launch`` and
``read`` spans reads nothing."""

SPLIT = (("scheduler", "complete"), ("scheduler", "batch-form"),
         ("device", "stack"), ("device", "pad/stage"))


def turnarounds(spans, window):
    """``(start, end)`` of each turnaround whose second launch starts in
    ``window``, from spans ``(track, name, t0, t1, args)``."""
    events = sorted((t0, name, t1) for track, name, t0, t1, _ in spans
                    if track == "device" and name in ("launch", "read"))
    out, read = [], None
    seen_launch = False
    for t0, name, t1 in events:
        if name == "read":
            read = t1
        elif name == "launch":
            if seen_launch and read is not None \
                    and window[0] <= t0 < window[1]:
                out.append((read, t0))
            seen_launch, read = True, None
    return out


def _covered(intervals, spans) -> float:
    """Time of sorted, disjoint ``intervals`` that sorted, disjoint
    ``spans`` cover."""
    total, j = 0.0, 0
    for a, b in intervals:
        while j < len(spans) and spans[j][1] <= a:
            j += 1
        k = j
        while k < len(spans) and spans[k][0] < b:
            total += min(b, spans[k][1]) - max(a, spans[k][0])
            k += 1
    return total


def read(run):
    gaps = turnarounds(run.spans, run.window)
    if not gaps:
        return None
    lo, hi = gaps[0][0], gaps[-1][1]
    parts = {}
    for track, name in SPLIT:
        inside = sorted((t0, t1) for tr, nm, t0, t1, _ in run.spans
                        if tr == track and nm == name and t1 > lo
                        and t0 < hi)
        parts[f"{track}/{name}"] = _covered(gaps, inside) / len(gaps) * 1e3
    mean = sum(b - a for a, b in gaps) / len(gaps) * 1e3
    parts["uncovered"] = mean - sum(parts.values())
    run.note(f"host_turnaround_ms over {len(gaps)} batch pairs, mean "
             f"{mean!r} ms: " + ", ".join(f"{k} {v!r}"
                                         for k, v in parts.items()))
    return mean
