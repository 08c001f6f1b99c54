"""The scheduler's host turnaround, on spans laid out by hand: pairs whose
second launch starts in the window count, a batch without a logits read
breaks its pair, and the note splits the mean by span."""
import re
from pathlib import Path

import pytest

from bench import spec

ROOT = Path(__file__).resolve().parents[2]


def _reader():
    metrics = spec.cell(ROOT, "paper-cifar32.coef.closed")["metrics_dir"]
    return spec.reader(metrics, "host_turnaround_ms")


class View:
    def __init__(self, spans, window):
        self.spans = spans
        self.window = window
        self.notes = []

    def note(self, text):
        self.notes.append(text)


def _batch(t, *, read=True):
    """One batch starting at ``t`` s: batch-form, stack, pad/stage and
    launch 1 ms each, read 6 ms, complete 2 ms."""
    ms = 1e-3
    spans = [("scheduler", "batch-form", t, t + ms, {}),
             ("device", "stack", t + ms, t + 2 * ms, {}),
             ("device", "pad/stage", t + 2 * ms, t + 3 * ms, {}),
             ("device", "launch", t + 3 * ms, t + 4 * ms, {})]
    if read:
        spans.append(("device", "read", t + 4 * ms, t + 10 * ms, {}))
    spans.append(("scheduler", "complete", t + 10 * ms, t + 12 * ms, {}))
    return spans


def _parts(note):
    return {k: float(v) for k, v in
            re.findall(r"(scheduler/\S+|device/\S+|uncovered) ([-\d.e]+)",
                       note)}


def test_pairs_in_the_window():
    """Batches every 20 ms: a read ends 13 ms before the next launch
    starts; the window holds the second launches of two pairs."""
    spans = [s for t in (0.0, 0.02, 0.04, 0.06) for s in _batch(t)]
    view = View(spans, (0.015, 0.05))
    assert _reader()(view) == pytest.approx(13.0)
    (note,) = view.notes
    assert "over 2 batch pairs" in note
    parts = _parts(note)
    assert parts["scheduler/complete"] == pytest.approx(2.0)
    assert parts["scheduler/batch-form"] == pytest.approx(1.0)
    assert parts["device/stack"] == pytest.approx(1.0)
    assert parts["device/pad/stage"] == pytest.approx(1.0)
    assert parts["uncovered"] == pytest.approx(8.0)


def test_batch_without_read_breaks_its_pair():
    """The second batch's executable raised: no read, so the pair it
    opens is left out and only the pair before it counts."""
    spans = (_batch(0.0) + _batch(0.02, read=False) + _batch(0.04)
             + _batch(0.06))
    view = View(spans, (0.015, 0.05))
    assert _reader()(view) == pytest.approx(13.0)
    assert "over 1 batch pairs" in view.notes[0]


def test_nothing_to_read():
    """No launch in the window, or a program without the spans."""
    spans = [s for t in (0.0, 0.02) for s in _batch(t)]
    assert _reader()(View(spans, (0.5, 1.0))) is None
    dispatch_only = [("device", "device-dispatch", 0.0, 0.01, {"n": 2})]
    view = View(dispatch_only, (0.0, 1.0))
    assert _reader()(view) is None
    assert view.notes == []
