"""A 0.2 s window of ``paper-cifar32.coef.closed``, traced on one TPU v5e
with the program's step scopes and per-batch span annotations: its
profile, the served program's compiled text (stack-frame tables left
out) and the program's ring spans of the scheduler, device and ingest
tracks around the window."""
import gzip
import json
import shutil
import statistics
from pathlib import Path

import pytest

from bench import spec

DATA = Path(__file__).parent / "data"
ROOT = Path(__file__).resolve().parents[2]
STEPS = ("stem", "s0b0", "s1b0", "s2b0", "head")
PER_BATCH = ("scheduler/batch-form", "device/stack", "device/pad/stage",
             "device/launch", "device/read", "scheduler/complete")


def _metric(name):
    metrics = spec.cell(ROOT, "paper-cifar32.coef.closed")["metrics_dir"]
    return spec.reader(metrics, name)


def _module(name):
    """A reader's module, for its helpers."""
    import importlib.util

    metrics = spec.cell(ROOT, "paper-cifar32.coef.closed")["metrics_dir"]
    s = importlib.util.spec_from_file_location(f"t_{name}",
                                               metrics / f"{name}.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod


class View:
    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.notes = []

    def note(self, text):
        self.notes.append(text)


@pytest.fixture(scope="module")
def trace_path(tmp_path_factory):
    path = tmp_path_factory.mktemp("scoped") / "t.xplane.pb"
    with gzip.open(DATA / "cifar_scoped.xplane.pb.gz") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def ring():
    with open(DATA / "cifar_scoped.spans.json") as f:
        rec = json.load(f)
    rec["spans"] = [(track, name, t0, t1, {})
                    for track, name, t0, t1 in rec["spans"]]
    return rec


@pytest.fixture(scope="module")
def steps(trace_path):
    tsm = _module("top_step_ms")
    with gzip.open(DATA / "cifar_scoped.hlo.txt.gz", "rt") as f:
        text = f.read()
    smap = tsm.step_map(text, STEPS)
    return smap, tsm.step_times(trace_path, {"jit_inner": smap})


def test_step_map_names_every_step(steps):
    smap, _ = steps
    assert set(smap.values()) == set(STEPS)
    assert smap["jpeg_conv_pallas.1"] == "s2b0"
    assert smap["reshape.1"] == "s2b0"


def test_top_step_on_recorded_trace(steps):
    """11 executions of the served module lie wholly in the window;
    ``s2b0`` (the Pallas ``jpeg_conv`` and its operator's reshape) takes
    12.03 ms of each, and the steps claim 97.9% of the module's device
    time (most of the rest waits on async copies and slices, whose
    ``-done`` instructions carry no metadata)."""
    _, times = steps
    assert times["executions"] == 11
    assert times["claimed_s"] / times["module_s"] == pytest.approx(
        0.97942, abs=1e-4)
    top = list(times["unclaimed"])[:2]
    assert top == ["slice-done.4", "copy-done.16"]
    assert sum(times["unclaimed"][n] for n in top) > 0.7 * (
        times["module_s"] - times["claimed_s"])
    view = View(trace={"steps": times})
    assert _metric("top_step_ms")(view) == pytest.approx(12.0295, abs=1e-3)
    assert "top_step_ms s2b0 over 11 executions" in view.notes[0]


def test_top_step_reads_nothing_without_steps(trace_path):
    """A program traced without step scopes maps no instruction."""
    tsm = _module("top_step_ms")
    plain = ('%reshape.1 = f32[2]{0} reshape(%a), metadata={op_name='
             '"jit(inner)/jit(jpeg_conv_pallas)/reshape"}')
    assert tsm.step_map(plain, STEPS) == {}
    times = tsm.step_times(trace_path, {"jit_inner": {}})
    assert times["by_step"] == {} and times["executions"] == 11
    assert _metric("top_step_ms")(View(trace={"steps": times})) is None
    assert _metric("top_step_ms")(View(trace={})) is None


def test_host_turnaround_on_recorded_spans(ring):
    """12 batch pairs in the window: 2.60 ms from a read's end to the next
    launch, most of it ``device/stack`` and ``scheduler/complete``."""
    view = View(spans=ring["spans"], window=tuple(ring["window"]))
    assert _metric("host_turnaround_ms")(view) == pytest.approx(
        2.602976666665313, rel=1e-6)
    assert "over 12 batch pairs" in view.notes[0]


def test_ring_spans_have_profiler_twins(ring, trace_path):
    """Every batch dispatched in the window has one of each per-batch
    span in the ring and one as a host-plane annotation; mapped by the
    run's trace offset, a ring span starts within 0.2 ms of its twin."""
    from jax.profiler import ProfileData

    host: dict = {}
    for plane in ProfileData.from_file(trace_path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in PER_BATCH:
                        host.setdefault(ev.name, []).append(
                            ev.start_ns * 1e-9)
    w0, w1 = ring["window"]
    off = ring["trace_offset"]
    spans = ring["spans"]
    dispatches = [(t0, t1) for track, name, t0, t1, _ in spans
                  if name == "device-dispatch" and w0 <= t0 < w1]
    assert len(dispatches) == 12
    gaps = []
    for full in PER_BATCH:
        starts = [t0 for track, name, t0, _, _ in spans
                  if f"{track}/{name}" == full and w0 <= t0 < w1]
        assert len(starts) == len(dispatches), full
        for t0 in starts:
            gaps.append(min(abs(h - (t0 + off)) for h in host[full]))
    assert max(gaps) < 2e-4
    assert statistics.median(gaps) < 2e-5
    for track, name, t0, t1, _ in spans:
        if track == "device" and name != "device-dispatch" \
                and w0 <= t0 < w1:
            assert sum(a <= t0 and t1 <= b for a, b in dispatches) == 1
