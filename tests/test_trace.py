"""Flight-recorder tracing (``repro.serving.trace``).

Contracts:

* **bounded ring** — the tracer keeps the newest ``capacity`` events,
  counts every eviction in ``dropped``, and recording stays safe under
  concurrent writers;
* **Perfetto-loadable export** — Chrome trace-event JSON with one pid
  per component track, µs timestamps relative to construction, flow
  pairs carrying the request id; ``validate_trace`` accepts it and
  rejects schema violations and orphan chains;
* **scheduler integration** — a traced serve run closes every request
  chain (admission → queue → terminal instant), links each completed
  request to exactly one device-dispatch span, and the per-stage span
  sums reconcile with the report's ``device_wall_s``/``ingest_wall_s``
  (within 5%: the spans *are* the recorded intervals).
"""
import json
import threading

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.core import plan as PL
from repro.core import resnet as R
from repro import serving as SV
from repro.serving.trace import NULL_TRACER, Tracer, validate_trace


class FakeClock:
    """Deterministic monotonic clock: advances only on ``tick``."""

    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt=1.0):
        self.t += dt
        return self.t


# --------------------------------------------------------------------------
# Ring buffer
# --------------------------------------------------------------------------


def test_ring_keeps_newest_and_counts_drops():
    clk = FakeClock()
    tr = Tracer(capacity=4, clock=clk)
    for i in range(10):
        tr.instant("scheduler", f"ev{i}", t=clk.tick())
    evs = tr.events()
    assert len(evs) == 4
    assert tr.dropped == 6
    # a flight recorder keeps the end of the story, not the beginning
    assert [e[3] for e in evs] == ["ev6", "ev7", "ev8", "ev9"]
    assert tr.export()["otherData"]["dropped"] == 6


def test_ring_capacity_validation():
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_span_timestamps_relative_microseconds():
    clk = FakeClock(t=50.0)
    tr = Tracer(clock=clk)          # construction reads t0 = 50.0
    t0 = clk.tick(1.0)              # 51.0 -> ts = 1s
    t1 = clk.tick(0.25)             # 51.25 -> dur = 0.25s
    tr.span("device", "device-dispatch", t0, t1, args={"n": 2})
    (ev,) = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"]
    assert ev["ts"] == pytest.approx(1e6)
    assert ev["dur"] == pytest.approx(0.25e6)
    assert ev["args"] == {"n": 2}


def test_span_negative_interval_clamped():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.span("device", "x", clk() + 5.0, clk())  # t1 < t0
    (ev,) = [e for e in tr.export()["traceEvents"] if e["ph"] == "X"]
    assert ev["dur"] == 0.0


def test_export_pids_and_process_metadata():
    tr = Tracer(clock=FakeClock())
    tr.instant("request", "complete", t=100.0, tid=3)
    tr.instant("scheduler", "tier-switch", t=100.0)
    out = tr.export()
    meta = {e["args"]["name"]: e["pid"]
            for e in out["traceEvents"]
            if e["ph"] == "M" and e["name"] == "process_name"}
    # pids follow canonical track order regardless of recording order
    assert meta == {"scheduler": 1, "request": 2}
    by_cat = {e["cat"]: e["pid"] for e in out["traceEvents"]
              if e["ph"] == "i"}
    assert by_cat == {"scheduler": 1, "request": 2}


def test_flow_pair_export():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.flow(7, ("request", 7, clk.tick()), ("device", 0, clk.tick()))
    s, f = [e for e in tr.export()["traceEvents"] if e["ph"] in "sf"]
    assert s["ph"] == "s" and f["ph"] == "f"
    assert s["id"] == f["id"] == 7
    assert f["bp"] == "e"
    assert s["cat"] == f["cat"] == "flow"


def test_summary_counts_by_name():
    tr = Tracer(clock=FakeClock())
    tr.instant("scheduler", "reject", t=100.0)
    tr.instant("scheduler", "reject", t=100.0)
    tr.span("device", "device-dispatch", 100.0, 100.5)
    s = tr.summary()
    assert s["enabled"] and s["events"] == 3 and s["dropped"] == 0
    assert s["by_name"] == {"scheduler/reject": 2,
                            "device/device-dispatch": 1}


def test_thread_hammer_never_loses_accounting():
    """N writers race the ring: every record is either retained or
    counted as dropped — no event vanishes silently."""
    tr = Tracer(capacity=512)
    n_threads, per_thread = 8, 1000

    def hammer(k):
        for i in range(per_thread):
            tr.instant("scheduler", "ev", tid=k, args={"i": i})

    ts = [threading.Thread(target=hammer, args=(k,))
          for k in range(n_threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    evs = tr.events()
    assert len(evs) == 512
    assert tr.dropped == n_threads * per_thread - 512


def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    NULL_TRACER.span("device", "x", 0.0, 1.0)
    NULL_TRACER.instant("device", "y")
    NULL_TRACER.flow(1, ("a", 0, 0.0), ("b", 0, 0.0))
    assert NULL_TRACER.events() == []
    assert NULL_TRACER.summary()["events"] == 0
    assert NULL_TRACER.now() == 0.0


class _Annotations:
    """Stand-in for ``jax.profiler.TraceAnnotation`` that logs enters and
    exits."""

    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.log.append(("enter", self.name))

    def __exit__(self, *exc):
        self.log.append(("exit", self.name))


def test_scope_records_ring_span_inside_an_annotation(monkeypatch):
    """``Tracer.scope`` records the ``with`` block exactly as ``span``
    would, with args read at exit, inside an annotation named
    ``<track>/<name>``; an exception still closes both."""
    from repro.serving import trace as trace_mod

    monkeypatch.setattr(trace_mod, "TraceAnnotation", _Annotations)
    _Annotations.log = []
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.scope("device", "stack", tid=3) as sc:
        clk.tick(0.5)
        sc.args = {"n": 4}
    with pytest.raises(RuntimeError):
        with tr.scope("scheduler", "complete"):
            clk.tick(0.25)
            raise RuntimeError("boom")
    assert _Annotations.log == [("enter", "device/stack"),
                                ("exit", "device/stack"),
                                ("enter", "scheduler/complete"),
                                ("exit", "scheduler/complete")]
    (a, b) = tr.events()
    assert a == ("X", "device", 3, "stack", 0.0, 0.5, {"n": 4})
    assert b == ("X", "scheduler", 0, "complete", 0.5, 0.25, None)


def test_null_scope_is_one_shared_empty_context(monkeypatch):
    """Disabled tracing: ``scope`` hands back one preallocated
    ``nullcontext`` and opens no annotation."""
    import contextlib

    from repro.serving import trace as trace_mod

    monkeypatch.setattr(trace_mod, "TraceAnnotation", _Annotations)
    _Annotations.log = []
    sc = NULL_TRACER.scope("device", "stack", args={"n": 1})
    assert sc is NULL_TRACER.scope("scheduler", "complete")
    assert isinstance(sc, contextlib.nullcontext)
    with sc:
        pass
    assert _Annotations.log == []
    assert NULL_TRACER.events() == []


def test_jax_profile_none_is_noop():
    with SV.jax_profile(None):
        pass
    with SV.jax_profile(""):
        pass


def test_jax_profile_that_cannot_start_raises(monkeypatch, tmp_path):
    """A requested device profile never degrades to an untraced run."""
    import jax

    def refuse(_dir):
        raise RuntimeError("profiler unavailable")

    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with SV.jax_profile(str(tmp_path)):
            pass


# --------------------------------------------------------------------------
# validate_trace
# --------------------------------------------------------------------------


def _chain(tr, clk, rid):
    """Record one complete request chain on ``tr``."""
    t_sub = clk.tick()
    t_enq = clk.tick(0.01)
    tr.span("request", "admission", t_sub, t_enq, tid=rid)
    t_take = clk.tick(0.1)
    tr.span("request", "queue", t_enq, t_take, tid=rid)
    t1 = clk.tick(0.2)
    tr.span("device", "device-dispatch", t_take, t1,
            args={"rids": [rid], "n": 1})
    tr.flow(rid, ("request", rid, t_take), ("device", 0, t_take))
    tr.instant("request", "complete", t=t1, tid=rid)


def test_validate_accepts_closed_chains():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    for rid in (1, 2, 3):
        _chain(tr, clk, rid)
    summ = validate_trace(tr.export())
    assert summ["requests"] == summ["complete"] == 3
    assert summ["open_chains"] == []
    assert summ["dropped"] == 0
    assert summ["spans_by_name"]["request/admission"] == 3
    assert summ["device_span_s"] == pytest.approx(0.6, rel=1e-3)


def test_validate_device_span_is_union_of_dispatches():
    """Two batches in flight overlap: the dispatch spans' union, not
    their sum, is the time ``device_wall_s`` accumulates."""
    clk = FakeClock()
    tr = Tracer(clock=clk)
    t0 = clk.tick()
    tr.span("device", "device-dispatch", t0, t0 + 0.3, tid=0)
    tr.span("device", "device-dispatch", t0 + 0.2, t0 + 0.5, tid=1)
    tr.span("device", "device-dispatch", t0 + 0.7, t0 + 0.8, tid=0)
    summ = validate_trace(tr.export())
    assert summ["device_span_s"] == pytest.approx(0.6, rel=1e-3)


def test_validate_rejects_orphan_chain():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    _chain(tr, clk, 1)
    tr.span("request", "admission", clk.tick(), clk.tick(), tid=9)
    with pytest.raises(ValueError, match="orphan"):
        validate_trace(tr.export())
    summ = validate_trace(tr.export(), require_closed=False)
    assert summ["open_chains"] == [9]


def test_validate_rejects_complete_without_dispatch_membership():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    t0, t1 = clk.tick(), clk.tick()
    tr.span("request", "admission", t0, t1, tid=5)
    tr.span("request", "queue", t1, clk.tick(), tid=5)
    tr.instant("request", "complete", t=clk.tick(), tid=5)
    with pytest.raises(ValueError, match="device-dispatch"):
        validate_trace(tr.export())


def test_validate_rejects_chain_without_admission():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.instant("request", "complete", t=clk.tick(), tid=4)
    with pytest.raises(ValueError, match="without admission"):
        validate_trace(tr.export())


def test_validate_rejects_schema_violations():
    with pytest.raises(ValueError, match="traceEvents"):
        validate_trace({"foo": []})
    good = Tracer(clock=FakeClock()).export()
    bad = json.loads(json.dumps(good))
    bad["traceEvents"].append({"name": "x", "ph": "Q", "ts": 0.0,
                               "pid": 1, "tid": 0})
    with pytest.raises(ValueError, match="bad ph"):
        validate_trace(bad)
    bad2 = json.loads(json.dumps(good))
    bad2["traceEvents"].append({"name": "x", "ph": "X", "ts": 0.0,
                                "pid": 1, "tid": 0, "dur": -5.0})
    with pytest.raises(ValueError, match="dur"):
        validate_trace(bad2)


# --------------------------------------------------------------------------
# Scheduler integration
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def setup():
    spec = R.ResNetSpec(widths=(6, 8), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, 16, 16)) * 0.5
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    cfg = DSP.DispatchConfig(path="reference")
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    ladder = SV.build_ladder(plan, caps=(None, 16))
    return spec, coef, plan, ladder


#: the spans every dispatched batch leaves, as "<track>/<name>"
PER_BATCH = ("scheduler/batch-form", "device/stack", "device/pad/stage",
             "device/launch", "device/read", "scheduler/complete")
DEVICE_CHILDREN = PER_BATCH[1:5]


def _sched(ladder, coef, tracer, **kw):
    kw.setdefault("batch", 2)
    kw.setdefault("grid", tuple(coef.shape[1:3]))
    kw.setdefault("channels", int(coef.shape[3]))
    return SV.BandElasticScheduler(ladder, tracer=tracer, **kw)


def _jpeg_traffic(n, seed=0):
    from repro.codec import encode_pixels
    from repro.core import dct as dctlib

    rng = np.random.default_rng(seed)
    qt = np.rint(dctlib.quantization_table(
        75, dc_is_mean=False)).astype(np.int64)
    return [encode_pixels(
        np.clip(rng.normal(0, 0.3, (3, 16, 16)), -1.0, 127.0 / 128.0),
        qtable=qt) for _ in range(n)]


def test_traced_run_closes_chains_and_reconciles_walls(setup, tmp_path):
    """The acceptance run: mixed traffic through a traced scheduler →
    every chain closes, every completed request sits in exactly one
    device-dispatch span, and span sums match the metrics walls ≤5%."""
    spec, coef, plan, ladder = setup
    tracer = SV.Tracer()
    n_coef, n_bytes = 6, 6
    with _sched(ladder, coef, tracer) as s:
        s.warmup()
        reqs = [s.submit(np.asarray(coef[i % coef.shape[0]]))
                for i in range(n_coef)]
        reqs += [s.submit(d, kind="bytes") for d in _jpeg_traffic(n_bytes)]
        outs = [r.result(timeout=120) for r in reqs]
    assert all(np.isfinite(o).all() for o in outs)

    path = tmp_path / "trace.json"
    tracer.write(str(path))
    with open(path) as f:
        obj = json.load(f)
    summ = validate_trace(obj)
    assert summ["dropped"] == 0
    assert summ["complete"] == n_coef + n_bytes
    assert summ["requests"] == n_coef + n_bytes
    assert summ["open_chains"] == []
    assert summ["failed"] == summ["shed"] == 0
    # each batch leaves one batch-form, one device-dispatch with its four
    # children (stack, pad/stage, launch, read) and one complete span;
    # bytes batches add ingest-decode spans
    by = summ["spans_by_name"]
    for name in PER_BATCH:
        assert by[name] == by["device/device-dispatch"], name
    assert by["ingest/ingest-decode"] >= 1
    assert summ["flows"] == 2 * (n_coef + n_bytes)

    rep = s.metrics.report()
    # the device-dispatch spans record the *identical* intervals
    # record_batch accumulates, so the sums agree to rounding; 5% is the
    # acceptance bound
    assert summ["device_span_s"] == pytest.approx(
        rep["device_wall_s"], rel=0.05)
    assert summ["ingest_span_s"] == pytest.approx(
        rep["ingest_wall_s"], rel=0.05, abs=1e-3)


def test_traced_shed_and_fail_close_their_chains(setup):
    """Expired and poisoned requests still terminate their trace chains
    (shed/fail instants) — no orphans on the unhappy paths."""
    spec, coef, plan, ladder = setup
    tracer = SV.Tracer()
    with _sched(ladder, coef, tracer) as s:
        ok = s.submit(np.asarray(coef[0]))
        expired = s.submit(np.asarray(coef[1]), deadline_s=-0.001)
        bad = s.submit(b"not a jpeg scan", kind="bytes")
        assert np.isfinite(ok.result(timeout=60)).all()
        with pytest.raises(SV.DeadlineExceeded):
            expired.result(timeout=60)
        with pytest.raises(SV.RequestFailed):
            bad.result(timeout=60)
        s.drain()
    summ = validate_trace(tracer.export())
    assert summ["open_chains"] == []
    assert summ["shed"] == 1
    assert summ["failed"] == 1
    assert summ["complete"] == 1


def test_traced_overload_marks_tier_switches(setup):
    """Tier switches surface as scheduler-track instants carrying the
    from/to tiers, alongside the metrics timeline."""
    from repro.serving.qos import QosPolicy

    spec, coef, plan, ladder3 = setup
    ladder = SV.build_ladder(plan, caps=(None, 32, 16))
    tracer = SV.Tracer()
    policy = QosPolicy(high_depth=1.5, low_depth=0.5, hysteresis=1)
    with _sched(ladder, coef, tracer, policy=policy, max_pending=64) as s:
        reqs = [s.submit(np.asarray(coef[i % coef.shape[0]]))
                for i in range(24)]
        s.drain(timeout=120)
    assert all(r is not None and r.done() for r in reqs)
    switches = [e for e in tracer.events()
                if e[0] == "i" and e[3] == "tier-switch"]
    assert switches, "overload burst must trace tier-switch instants"
    assert len(switches) == len(s.metrics.tier_switches)
    assert all({"from", "to", "reason"} <= set(e[6]) for e in switches)
    summ = validate_trace(tracer.export())
    assert summ["complete"] == 24 and summ["open_chains"] == []


def test_untraced_scheduler_records_nothing(setup):
    spec, coef, plan, ladder = setup
    with _sched(ladder, coef, None) as s:
        assert s.tracer is NULL_TRACER
        r = s.submit(np.asarray(coef[0]))
        assert np.isfinite(r.result(timeout=60)).all()
    assert s.tracer.events() == []


def _host_events(log_dir):
    """``name -> [(start_ns, end_ns)]`` of the profile's host-plane
    events."""
    import glob
    import os

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out: dict = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    out.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def test_per_batch_scopes_reach_the_profiler(setup, tmp_path):
    """Under a profiler session every dispatched batch leaves each
    per-batch span once in the ring and once as a host-plane event; the
    four device children nest in their device-dispatch span on their
    trace row (two batches in flight overlap, on rows 0 and 1); the trace
    still validates; the disabled tracer's scope leaves no event."""
    spec, coef, plan, ladder = setup
    tracer = SV.Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with _sched(ladder, coef, tracer) as s:
        s.warmup()
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            reqs = [s.submit(np.asarray(coef[i % coef.shape[0]]))
                    for i in range(6)]
            reqs += [s.submit(d, kind="bytes") for d in _jpeg_traffic(4)]
            assert all(np.isfinite(r.result(timeout=120)).all()
                       for r in reqs)
            s.drain()  # every batch's complete span has closed
            with NULL_TRACER.scope("device", "null-probe"):
                pass
        finally:
            jax.profiler.stop_trace()
    host = _host_events(tmp_path)
    spans = [e for e in tracer.events() if e[0] == "X"]
    dispatches = [(tid, t, t + d) for _, track, tid, name, t, d, _ in spans
                  if (track, name) == ("device", "device-dispatch")]
    assert len(dispatches) >= 5  # batch 2: 6 coefficient + 4 bytes images
    for full in PER_BATCH:
        ring = [e for e in spans if f"{e[1]}/{e[3]}" == full]
        assert len(ring) == len(dispatches), full
        assert len(host.get(full, [])) == len(dispatches), full
    for _, track, tid, name, t, d, _ in spans:
        if f"{track}/{name}" in DEVICE_CHILDREN:
            assert sum(row == tid and a <= t and t + d <= b
                       for row, a, b in dispatches) == 1
    assert "device/null-probe" not in host
    ingest = [e for e in spans if (e[1], e[3]) == ("ingest", "ingest-decode")]
    assert len(host["ingest/ingest-decode"]) == len(ingest) >= 1
    assert validate_trace(tracer.export())["open_chains"] == []
