"""Band-elastic serving runtime (``repro.serving``).

Contracts:

* **ladder tiers are exact** — a tier derived by prefix-slicing the base
  plan's operators produces logits *bit-identical* to independently
  building + compiling a plan at the capped band assignment;
* **ladder save/restore** round-trips bit-exactly through
  ``CheckpointManager``; a manifest saved against a different plan is
  rejected loudly;
* **scheduler lifecycle** mirrors the PR-4 ``prefetch`` contract: close
  drains by default, a non-draining close fails queued requests with
  ``SchedulerClosed``, and a worker crash re-raises at every waiter and
  at ``close()`` instead of hanging;
* **QoS policy** degrades under queue pressure / deadline pressure and
  recovers on drain, each only after ``hysteresis`` consecutive signals.
"""
import threading
import time

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import dispatch as DSP
from repro.core import jpeg as J
from repro.core import plan as PL
from repro.core import resnet as R
from repro import serving as SV
from repro.serving.qos import QosPolicy, TierSelector


@pytest.fixture(scope="module")
def setup():
    # two stages -> one strided projection block; 16x16 input = 2x2 blocks
    spec = R.ResNetSpec(widths=(6, 8), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    key = jax.random.PRNGKey(7)
    for name in params:
        if "_bn" in name or name.endswith("bn"):
            k1, k2, k3, k4, key = jax.random.split(key, 5)
            c = params[name]["gamma"].shape[0]
            params[name]["gamma"] = 1.0 + 0.2 * jax.random.normal(k1, (c,))
            params[name]["beta"] = 0.1 * jax.random.normal(k2, (c,))
            state[name]["mean"] = 0.1 * jax.random.normal(k3, (c,))
            state[name]["var"] = 1.0 + 0.3 * jax.random.uniform(k4, (c,))
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, 16, 16)) * 0.5
    coef = jnp.moveaxis(J.jpeg_encode(x, quality=spec.quality, scaled=True),
                        1, 3)
    cfg = DSP.DispatchConfig(path="reference")
    plan = PL.build_plan(params, state, spec, dispatch=cfg)
    return spec, params, state, coef, plan


# --------------------------------------------------------------------------
# Plan ladder
# --------------------------------------------------------------------------


@pytest.mark.parametrize("cap", [48, 32, 16])
def test_tier_bit_identical_to_independent_compile(setup, cap):
    """A derived tier == build_plan at the capped bands, compiled — both
    the plan walk and the compiled schedule, to the bit."""
    spec, params, state, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, cap))
    tier = ladder.tiers[1]
    assert tier.bands == {k: min(v, cap) for k, v in plan.bands.items()}

    indep = PL.build_plan(params, state, spec, dispatch=plan.cfg,
                          bands=dict(tier.bands))
    got_walk = np.asarray(PL.apply_plan(tier.plan, coef))
    want_walk = np.asarray(PL.apply_plan(indep, coef))
    assert np.array_equal(got_walk, want_walk)

    indep_cp = PL.compile_plan(indep)
    got = np.asarray(PL.apply_compiled(tier.compiled, coef))
    want = np.asarray(PL.apply_compiled(indep_cp, coef))
    assert np.array_equal(got, want)


def test_top_tier_is_the_base_plan(setup):
    spec, params, state, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, 32))
    assert ladder.top.plan is plan
    assert ladder.top.cap is None
    np.testing.assert_array_equal(
        np.asarray(PL.apply_compiled(ladder.top.compiled, coef)),
        np.asarray(PL.apply_compiled(PL.compile_plan(plan), coef)))


def test_redundant_caps_share_compiled_schedules(setup):
    """Caps at or above the plan's own band assignment collapse onto the
    previous tier and share its CompiledPlan object outright."""
    spec, params, state, coef, plan = setup
    assert max(plan.bands.values()) == 64
    ladder = SV.build_ladder(plan, caps=(None, 64, 32))
    assert len(ladder) == 3
    assert ladder.tiers[1].shared_with == 0
    assert ladder.tiers[1].compiled is ladder.tiers[0].compiled
    assert ladder.tiers[2].shared_with is None


def test_ladder_caps_validation(setup):
    *_, plan = setup
    with pytest.raises(ValueError):
        SV.build_ladder(plan, caps=(32, None))     # None must come first
    with pytest.raises(ValueError):
        SV.build_ladder(plan, caps=(None, 24, 32))  # must decrease
    with pytest.raises(ValueError):
        SV.build_ladder(plan, caps=(None, 20))      # not a multiple of 8


def test_ladder_save_restore_roundtrip(setup, tmp_path):
    spec, params, state, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, 32, 16))
    d = str(tmp_path / "plan")
    SV.save_ladder(ladder, d)
    restored = SV.load_ladder(d)
    assert restored.caps == ladder.caps
    for t0, t1 in zip(ladder.tiers, restored.tiers):
        assert t0.name == t1.name and t0.bands == t1.bands
        np.testing.assert_array_equal(
            np.asarray(PL.apply_compiled(t0.compiled, coef)),
            np.asarray(PL.apply_compiled(t1.compiled, coef)))


def test_stale_ladder_manifest_rejected(setup, tmp_path):
    """A ladder manifest saved against a different plan must not silently
    serve different math."""
    spec, params, state, coef, plan = setup
    d = str(tmp_path / "plan")
    SV.save_ladder(SV.build_ladder(plan, caps=(None, 32)), d)
    other = PL.build_plan(params, state, spec, dispatch=plan.cfg, bands=24)
    with pytest.raises(ValueError, match="stale"):
        SV.load_ladder(d, plan=other)


# --------------------------------------------------------------------------
# Scheduler
# --------------------------------------------------------------------------


def _sched(plan, coef, **kw):
    ladder = kw.pop("ladder", None) or SV.build_ladder(plan,
                                                       caps=(None, 16))
    kw.setdefault("batch", 2)
    kw.setdefault("grid", tuple(coef.shape[1:3]))
    kw.setdefault("channels", int(coef.shape[3]))
    return SV.BandElasticScheduler(ladder, **kw)


def test_scheduler_results_match_compiled_plan(setup):
    spec, params, state, coef, plan = setup
    # a watermark the burst can't reach pins the selector at the top tier
    # (this test is about result parity, not the QoS policy)
    calm = QosPolicy(high_depth=1e9, low_depth=0.5)
    with _sched(plan, coef, policy=calm) as s:
        # the runtime serves the band-elastic (transform-domain GEMM)
        # executor off-TPU — compare against the same lowering
        want = np.asarray(PL.apply_compiled(PL.compile_plan(plan), coef,
                                            executor=s.executor))
        reqs = [s.submit(np.asarray(coef[i]))
                for i in range(coef.shape[0])]
        got = np.stack([r.result(timeout=60) for r in reqs])
    # single-tier pressure never builds with batch 2 and 6 requests
    # submitted inline — everything should have served at the top tier
    assert all(r.tier == "top" for r in reqs)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # and the top tier's GEMM executor must keep top-1 parity with the
    # per-layer plan walk (the serve path's fidelity gate)
    walk = np.asarray(PL.apply_plan(plan, coef))
    assert (got.argmax(-1) == walk.argmax(-1)).all()


def test_scheduler_close_drains_pending(setup):
    spec, params, state, coef, plan = setup
    s = _sched(plan, coef)
    reqs = [s.submit(np.asarray(coef[i % coef.shape[0]]))
            for i in range(7)]
    s.close()  # drain=True: everything completes before the join
    assert all(r.done() for r in reqs)
    assert all(r.result() is not None for r in reqs)
    assert s.metrics.report()["requests"] == 7


def test_scheduler_close_without_drain_fails_pending(setup):
    spec, params, state, coef, plan = setup
    s = _sched(plan, coef)
    # stall the worker by submitting from a paused queue: grab the lock so
    # the worker cannot pop, enqueue, then close(drain=False)
    with s._lock:
        reqs = []
        for i in range(5):
            r = SV.ServeRequest(1000 + i, "coefficients",
                                np.asarray(coef[0]), None)
            s._queues["coefficients"].append(r)
            reqs.append(r)
        s._stop = True
        s._drain = False
        s._work.notify_all()
    s._worker.join(timeout=30)
    assert not s._worker.is_alive()
    for r in reqs:
        assert r.done()
        with pytest.raises(SV.SchedulerClosed):
            r.result()
    with pytest.raises(SV.SchedulerClosed):
        s.submit(np.asarray(coef[0]))


def test_scheduler_worker_exception_contained(setup):
    """A crash in the forward fails only its own batch — with
    RequestFailed carrying the cause — and the scheduler keeps serving
    (the PR-8 fault-isolation contract; the old behaviour poisoned the
    scheduler and every future submission)."""
    spec, params, state, coef, plan = setup
    # an always-failing executor would trip the breaker (by design);
    # this test is about containment, so hold the breaker wide open
    lenient = SV.BreakerPolicy(max_consecutive=10_000, min_samples=10_000)
    s = _sched(plan, coef, breaker=lenient, executor_retries=1)
    boom = RuntimeError("forward exploded")
    originals = {}
    for ex in {id(e): e for e in s._execs}.values():
        originals[id(ex)] = ex.coef_fn

    calls = []

    def bad_fn(_, **kw):
        calls.append(1)
        raise boom

    for ex in {id(e): e for e in s._execs}.values():
        ex.coef_fn = bad_fn
    r = s.submit(np.asarray(coef[0]))
    with pytest.raises(SV.RequestFailed) as ei:
        r.result(timeout=30)
    assert ei.value.stage == "executor"
    assert ei.value.__cause__ is boom
    # the bounded retry ran: original attempt + 1 retry
    assert len(calls) == 2
    # the scheduler survived: restore the executor and serve normally
    for ex in {id(e): e for e in s._execs}.values():
        ex.coef_fn = originals[id(ex)]
    r2 = s.submit(np.asarray(coef[0]))
    assert r2.result(timeout=60) is not None
    assert s.metrics.failures_total().get("executor", 0) >= 1
    assert s.health()["worker_alive"]
    s.close()  # no re-raise: the failure was contained, not fatal


def test_scheduler_admission_control(setup):
    """Over max_pending queued requests, submit() rejects (returns None)
    and the rejection lands in the metrics."""
    spec, params, state, coef, plan = setup
    s = _sched(plan, coef, max_pending=2)
    gate = threading.Event()
    for ex in {id(e): e for e in s._execs}.values():
        inner = ex.coef_fn

        def gated(c, _inner=inner, **kw):
            gate.wait(timeout=60)  # hold the worker mid-batch
            return _inner(c, **kw)

        ex.coef_fn = gated
    results = [s.submit(np.asarray(coef[0])) for _ in range(8)]
    accepted = [r for r in results if r is not None]
    n_rejected = results.count(None)
    # the worker can absorb at most one in-flight batch (2 slots) beyond
    # the 2-deep queue before admission control kicks in
    assert n_rejected >= 4
    gate.set()
    s.close()
    assert all(r.done() for r in accepted)
    assert s.metrics.report()["rejected"] == n_rejected


def test_scheduler_deadline_misses_recorded(setup):
    spec, params, state, coef, plan = setup
    with _sched(plan, coef) as s:
        # unwarmed: the first batch pays its jit compile, so a short
        # deadline is still live at dequeue but gone by completion — a
        # served-but-missed request
        r = s.submit(np.asarray(coef[0]), deadline_s=0.2)
        # while a request already expired when the worker sees it is shed
        # at dequeue with DeadlineExceeded, never burning a batch slot
        r2 = s.submit(np.asarray(coef[1]), deadline_s=-0.001)
        assert np.isfinite(r.result(timeout=60)).all()
        with pytest.raises(SV.DeadlineExceeded):
            r2.result(timeout=60)
        s.drain()
    rep = s.metrics.report()
    assert rep["deadline_misses"] >= 1
    assert rep["deadline_miss_rate"] > 0
    assert rep["deadline_shed"] == 1


def test_scheduler_sheds_expired_bytes_before_decode(setup):
    """An expired bytes request is shed at ingest dequeue — the codec is
    never invoked for it (the decode would be wasted work)."""
    from repro.codec import encode_pixels, ingest as ingestlib
    from repro.core import dct as dctlib

    spec, params, state, coef, plan = setup
    rng = np.random.default_rng(1)
    qt = np.rint(dctlib.quantization_table(
        75, dc_is_mean=False)).astype(np.int64)
    data = encode_pixels(
        np.clip(rng.normal(0, 0.3, (3, 16, 16)), -1.0, 127.0 / 128.0),
        qtable=qt)
    calls = []
    orig = ingestlib.ingest_batch

    def spy(datas, **kw):
        calls.append(len(list(datas)))
        return orig(datas, **kw)

    with _sched(plan, coef) as s:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ingestlib, "ingest_batch", spy)
            r = s.submit(data, kind="bytes", deadline_s=-0.001)
            with pytest.raises(SV.DeadlineExceeded):
                r.result(timeout=60)
            s.drain()
    assert calls == []
    assert s.metrics.report()["deadline_shed"] == 1


def _jpeg_traffic(n, seed=0):
    from repro.codec import encode_pixels
    from repro.core import dct as dctlib

    rng = np.random.default_rng(seed)
    qt = np.rint(dctlib.quantization_table(
        75, dc_is_mean=False)).astype(np.int64)
    return [encode_pixels(
        np.clip(rng.normal(0, 0.3, (3, 16, 16)), -1.0, 127.0 / 128.0),
        qtable=qt) for _ in range(n)]


def test_scheduler_decodes_bytes_off_worker(setup):
    """Entropy decode never runs inline in the execute worker: every
    ingest_batch call lands on the dedicated ingest thread, and the
    worker only sees already-decoded coefficient batches."""
    from repro.codec import ingest as ingestlib

    spec, params, state, coef, plan = setup
    threads = []
    orig = ingestlib.ingest_batch

    def spy(datas, **kw):
        threads.append(threading.current_thread().name)
        return orig(datas, **kw)

    with _sched(plan, coef) as s:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(ingestlib, "ingest_batch", spy)
            reqs = [s.submit(d, kind="bytes")
                    for d in _jpeg_traffic(4)]
            reqs += [s.submit(np.asarray(coef[i % coef.shape[0]]))
                     for i in range(4)]
            outs = [r.result(timeout=60) for r in reqs]
    assert all(np.isfinite(o).all() for o in outs)
    assert threads and set(threads) == {"scheduler-ingest"}


def test_scheduler_ingest_wall_split_from_device_wall(setup):
    """The QoS tier EMA sees device wall only; host decode wall is
    reported separately (bytes-heavy traffic must not poison the
    selector with cost no band tier can reduce)."""
    spec, params, state, coef, plan = setup
    with _sched(plan, coef) as s:
        observed = []
        orig = s.selector.observe
        s.selector.observe = (
            lambda t, w, **kw: (observed.append(w), orig(t, w, **kw))[1])
        for d in _jpeg_traffic(6, seed=2):
            s.submit(d, kind="bytes")
        s.drain()
    rep = s.metrics.report()
    assert rep["ingest_wall_s"] > 0
    assert rep["device_wall_s"] > 0
    assert rep["ingest"]["wall_s"] == rep["ingest_wall_s"]
    # every observation fed to the EMA is a device wall: they sum to the
    # reported device total, none contains the decode wall
    assert observed and abs(sum(observed) - rep["device_wall_s"]) < 1e-6


def test_scheduler_mixed_ingest_queues(setup):
    """bytes and coefficients requests interleave; batches stay
    kind-homogeneous and every request completes with sane logits."""
    from repro.codec import encode_pixels
    from repro.core import dct as dctlib

    spec, params, state, coef, plan = setup
    rng = np.random.default_rng(0)
    qt = np.rint(dctlib.quantization_table(
        75, dc_is_mean=False)).astype(np.int64)
    datas = [encode_pixels(
        np.clip(rng.normal(0, 0.3, (3, 16, 16)), -1.0, 127.0 / 128.0),
        qtable=qt) for _ in range(3)]
    with _sched(plan, coef) as s:
        reqs = []
        for i in range(3):
            reqs.append(s.submit(np.asarray(coef[i])))
            reqs.append(s.submit(datas[i], kind="bytes"))
        outs = [r.result(timeout=60) for r in reqs]
    assert all(np.isfinite(o).all() for o in outs)
    assert all(o.shape == (spec.num_classes,) for o in outs)
    assert {r.kind for r in reqs} == {"coefficients", "bytes"}


def test_scheduler_overload_degrades_then_serves_everything(setup):
    """A saturating burst forces tier degradation (switch events with
    queue-depth reasons); every request still completes."""
    spec, params, state, coef, plan = setup
    ladder = SV.build_ladder(plan, caps=(None, 32, 16))
    policy = QosPolicy(high_depth=1.5, low_depth=0.5, hysteresis=1)
    with _sched(plan, coef, ladder=ladder, batch=2,
                policy=policy, max_pending=64) as s:
        reqs = [s.submit(np.asarray(coef[i % coef.shape[0]]))
                for i in range(24)]
        s.drain(timeout=120)
    assert all(r is not None and r.done() for r in reqs)
    switches = s.metrics.tier_switches
    assert switches, "overload burst must trigger tier degradation"
    assert any("queue depth" in sw["reason"] for sw in switches)
    assert len({r.tier for r in reqs}) > 1


# --------------------------------------------------------------------------
# QoS policy (deterministic unit tests — no threads, no clocks)
# --------------------------------------------------------------------------


def test_selector_degrades_with_hysteresis():
    events = []
    sel = TierSelector(3, QosPolicy(high_depth=2.0, hysteresis=2),
                       on_switch=lambda *a: events.append(a))
    assert sel.select(pending=32, batch=4) == 0  # 1st overload signal
    assert sel.select(pending=32, batch=4) == 1  # 2nd -> degrade
    assert sel.select(pending=32, batch=4) == 1
    assert sel.select(pending=32, batch=4) == 2  # bottoms out
    assert sel.select(pending=32, batch=4) == 2  # stays at the floor
    assert len(events) == 2
    assert events[0][1:3] == ("0", "1")


def test_selector_recovers_on_drain_with_hysteresis():
    sel = TierSelector(2, QosPolicy(high_depth=2.0, low_depth=0.5,
                                    hysteresis=2))
    sel.tier = 1
    assert sel.select(pending=1, batch=4) == 1   # 1st drained signal
    assert sel.select(pending=1, batch=4) == 0   # 2nd -> recover
    assert sel.select(pending=1, batch=4) == 0   # already at top


def test_selector_hysteresis_resets_on_mixed_signals():
    sel = TierSelector(2, QosPolicy(high_depth=2.0, hysteresis=2))
    sel.select(pending=32, batch=4)              # overload x1
    sel.select(pending=4, batch=4)               # normal — resets streak
    assert sel.select(pending=32, batch=4) == 0  # overload x1 again
    assert sel.select(pending=32, batch=4) == 1


def test_selector_deadline_slack_triggers_degradation():
    sel = TierSelector(2, QosPolicy(hysteresis=1))
    sel.observe(0, batch_wall_s=0.5)  # tier 0 takes ~500ms per batch
    # queue is short, but the head cannot make its 100ms deadline
    assert sel.select(pending=2, batch=4, head_slack_s=0.1) == 1


def test_selector_recovery_respects_deadline_margin():
    sel = TierSelector(2, QosPolicy(hysteresis=1, recover_margin=1.5))
    sel.tier = 1
    sel.observe(0, batch_wall_s=0.5)
    sel.observe(1, batch_wall_s=0.05)
    # drained queue, but climbing back would blow the head deadline
    assert sel.select(pending=1, batch=4, head_slack_s=0.2) == 1
    # with slack, recovery proceeds
    assert sel.select(pending=1, batch=4, head_slack_s=5.0) == 0


def test_metrics_percentiles_shape():
    rep = SV.percentiles([0.010, 0.020, 0.030, 0.100])
    assert rep["n"] == 4
    assert rep["p50_ms"] == pytest.approx(25.0, abs=1.0)
    assert rep["p99_ms"] <= rep["max_ms"] == pytest.approx(100.0)
    assert SV.percentiles([]) == {"n": 0}


def test_qos_serve_raises_when_executor_fails(tmp_path, monkeypatch):
    """Outside --chaos, a request the executor fails makes the QoS serve
    raise (non-zero exit) after its report; images_per_s counts only
    completed images."""
    import json

    from repro.launch import serve as S

    def broken(self, *a, **k):
        raise RuntimeError("injected executor fault")

    monkeypatch.setattr(SV.BandElasticScheduler, "_execute", broken)
    report = tmp_path / "report.json"
    args = S.build_parser().parse_args(
        ["--arch", "jpeg-resnet", "--reduced", "--qos", "--batch", "2",
         "--requests", "2", "--tiers", "auto", "--batch-buckets", "fixed",
         "--plan-dir", str(tmp_path / "plan"), "--report-out",
         str(report)])
    with pytest.raises(RuntimeError, match="failed in the service"):
        S.serve_jpeg_resnet(args)
    out = json.loads(report.read_text())
    assert out["completed"] == 0 and out["images_per_s"] == 0.0


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    """Entry points keep JAX's compile cache where JAX_COMPILATION_CACHE_DIR
    says (setting no directory themselves), else at the checkout's fixed
    ``.jax_cache/``, and lower JAX's persist threshold unless
    JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS is set; importing the
    module sets nothing."""
    from pathlib import Path

    from repro.launch import compile_cache as CC

    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    root = Path(__file__).resolve().parents[1]
    try:
        assert CC.enable_compile_cache() == "/cache/from/env"
        assert jax.config.jax_compilation_cache_dir == before
        # grid cells compiling in under JAX's default 1 s are persisted
        assert jax.config.jax_persistent_cache_min_compile_time_secs \
            == CC.MIN_COMPILE_SECS < 1.0
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.5)
        monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                           "2.5")
        assert CC.enable_compile_cache() == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(root / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 2.5
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          min_secs)
