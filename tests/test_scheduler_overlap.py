"""Two batches in flight (``serving.scheduler``).

Contracts:

* **overlap** — with a full batch queued, the worker launches batch N+1
  before it reads batch N; answers equal a serial run's and complete in
  FIFO order; a partial batch never launches behind a running one;
* **counters** — the ``device/launch`` span's ``behind`` and the
  report's ``overlap_share`` read 1 behind a full queue and 0 at a
  trickle; the QoS selector and ``record_batch`` get each batch's own
  time, not the time it waited behind the batch before it;
* **failures stay per batch** — a fault at the launch or the read of
  batch N retries or fails N alone and answers N+1; a non-draining close
  and a worker crash fail both batches in flight; ``drain`` and a
  draining close answer both.

The device here is a queue in front of the real grid cells: each launch
runs ``d`` seconds after the batch before it finishes, and its logits
read as ready only then, so the ordering is deterministic whatever the
CPU's speed.
"""
import statistics
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
from repro.serving.faults import InjectedFault

D = 0.05  # fake device time per batch, seconds


@pytest.fixture(scope="module")
def setup():
    spec = R.ResNetSpec(widths=(6, 8), num_classes=10)
    params, state = R.init_resnet(jax.random.PRNGKey(0), spec)
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 3, 16, 16)) * 0.5
    coef = np.asarray(jnp.moveaxis(
        J.jpeg_encode(x, quality=spec.quality, scaled=True), 1, 3))
    plan = PL.build_plan(params, state, spec,
                         dispatch=DSP.DispatchConfig(path="reference"))
    return coef, SV.build_ladder(plan, caps=(None,))


class _Out:
    """A launched batch's logits: ready ``ready_at``; reading waits."""

    def __init__(self, dev, k, out, ready_at):
        self.dev, self.k, self.out, self.ready_at = dev, k, out, ready_at

    def is_ready(self):
        return time.monotonic() >= self.ready_at

    def __array__(self, dtype=None, copy=None):
        self.dev.log.append(("read", self.k))
        err = self.dev.fail_reads.pop(self.k, None)
        time.sleep(max(0.0, self.ready_at - time.monotonic()))
        if err is not None:
            raise err
        return np.asarray(self.out, dtype)


class Device:
    """Wraps every grid column's ``coef_fn``: the ``k``-th launch (from
    0) is logged, may wait on ``gates[k]``, and runs ``d`` seconds after
    the device finishes what was launched before it; ``fail_reads[k]``
    is raised by that launch's first read."""

    def __init__(self, sched, d=D):
        self.d = d
        self.busy_until = 0.0
        self.log: list[tuple[str, int]] = []
        self.gates: dict[int, tuple[threading.Event, threading.Event]] = {}
        self.fail_reads: dict[int, BaseException] = {}
        for ex in {id(e): e for e in sched._execs}.values():
            ex.coef_fn = (lambda rows, _inner=ex.coef_fn, **kw:
                          self._launch(_inner, rows, **kw))

    def gate(self, k):
        """Hold launch ``k``: returns (reached, release) events."""
        self.gates[k] = (threading.Event(), threading.Event())
        return self.gates[k]

    def _launch(self, inner, rows, **kw):
        k = sum(1 for e in self.log if e[0] == "launch")
        self.log.append(("launch", k))
        if k in self.gates:
            reached, release = self.gates[k]
            reached.set()
            assert release.wait(timeout=30)
        out = inner(rows, **kw)
        self.busy_until = max(time.monotonic(), self.busy_until) + self.d
        return _Out(self, k, out, self.busy_until)


def _sched(ladder, coef, **kw):
    kw.setdefault("batch", 2)
    kw.setdefault("buckets", (kw["batch"],))
    kw.setdefault("grid", tuple(coef.shape[1:3]))
    kw.setdefault("channels", int(coef.shape[3]))
    kw.setdefault("breaker", SV.BreakerPolicy(max_consecutive=10_000,
                                              min_samples=10_000))
    s = SV.BandElasticScheduler(ladder, **kw)
    s.warmup(kinds=("coefficients",))
    return s, Device(s)


def _enqueue(s, payloads):
    """Queue requests all at once, so the worker's next look sees every
    one (``submit`` one by one could let it take the first alone)."""
    with s._lock:
        reqs = [SV.ServeRequest(next(s._rid), "coefficients", p, None)
                for p in payloads]
        s._queues["coefficients"].extend(reqs)
        s._work.notify_all()
    return reqs


def _launches_and_reads(dev):
    return [f"{'L' if kind == 'launch' else 'R'}{k}" for kind, k in dev.log]


def _serial(s, coef, pairs):
    """Each pair of rows as its own batch, one batch at a time."""
    out = []
    for i in pairs:
        reqs = _enqueue(s, [coef[i], coef[i + 1]])
        out += [r.result(timeout=60) for r in reqs]
    return np.stack(out)


class _Calls:
    """``faults=`` hook recording ``on_execute``; raises ``raise_at[seq]``
    on every attempt of that dispatch."""

    def __init__(self, raise_at=None):
        self.seqs: list[int] = []
        self.raise_at = raise_at or {}

    def on_ingest(self, reqs):
        pass

    def on_execute(self, seq, reqs):
        self.seqs.append(seq)
        if seq in self.raise_at:
            raise self.raise_at[seq]


class _Die(BaseException):
    """Worker-killing poison: not an Exception, so no retry."""


# --------------------------------------------------------------------------
# overlap
# --------------------------------------------------------------------------


def test_full_queue_launches_next_batch_before_reading(setup):
    coef, ladder = setup
    s, dev = _sched(ladder, coef)
    with s:
        reqs = _enqueue(s, list(coef[:8]))
        got = np.stack([r.result(timeout=60) for r in reqs])
        s.drain(timeout=60)
        assert _launches_and_reads(dev) == ["L0", "L1", "R0", "L2", "R1",
                                            "L3", "R2", "R3"]
        # FIFO: each request completes no earlier than the one before it
        done = [r.submitted + r.latency_s for r in reqs]
        assert done == sorted(done)
        want = _serial(s, coef, range(0, 8, 2))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("traffic", ["full", "trickle"])
def test_behind_and_overlap_share(setup, traffic):
    coef, ladder = setup
    tracer = SV.Tracer()
    s, dev = _sched(ladder, coef, tracer=tracer)
    with s:
        if traffic == "full":
            reqs = _enqueue(s, list(coef[:10]))
            [r.result(timeout=60) for r in reqs]
        else:
            for i in range(3):
                _enqueue(s, [coef[i], coef[i + 1]])[1].result(timeout=60)
        s.drain(timeout=60)
    launches = [e for e in tracer.events()
                if e[0] == "X" and (e[1], e[3]) == ("device", "launch")]
    behind = [e[6]["behind"] for e in launches]
    share = s.metrics.report()["overlap_share"]
    rows = [e[2] for e in launches]
    if traffic == "full":
        # every batch but the first launched behind the one before it,
        # on the trace row that batch left free
        assert behind == [0, 1, 1, 1, 1]
        assert rows == [0, 1, 0, 1, 0]
        assert share == pytest.approx(0.8)
    else:
        assert behind == [0, 0, 0]
        assert rows == [0, 0, 0]
        assert share == 0.0


@pytest.mark.parametrize("case", ["partial", "fills", "shed"])
def test_partial_batch_never_launches_behind(setup, case):
    """Batch 2.  ``partial``: a third request waits for the running batch
    to finish; ``fills``: a fourth arrives while batch 0 runs, so a full
    batch launches behind it; ``shed``: a full batch is queued but one of
    its requests has expired, so what is left waits to run alone."""
    coef, ladder = setup
    s, dev = _sched(ladder, coef, tracer=SV.Tracer())
    with s:
        if case == "shed":
            reqs = _enqueue(s, list(coef[:2]))
            with s._lock:
                late = SV.ServeRequest(next(s._rid), "coefficients",
                                       coef[2], time.monotonic() - 1.0)
                s._queues["coefficients"].append(late)
            reqs += _enqueue(s, [coef[3]])
        else:
            reqs = _enqueue(s, list(coef[:3]))
        if case == "fills":
            time.sleep(D / 4)  # batch 0 is on the device
            reqs += _enqueue(s, [coef[3]])
        [r.result(timeout=60) for r in reqs]
        s.drain(timeout=60)
    if case == "fills":
        assert _launches_and_reads(dev) == ["L0", "L1", "R0", "R1"]
    else:
        assert _launches_and_reads(dev) == ["L0", "R0", "L1", "R1"]
    if case == "shed":
        with pytest.raises(SV.DeadlineExceeded):
            late.result(timeout=0)
    launches = [e[6] for e in s.tracer.events()
                if e[0] == "X" and (e[1], e[3]) == ("device", "launch")]
    stages = [e[6]["n"] for e in s.tracer.events()
              if e[0] == "X" and (e[1], e[3]) == ("device", "pad/stage")]
    assert [a["behind"] for a in launches] == [0, int(case == "fills")]
    assert stages == ([2, 2] if case == "fills" else [2, 1])


# --------------------------------------------------------------------------
# failures and lifecycle
# --------------------------------------------------------------------------


@pytest.mark.parametrize("where,retries,calls", [
    ("launch", 1, [0, 1, 1, 2]),   # every attempt of dispatch 1 raises
    ("read", 1, [0, 1, 2, 1]),     # the read fails once: 1 launches again
    ("read", 0, [0, 1, 2]),        # no budget: dispatch 1 fails
])
def test_fault_in_one_batch_spares_the_batch_behind(setup, where, retries,
                                                    calls):
    coef, ladder = setup
    boom = InjectedFault("injected")
    hook = _Calls(raise_at={1: boom} if where == "launch" else None)
    s, dev = _sched(ladder, coef, faults=hook, executor_retries=retries)
    if where == "read":
        dev.fail_reads[1] = boom
    with s:
        reqs = _enqueue(s, list(coef[:6]))
        for r in reqs:
            r._event.wait(timeout=60)
        want = _serial(s, coef, range(0, 6, 2))
        assert hook.seqs[:len(calls)] == calls
    doomed = where == "launch" or retries == 0
    for i, r in enumerate(reqs):
        if doomed and i in (2, 3):
            with pytest.raises(SV.RequestFailed) as ei:
                r.result()
            assert ei.value.stage == "executor"
            assert ei.value.__cause__ is boom
        else:
            np.testing.assert_array_equal(r.result(), want[i])
    assert s.metrics.failures_total().get("executor", 0) == 2 * doomed


def test_draining_close_answers_both_batches(setup):
    coef, ladder = setup
    s, dev = _sched(ladder, coef)
    reqs = _enqueue(s, list(coef[:4]))
    s.close()
    assert _launches_and_reads(dev) == ["L0", "L1", "R0", "R1"]
    assert all(np.isfinite(r.result(timeout=0)).all() for r in reqs)


def test_non_draining_close_fails_both_batches(setup):
    """Close arrives while batch 1 launches behind batch 0: both fail."""
    coef, ladder = setup
    s, dev = _sched(ladder, coef)
    reached, release = dev.gate(1)
    reqs = _enqueue(s, list(coef[:4]))
    assert reached.wait(timeout=30)
    closer = threading.Thread(target=s.close, kwargs={"drain": False})
    closer.start()
    deadline = time.monotonic() + 30
    while not s._stop and time.monotonic() < deadline:
        time.sleep(0.001)
    release.set()
    closer.join(timeout=30)
    assert not closer.is_alive() and not s._worker.is_alive()
    for r in reqs:
        with pytest.raises(SV.SchedulerClosed):
            r.result(timeout=0)
    assert _launches_and_reads(dev) == ["L0", "L1"]


@pytest.mark.parametrize("where", ["launch", "read"])
def test_worker_crash_fails_both_batches(setup, where):
    coef, ladder = setup
    die = _Die("worker killed")
    hook = _Calls(raise_at={1: die} if where == "launch" else None)
    s, dev = _sched(ladder, coef, faults=hook)
    if where == "read":
        dev.fail_reads[0] = die   # batch 1 is already in flight
    reqs = _enqueue(s, list(coef[:4]))
    for r in reqs:
        with pytest.raises(_Die):
            r.result(timeout=30)
    with pytest.raises(_Die):
        s.close()
    assert not s._worker.is_alive()


def test_drain_returns_after_both_batches(setup):
    coef, ladder = setup
    s, dev = _sched(ladder, coef)
    reached, release = dev.gate(1)
    reqs = _enqueue(s, list(coef[:4]))
    assert reached.wait(timeout=30)
    drained = []
    waiter = threading.Thread(
        target=lambda: drained.append(s.drain(timeout=30)))
    waiter.start()
    time.sleep(0.05)
    # both batches left the queue and count as in flight
    assert s.health()["in_flight"] == 4 and s.pending == 0
    assert waiter.is_alive() and not drained
    release.set()
    waiter.join(timeout=30)
    assert drained == [True]
    assert all(r.done() and r.error() is None for r in reqs)
    s.close()


# --------------------------------------------------------------------------
# the QoS wall
# --------------------------------------------------------------------------


def test_qos_wall_is_each_batch_own_time(setup):
    """Five batches behind a full queue, ``d`` of device time each: the
    selector and ``record_batch`` see about ``d`` a batch, not the ``2d``
    from a batch's stack to its read when it waits behind another."""
    coef, ladder = setup
    d = 0.2
    s, dev = _sched(ladder, coef)
    dev.d = d
    walls = []
    observe = s.selector.observe

    def record(tier, wall, **kw):
        walls.append(wall)
        observe(tier, wall, **kw)

    s.selector.observe = record
    with s:
        reqs = _enqueue(s, list(coef[:10]))
        [r.result(timeout=60) for r in reqs]
        s.drain(timeout=60)
    assert len(walls) == 5
    assert all(0.5 * d < w < 1.5 * d for w in walls), walls
    assert statistics.median(walls) == pytest.approx(d, rel=0.2)
    rep = s.metrics.report()
    assert rep["device_wall_s"] == pytest.approx(sum(walls), abs=1e-5)
    assert rep["per_tier"]["top"]["wall_s"] == pytest.approx(sum(walls),
                                                            abs=1e-5)
    assert rep["overlap_share"] == pytest.approx(0.8)
