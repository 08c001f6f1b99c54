"""The load generator: a closed loop of clients, driven by data.

It drives ``BandElasticScheduler.submit`` from the client's side and
times each request itself on the host's monotonic clock, from when it
was sent until its logits are on the host.  Each run has a warm period
of the cell's own traffic, which set-up pays, and then the measured
window; the profiler's ``bench.window`` annotation, when tracing, spans
exactly that window.
"""
from __future__ import annotations

import contextlib
import time

clock = time.monotonic

#: how long a request sent in the window may take to come back before it
#: counts as never answered
DRAIN_S = 60.0


class Record:
    """One request as the client saw it."""

    __slots__ = ("item", "t_sent", "t_done", "req", "error")

    def __init__(self, item: int):
        self.item = item
        self.t_sent = None
        self.t_done = None
        self.req = None
        self.error = None


#: the profiler starts this long before the window opens
TRACE_LEAD_S = 0.5


class Window:
    """The measured interval ``[t0, t1)``.  While it is open, JAX's
    compile events are counted into ``counter``.  With a ``trace_dir``
    the profiler starts :data:`TRACE_LEAD_S` before it (:meth:`prepare`)
    and the annotation ``bench.window`` spans it; ``opened`` is the host
    clock when the annotation began."""

    def __init__(self, start: float, seconds: float, counter: dict,
                 trace_dir: str | None, trace_options=None):
        self.t0 = start
        self.t1 = start + seconds
        self.opened = None
        self._counter = counter
        self._trace_dir = trace_dir
        self._trace_options = trace_options
        self._tracing = False
        self._ctx = None

    def prepare(self) -> None:
        if self._trace_dir is not None and not self._tracing:
            import jax

            jax.profiler.start_trace(self._trace_dir,
                                     profiler_options=self._trace_options)
            self._tracing = True

    def stop_trace(self) -> None:
        if self._tracing:
            import jax

            jax.profiler.stop_trace()
            self._tracing = False

    def enter(self) -> None:
        self.prepare()
        self._counter["armed"] = True
        self.opened = clock()
        if self._trace_dir is not None:
            import jax

            self._ctx = jax.profiler.TraceAnnotation("bench.window")
            self._ctx.__enter__()

    def annotation(self, name: str):
        """A profiler annotation while tracing, else nothing."""
        if self._tracing:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    def exit(self) -> None:
        self._counter["armed"] = False
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None


def _send(sched, kind: str, payloads, rec: Record) -> None:
    rec.t_sent = clock()
    try:
        rec.req = sched.submit(payloads[rec.item], kind=kind)
        if rec.req is None:
            rec.error = "rejected by admission control"
    except Exception as e:  # a refused request is a missing answer
        rec.error = f"{type(e).__name__}: {e}"


def closed_loop(sched, kind: str, payloads, *, clients: int,
                warm_s: float, seconds: float, order, counter: dict,
                trace_dir: str | None = None, trace_options=None
                ) -> tuple[list[Record], Window]:
    """``clients`` callers, each sending its next request as soon as its
    previous one is answered, from one thread.  ``order`` maps the n-th
    request sent to its payload index."""
    records: list[Record] = []
    outstanding: list[Record] = []

    def send() -> None:
        rec = Record(int(order(len(records))))
        records.append(rec)
        _send(sched, kind, payloads, rec)
        if rec.req is not None:
            outstanding.append(rec)

    start = clock()
    win = Window(start + warm_s, seconds, counter, trace_dir, trace_options)
    lead = win.t0 - TRACE_LEAD_S
    for _ in range(clients):
        send()
    inside = False
    while outstanding:
        now = clock()
        if lead <= now < win.t0:
            win.prepare()
        if not inside and win.t0 <= now < win.t1:
            win.enter()
            inside = True
        elif inside and now >= win.t1:
            win.exit()
            inside = False
        boundary = (lead if now < lead else win.t0 if now < win.t0
                    else win.t1 if now < win.t1 else win.t1 + DRAIN_S)
        head = outstanding[0]
        try:
            head.req.result(timeout=max(0.0, boundary - now))
        except TimeoutError:
            if clock() >= win.t1 + DRAIN_S:
                for rec in outstanding:
                    rec.error = "no answer within the drain limit"
                break
            continue
        except Exception:
            pass
        now = clock()
        still = []
        for rec in outstanding:
            if rec.req.done():
                rec.t_done = now
                err = rec.req.error()
                if err is not None:
                    rec.error = f"{type(err).__name__}: {err}"
            else:
                still.append(rec)
        n_done = len(outstanding) - len(still)
        outstanding[:] = still
        if now < win.t1:
            with win.annotation("bench.submit"):
                for _ in range(n_done):
                    send()
    win.exit()
    return records, win


@contextlib.contextmanager
def counting_compiles():
    """Counts JAX's trace, lowering and compile events while
    ``counter["armed"]`` is set (a :class:`Window` is open)."""
    import jax.monitoring as mon

    counter = {"armed": False, "events": 0, "names": []}

    def listener(event: str, duration: float, **kw) -> None:
        if counter["armed"] and ("compile" in event or "trace" in event):
            counter["events"] += 1
            counter["names"].append(event)

    mon.register_event_duration_secs_listener(listener)
    try:
        yield counter
    finally:
        mon.unregister_event_duration_listener(listener)
