"""Tracing — named spans bridging to jax.profiler.

Rebuild of the reference's tracepoint layer (ref: src/tracing/*.tp
LTTng tracepoints + src/common/tracer.cc Jaeger/OpenTelemetry spans,
compiled in behind WITH_LTTNG/WITH_JAEGER and cheap no-ops otherwise).
Here the trace sink is the XLA profiler: a `span("name")` shows up in
a jax.profiler trace (TensorBoard / xprof) alongside the device
timeline, which is the TPU-native way to answer "which host stage
stalled the launch pipeline" — the question LTTng answers for the
reference's op path.

"Tracing on" is "a profiler session is live"
(`TraceAnnotation.is_enabled()`): there is no other switch. While one
is, a span that ends has two sinks — the profiler's timeline (device
clock: idle gaps get stage names) and the process-wide span log below
(`time.perf_counter`: per-op stage times, with each span's SELF time,
its duration minus what its child spans on the same thread covered).
While none is, a span is a few attribute stores and touches neither.
Spans also time into an optional PerfCounters time_avg key, so
production counters and profiler traces come from the SAME
instrumentation points (the reference does this double-duty with
OpTracker + tracepoints). Compiles are logged always, by one
jax.monitoring listener: they are rare, and one in an untraced stretch
must still count.

Usage:
    with span("recovery.launch"):
        ...
    with span("osd.op", counters=perf, key="op_latency"):
        ...
    start_trace("/tmp/trace")   # capture; view in tensorboard/xprof
    ...
    stop_trace()                # -> the capture's stage table
"""

from __future__ import annotations

import collections
import contextlib
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from . import flight_recorder as _fr
from . import profiler as _prof

_enabled = TraceAnnotation.is_enabled
_now = time.perf_counter

#: finished spans of the live capture(s) plus every `xla.compile`.
#: Appends are GIL-atomic; readers copy (`span_log`).
_LOG: collections.deque = collections.deque(maxlen=65536)
#: records the full log has pushed out at its old end, since the process
#: began (`span_log_dropped`): a log that wrapped is seen, not read
_dropped = [0]
_drop_lock = threading.Lock()
_tls = threading.local()             # .stack: this thread's open spans


def _log(name: str, start: float, dur: float, self_s: float,
         trace_id: int | None = None, nbytes: int | None = None,
         **more) -> None:
    """One record: start and dur in perf_counter seconds."""
    if len(_LOG) == getattr(_LOG, "maxlen", None):
        with _drop_lock:
            _dropped[0] += 1
    _LOG.append({"name": name, "start": start, "dur": dur, "self": self_s,
                 "trace_id": trace_id, "nbytes": nbytes, **more})


class span:
    """Named span: visible in jax.profiler traces and, while a capture
    is live, logged with its self time; optionally tincs
    `counters[key]` (a time_avg) with the wall duration; when a
    SAMPLED trace context is active (utils/flight_recorder) — recorded
    into the executing daemon's flight ring under that trace; and —
    when the r19 CPU sampler is on — tags this thread with the span's
    attribution category so wall-clock samples land in the same
    queue/crypto/encode/store buckets the trace critical-path uses.
    One instrumentation point, so none of its consumers can drift from
    the others."""

    __slots__ = ("name", "counters", "key", "nbytes", "_t0", "_ann",
                 "_flight", "_tagged", "_covered")

    def __init__(self, name: str, counters=None, key: str | None = None,
                 nbytes: int | None = None):
        self.name, self.counters, self.key = name, counters, key
        self.nbytes = nbytes

    def __enter__(self):
        self._flight = None
        if _fr.current_sampled() is not None:
            tags = {} if self.nbytes is None else {"nbytes": self.nbytes}
            self._flight = _fr.trace_span(self.name, **tags)
            self._flight.__enter__()
        self._tagged = _prof.push_span(self.name)
        self._ann = None
        if _enabled():
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
            self._covered = 0.0      # seconds under child spans
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append(self)
        self._t0 = _now()
        return self

    def __exit__(self, *exc):
        # record even when the body raises — failing/slow-error ops are
        # exactly the ones worth timing (PerfCounters.time() semantics)
        dur = _now() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
            stack = _tls.stack
            stack.pop()
            if stack:
                stack[-1]._covered += dur
            ctx = _fr.current()
            _log(self.name, self._t0, dur, dur - self._covered,
                 ctx.trace_id if ctx else None, self.nbytes)
        if self._tagged:
            _prof.pop_span()
        if self._flight is not None:
            self._flight.__exit__(None, None, None)
        if self.counters is not None and self.key is not None:
            self.counters.tinc(self.key, dur)
        return False


class locked:
    """`with locked(lock, "osd.pg_lock.wait"):` — `lock` held for the
    body, the wait for it a span of its own."""

    __slots__ = ("_lock", "_wait")

    def __init__(self, lock, wait_span: str):
        self._lock, self._wait = lock, wait_span

    def __enter__(self):
        with span(self._wait):
            self._lock.acquire()

    def __exit__(self, *exc):
        self._lock.release()
        return False


def record_wait(name: str, start: float, dur: float,
                trace_id: int | None = None) -> None:
    """Log a wait that is known only once it is over (a queue wait, a
    reply awaited): `start` on time.perf_counter. Log only, no profiler
    annotation — nothing ran."""
    if _enabled():
        _log(name, start, dur, dur, trace_id)


_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _on_compile(event: str, duration_secs: float, **kw) -> None:
    """Every jitted program of every layer passes here when it is
    compiled or loaded from the persistent cache; `program` is the
    jitted function's name."""
    if event != _COMPILE_EVENT:
        return
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1]._covered += duration_secs
    _log("xla.compile", _now() - duration_secs, duration_secs,
         duration_secs, program=kw.get("fun_name"))


jax.monitoring.register_event_duration_secs_listener(_on_compile)


def span_log(since: float | None = None,
             until: float | None = None) -> list[dict]:
    """The logged records that ENDED in [since, until] (perf_counter
    seconds; None = unbounded), oldest first."""
    return [r for r in list(_LOG)
            if (since is None or r["start"] + r["dur"] >= since)
            and (until is None or r["start"] + r["dur"] <= until)]


def span_log_dropped() -> int:
    """Records the log has dropped since the process began: it keeps the
    newest `_LOG.maxlen`, and a reader of a stretch takes the count
    before and after it."""
    return _dropped[0]


def stage_table(records, ops: int) -> dict[str, dict]:
    """Self time summed by span name: name -> {count, self_s,
    self_ms_per_op}. `ops` is the number of operations the records
    served; each row is that stage's busy or waiting time an op, over
    every daemon and thread that worked for it."""
    table: dict[str, dict] = {}
    for r in records:
        row = table.setdefault(r["name"], {"count": 0, "self_s": 0.0})
        row["count"] += 1
        row["self_s"] += r["self"]
    for row in table.values():
        row["self_ms_per_op"] = row["self_s"] / ops * 1e3 if ops else None
    return table


# [ProfilerSession, log_dir, t_start, records dropped before it]
_session: list = [None, None, 0.0, 0]


def start_trace(log_dir: str) -> bool:
    """Begin a jax.profiler capture (the 'enable tracing' admin-socket
    toggle). Returns False when the profiler is unavailable.

    Drives an XLA ProfilerSession directly with the PYTHON TRACER OFF
    when the binding allows: the per-python-call events of the default
    tracer flood the profiler's ~1M-event buffer within the first
    compile, silently dropping the very span/device events the trace
    is for. Falls back to the plain jax.profiler API otherwise."""
    try:
        jax.devices()                # backend init before the session
        t_start, sess = _now(), None
        try:
            from jax._src.lib import _profiler
        except ImportError:
            jax.profiler.start_trace(log_dir)
        else:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            sess = _profiler.ProfilerSession(opts)
        _session[:] = [sess, log_dir, t_start, span_log_dropped()]
        return True
    except Exception:
        return False


def stop_trace() -> dict | None:
    """End the capture and write it under its directory. Returns the
    capture's stage table (`stage_table` of what the log gained, an
    op being one `osd.op`) with `dropped`, the records the log lost
    since the capture began (not 0: the table is short of them), or
    None when no capture could be stopped."""
    sess, log_dir, t_start, dropped_before = _session
    _session[0] = None
    try:
        if sess is not None:
            sess.export(sess.stop(), str(log_dir))
        else:
            jax.profiler.stop_trace()
    except Exception:
        return None
    records = span_log(since=t_start)
    ops = sum(1 for r in records if r["name"] == "osd.op")
    return {"dir": log_dir, "ops": ops,
            "dropped": span_log_dropped() - dropped_before,
            "stages": stage_table(records, ops)}


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a whole block: `with trace("/tmp/tr"): run_workload()`."""
    ok = start_trace(log_dir)
    try:
        yield ok
    finally:
        if ok:
            stop_trace()
