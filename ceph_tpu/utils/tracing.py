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
its duration minus what its child spans on the same thread covered, and
beside it `cpu`, the thread's own CPU seconds (`time.thread_time`,
read at most once in `_CPU_TRUST_S` a thread) by
the same rule: a stage whose wall is several times its CPU was waiting,
for the interpreter or the OS, not working; `tid` and `parent`, the
enclosing span's name, are the keys a later join needs; a span's
`tags`, where it has any, ride its record too). A
`detail=True` span is logged and annotated like any other but takes
nothing from its parent's self or cpu: the parts of a stage beside the
whole. While a session is live one `trace-probe` thread also logs the
host's side of the ledger (`host.tick`, `host.usage`: below).
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
import os
import re
import resource
import sys
import threading
import time

import jax
from jax.profiler import TraceAnnotation

from . import flight_recorder as _fr
from . import profiler as _prof

_enabled = TraceAnnotation.is_enabled
_now = time.perf_counter
_cpu_now = time.thread_time
#: a thread's CPU clock is read at most once in this many seconds (one
#: reading is a syscall: 0.25 us on a plain kernel, 6 us on the chip
#: host's sandbox, where the messenger's spans last 12): a span boundary
#: this close after a reading takes the reading plus the wall time
#: since, as if the thread had run; so `cpu` is within this of the clock
_CPU_TRUST_S = 250e-6

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
         cpu: float | None = 0.0, parent: str | None = None,
         **more) -> None:
    """One record, by the thread it is about: start and dur in
    perf_counter seconds, cpu in that thread's CPU seconds."""
    if len(_LOG) == getattr(_LOG, "maxlen", None):
        with _drop_lock:
            _dropped[0] += 1
    _LOG.append({"name": name, "start": start, "dur": dur, "self": self_s,
                 "trace_id": trace_id, "nbytes": nbytes, "cpu": cpu,
                 "tid": threading.get_ident(), "parent": parent, **more})


def _cpu_at(t: float) -> float:
    """This thread's CPU seconds at `t` (perf_counter, now): the
    clock's, or within `_CPU_TRUST_S` of a reading that reading plus
    the time since; never less than what it last handed out."""
    last = getattr(_tls, "cpu", None)    # [t of the reading, reading, max]
    if last is None:
        last = _tls.cpu = [-1.0, 0.0, 0.0]
    if t - last[0] >= _CPU_TRUST_S:
        last[1] = _cpu_now()
        last[0] = t = _now()             # the reading's own time is no span's
    last[2] = cpu = max(last[1] + (t - last[0]), last[2])
    return cpu


class span:
    """Named span: visible in jax.profiler traces and, while a capture
    is live, logged with its self time and its thread's CPU time;
    optionally tincs `counters[key]` (a time_avg) with the wall
    duration; when a SAMPLED trace context is active
    (utils/flight_recorder) — recorded into the executing daemon's
    flight ring under that trace, with `tags` and `nbytes`; and —
    when the r19 CPU sampler is on — tags this thread with the span's
    attribution category so wall-clock samples land in the same
    queue/crypto/encode/store buckets the trace critical-path uses.
    One instrumentation point, so none of its consumers can drift from
    the others. `detail=True`: a part shown beside its parent's whole
    (the record says so and the parent's self and cpu keep it)."""

    __slots__ = ("name", "counters", "key", "nbytes", "tags", "detail",
                 "_t0", "_c0", "_ann", "_flight", "_tagged", "_covered",
                 "_cpu_covered")

    def __init__(self, name: str, counters=None, key: str | None = None,
                 nbytes: int | None = None, tags: dict | None = None,
                 detail: bool = False):
        self.name, self.counters, self.key = name, counters, key
        self.nbytes, self.tags, self.detail = nbytes, tags, detail

    def __enter__(self):
        self._flight = None
        if _fr.current_sampled() is not None and not self.detail:
            tags = dict(self.tags) if self.tags else {}
            if self.nbytes is not None:
                tags["nbytes"] = self.nbytes
            self._flight = _fr._trace_span(self.name, **tags)
            self._flight.__enter__()
        self._tagged = _prof.push_span(self.name)
        self._ann = None
        if _enabled():
            if _probe[0] is None:
                _start_probe()
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
            # seconds under child spans: wall, and this thread's CPU
            self._covered = self._cpu_covered = 0.0
            stack = getattr(_tls, "stack", None)
            if stack is None:
                stack = _tls.stack = []
            stack.append(self)
            self._c0 = _cpu_at(_now())
        self._t0 = _now()
        return self

    def __exit__(self, *exc):
        # record even when the body raises — failing/slow-error ops are
        # exactly the ones worth timing (PerfCounters.time() semantics)
        dur = _now() - self._t0
        if self._ann is not None:
            cpu = _cpu_at(self._t0 + dur) - self._c0
            self._ann.__exit__(*exc)
            stack = _tls.stack
            stack.pop()
            parent, more = (stack[-1] if stack else None), {}
            if self.tags:
                more["tags"] = self.tags
            if self.detail:
                more["detail"] = True
            elif parent is not None:
                parent._covered += dur
                parent._cpu_covered += cpu
            ctx = _fr.current()
            _log(self.name, self._t0, dur, dur - self._covered,
                 ctx.trace_id if ctx else None, self.nbytes,
                 cpu - self._cpu_covered,
                 parent.name if parent is not None else None, **more)
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
        _log(name, start, dur, dur, trace_id)    # cpu 0.0: nothing ran


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
         duration_secs, cpu=None, program=kw.get("fun_name"))


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
    """Self time and CPU time summed by span name: name -> {count,
    self_s, self_ms_per_op, cpu_s, cpu_ms_per_op}. `ops` is the number
    of operations the records served; `self` is that stage's busy or
    waiting time an op, over every daemon and thread that worked for
    it, `cpu` what its threads ran of it (a `detail` span's row is a
    part of its parent's, not beside it)."""
    table: dict[str, dict] = {}
    for r in records:
        row = table.setdefault(r["name"],
                               {"count": 0, "self_s": 0.0, "cpu_s": 0.0})
        row["count"] += 1
        row["self_s"] += r["self"]
        row["cpu_s"] += r.get("cpu") or 0.0
    for row in table.values():
        row["self_ms_per_op"] = row["self_s"] / ops * 1e3 if ops else None
        row["cpu_ms_per_op"] = row["cpu_s"] / ops * 1e3 if ops else None
    return table


# -- the host probe: one thread, exactly as long as a capture ----------------

#: seconds the probe sleeps between two ticks
_PROBE_TICK_S = 0.010
_probe: list = [None]                # the live `trace-probe` thread
_probe_lock = threading.Lock()
_DAEMON_TOKEN = re.compile(r"^[a-z]+\.\w+$")


def thread_role(name: str) -> str:
    """A thread's role from its name. The one convention: dash-joined
    tokens, of which the daemon's is the one with a dot (`osd.3`,
    `mon.0`, `client.0`); the role is the others, each less its
    trailing digits (`osd.3-shard0` -> `shard`, `msgr-osd.3-r0` ->
    `msgr-r`, `profiler-mon.1` -> `profiler`, `bench-loop-7` ->
    `bench-loop`)."""
    tokens = (t.rstrip("0123456789_") for t in name.split("-")
              if not _DAEMON_TOKEN.match(t))
    return "-".join(t for t in tokens if t) or name


def _usage() -> dict:
    """What the process has used so far, and each live Python thread's
    CPU seconds as [ident, name, seconds]."""
    ru = resource.getrusage(resource.RUSAGE_SELF)
    threads = []
    for t in threading.enumerate():
        try:
            threads.append([t.ident, t.name, time.clock_gettime(
                time.pthread_getcpuclockid(t.ident))])
        except (OSError, TypeError, ValueError):
            pass                     # gone since it was listed
    return {"process_s": time.process_time(), "user_s": ru.ru_utime,
            "system_s": ru.ru_stime, "minflt": ru.ru_minflt,
            "nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw,
            "cpus": len(os.sched_getaffinity(0)),
            "switch_interval_s": sys.getswitchinterval(),
            "threads": threads}


def _probe_run() -> None:
    """`host.usage` now and at the end; between them a `host.tick`
    every `_PROBE_TICK_S` whose `late` is how long past its sleep the
    thread got to run again: what a thread that becomes runnable pays
    to take the GIL back (plus the OS's wake-up). Ends itself at the
    first tick that finds no session live."""
    t = _now()
    _log("host.usage", t, 0.0, 0.0, **_usage())
    while True:
        time.sleep(_PROBE_TICK_S)
        woke = _now()
        if not _enabled():
            break
        _log("host.tick", woke, 0.0, 0.0,
             late=max(0.0, woke - t - _PROBE_TICK_S))
        t = _now()
    _log("host.usage", woke, 0.0, 0.0, **_usage())
    with _probe_lock:
        _probe[0] = None


def _start_probe() -> None:
    with _probe_lock:
        if _probe[0] is None:
            _probe[0] = threading.Thread(target=_probe_run, daemon=True,
                                         name="trace-probe")
            _probe[0].start()


def _join_probe() -> None:
    """Wait for a probe whose capture has ended to log its last record."""
    probe = _probe[0]
    if probe is not None and not _enabled():
        probe.join(1.0)


def host_usage(records) -> dict | None:
    """The host's ledger of the stretch between the first and the last
    `host.usage` record of `records`: cores busy, user against system
    seconds, minor faults, context switches, CPU by thread role (a
    thread gone at the end is left out) with the runtime's native
    threads as the remainder, and the ticks' `late`. None without such
    a pair."""
    marks = [r for r in records if r["name"] == "host.usage"]
    if len(marks) < 2 or marks[-1]["start"] <= marks[0]["start"]:
        return None
    first, last = marks[0], marks[-1]
    seconds = last["start"] - first["start"]
    cpu_s = last["process_s"] - first["process_s"]
    before = {(ident, name): s for ident, name, s in first["threads"]}
    by_role: dict[str, float] = {}
    for ident, name, s in last["threads"]:
        s0 = before.get((ident, name), 0.0)
        role = thread_role(name)     # a smaller reading: the ident reused
        by_role[role] = by_role.get(role, 0.0) + (s - s0 if s >= s0 else s)
    late = sorted(r["late"] for r in records if r["name"] == "host.tick"
                  and first["start"] <= r["start"] <= last["start"])
    return {"seconds": seconds, "cpu_s": cpu_s,
            "cores_busy": cpu_s / seconds,
            "user_s": last["user_s"] - first["user_s"],
            "system_s": last["system_s"] - first["system_s"],
            "minor_faults": last["minflt"] - first["minflt"],
            "voluntary_switches": last["nvcsw"] - first["nvcsw"],
            "involuntary_switches": last["nivcsw"] - first["nivcsw"],
            "cpus": last["cpus"],
            "switch_interval_s": last["switch_interval_s"],
            "ticks": len(late),
            "late_mean_ms": sum(late) / len(late) * 1e3 if late else None,
            "late_p95_ms": (late[-(-95 * len(late) // 100) - 1] * 1e3
                            if late else None),
            "cpu_s_by_role": dict(sorted(by_role.items(),
                                         key=lambda kv: -kv[1])),
            "native_cpu_s": cpu_s - sum(by_role.values())}


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
        _join_probe()                # one that a capture just left behind
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
        _start_probe()
        return True
    except Exception:
        return False


def stop_trace() -> dict | None:
    """End the capture and write it under its directory. Returns the
    capture's stage table (`stage_table` of what the log gained, an
    op being one `osd.op`) with `dropped`, the records the log lost
    since the capture began (not 0: the table is short of them), and
    `host`, the host's ledger of the same stretch (`host_usage`), or
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
    _join_probe()                    # its last `host.usage` is in
    records = span_log(since=t_start)
    ops = sum(1 for r in records if r["name"] == "osd.op")
    return {"dir": log_dir, "ops": ops,
            "dropped": span_log_dropped() - dropped_before,
            "stages": stage_table((r for r in records
                                   if not r["name"].startswith("host.")),
                                  ops),
            "host": host_usage(records)}


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a whole block: `with trace("/tmp/tr"): run_workload()`."""
    ok = start_trace(log_dir)
    try:
        yield ok
    finally:
        if ok:
            stop_trace()
