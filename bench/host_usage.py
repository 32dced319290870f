"""The host's side of the program's span log, as the `host.*` readers and
the `*_cpu_ms_per_op` readers take it.

While a profiler session is live the program (`ceph_tpu/utils/tracing.py`,
PR 36) puts on every span record `cpu`, its thread's own CPU seconds
(`time.thread_time`) less what its child spans on the same thread used,
the rule of `self`; and one `trace-probe` thread logs, every 10 ms, a
`host.tick` whose `late` is how many seconds past its sleep the thread
got to run again (what a thread that becomes runnable pays to take the
GIL back, plus the OS's wake-up), and at its first and last tick a
`host.usage`: `process_s` (`time.process_time`), `user_s`, `system_s`,
`minflt`, `nvcsw`, `nivcsw`, `cpus`, `switch_interval_s`, and `threads`,
`[ident, name, CPU seconds]` of every live Python thread. The difference
of the two `host.usage` records is the ledger of the traced seconds:
`usage(run)`.

A thread's role is its name less the daemon's (the dash-separated token
with a dot: `osd.3`, `mon.0`, `client.0`) and less each other token's
trailing digits: `osd.3-shard0` is a `shard`, `msgr-osd.3-r0` a `msgr-r`,
`profiler-mon.1` a `profiler`, `osd.3-hb` an `hb`, `bench-loop-7` a
`bench-loop`. `PLANE_ROLES` are the roles that run whether or not an op
is in flight.

A program without the log, records without `cpu`, or a log without a
`host.usage` pair or a `host.tick` has nothing to read: `None`.
"""

from __future__ import annotations

import re

from bench import span_stages

USAGE = "host.usage"
TICK = "host.tick"
#: the always-on planes: each daemon's CPU sampler (`utils/profiler.py`)
#: and its heartbeat loop, which also ships telemetry, netobs and the
#: MgrReports (`_heartbeat_loop`, `_mon_hb_loop`); no `mgr/` plane has a
#: ticker of its own
PLANE_ROLES = ("profiler", "hb")
_DAEMON_TOKEN = re.compile(r"^[a-z]+\.\w+$")


def role_of(thread_name: str) -> str:
    tokens = (t.rstrip("0123456789_") for t in thread_name.split("-")
              if not _DAEMON_TOKEN.match(t))
    return "-".join(t for t in tokens if t) or thread_name


def records(run: dict) -> list | None:
    """Every record of the log; None without a traced run."""
    module = span_stages.tracing()
    if module is None or not run.get("trace"):
        return None
    return module.span_log()


def cpu_ms_per_op(run: dict, names: tuple[str, ...]) -> float | None:
    """CPU time of the spans called `names`, for each client op that
    completed in the traced seconds: what their threads ran, where
    `span_stages.self_ms_per_op` is what they ran or waited."""
    found = records(run)
    if not found or not run.get("traced_ops"):
        return None
    cpu = [r["cpu"] for r in found
           if r["name"] in names and r.get("cpu") is not None]
    return sum(cpu) / run["traced_ops"] * 1e3 if cpu else None


def usage(run: dict) -> dict | None:
    """The last `host.usage` record less the first: `seconds` between
    them, `cpu_s` of the process (user + system), `minor_faults` (0 on
    a kernel that does not count them: the chip host's),
    `cpu_s_by_role` (a thread gone at the last record is left out; one
    born between them counts whole) and `native_cpu_s`, the remainder:
    the runtime's own threads."""
    marks = [r for r in records(run) or () if r["name"] == USAGE]
    if len(marks) < 2 or marks[-1]["start"] <= marks[0]["start"]:
        return None
    first, last = marks[0], marks[-1]
    before = {(ident, name): s for ident, name, s in first["threads"]}
    by_role: dict[str, float] = {}
    for ident, name, s in last["threads"]:
        s0 = before.get((ident, name), 0.0)
        role = role_of(name)         # a smaller reading: the ident reused
        by_role[role] = by_role.get(role, 0.0) + (s - s0 if s >= s0 else s)
    cpu_s = last["process_s"] - first["process_s"]
    return {"seconds": last["start"] - first["start"], "cpu_s": cpu_s,
            "user_s": last["user_s"] - first["user_s"],
            "system_s": last["system_s"] - first["system_s"],
            "minor_faults": last["minflt"] - first["minflt"],
            "cpu_s_by_role": by_role,
            "native_cpu_s": cpu_s - sum(by_role.values())}


def late_ms(run: dict) -> float | None:
    """Mean `late` of the `host.tick` records, in ms."""
    late = [r["late"] for r in records(run) or ()
            if r["name"] == TICK and r.get("late") is not None]
    return sum(late) / len(late) * 1e3 if late else None
