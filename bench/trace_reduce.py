"""From a profiler trace to device busy time, op durations and idle gaps.

The trace is held as plain data, so that a recorded one can sit beside
the tests as JSON:

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

`from_xspace` makes that from the bytes a `ProfilerSession.stop()`
returns. `reduce` reads it:

- device planes are those named `/device:TPU:<n>`; the operations that
  ran on a chip are the events of its `XLA Ops` line (the `XLA Modules`
  line holds whole programs and `Steps` their groups: both cover the
  same time again and are not added);
- busy time is the union of those intervals, clipped to the window and
  averaged over the device planes; the window is the `bench.trace_window`
  host span the harness wraps round the traced seconds;
- an idle gap is a stretch of the window with no operation on the first
  device. Gaps under 10 us lie between the operations of one program and
  are summed as such; a longer one is split by what the host was doing at
  each instant (`_split_gap`), and the pieces are summed by name;
- a device operation's name is its HLO name without the `%`, the text
  after ` = ` and a trailing `.<n>`, so the clones of one fusion add up.

    python3 bench/trace_reduce.py dump <file.xplane.pb | file.json>
"""

from __future__ import annotations

import json
import re
import sys

try:
    from .stats import union_length
except ImportError:          # run as a script
    from stats import union_length

WINDOW_SPAN = "bench.trace_window"
DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
MICRO_GAP_NS = 10_000.0
#: host events that say nothing about what the host was doing
_SKIP_HOST = ("ThreadpoolListener::", "Transpose::ExecuteChunk", WINDOW_SPAN)


def from_xspace(data: bytes) -> dict:
    from jax.profiler import ProfileData
    space = ProfileData.from_serialized_xspace(data)
    return {"planes": [
        {"name": plane.name,
         "lines": [{"name": line.name,
                    "events": [[e.name, float(e.start_ns),
                                float(e.duration_ns)]
                               for e in line.events]}
                   for line in plane.lines]}
        for plane in space.planes]}


def _device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"]
            if p["name"].startswith(DEVICE_PREFIX)]


def _op_events(plane: dict) -> list[list]:
    lines = [ln for ln in plane["lines"] if ln["name"] == OP_LINE]
    return [e for ln in lines for e in ln["events"] if e[2] > 0]


def _host_events(trace: dict) -> list[list]:
    return [e for p in trace["planes"] if p["name"].startswith(HOST_PREFIX)
            for ln in p["lines"] for e in ln["events"]]


def _window(trace: dict) -> tuple[float, float] | None:
    spans = [e for e in _host_events(trace) if e[0] == WINDOW_SPAN]
    if spans:
        return spans[0][1], spans[0][1] + spans[0][2]
    ops = [e for p in _device_planes(trace) for e in _op_events(p)]
    if not ops:
        return None
    return min(e[1] for e in ops), max(e[1] + e[2] for e in ops)


def _clipped(events, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for _, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b))
    return out


def _gaps(busy: list[tuple[float, float]], lo: float,
          hi: float) -> list[tuple[float, float]]:
    gaps, edge = [], lo
    for a, b in sorted(busy):
        if a > edge:
            gaps.append((edge, a))
        edge = max(edge, b)
    if hi > edge:
        gaps.append((edge, hi))
    return gaps


def _split_gap(gap: tuple[float, float], host: list[list]) -> dict[str, float]:
    """The gap's nanoseconds by what the host was doing. At each instant
    the name is the longest host span active that is no longer than the
    gap (the outermost one that is about this gap and not about the
    whole run); where all are longer, the shortest active span."""
    lo, hi = gap
    near = [(max(s, lo), min(s + d, hi), d, name) for name, s, d in host
            if s < hi and s + d > lo]
    edges = sorted({lo, hi, *(e[0] for e in near), *(e[1] for e in near)})
    out: dict[str, float] = {}
    for a, b in zip(edges, edges[1:]):
        active = [e for e in near if e[0] <= a and e[1] >= b]
        fitting = [e for e in active if e[2] <= hi - lo]
        if fitting:
            name = max(fitting, key=lambda e: e[2])[3]
        elif active:
            name = min(active, key=lambda e: e[2])[3]
        else:
            name = "(no host span)"
        out[name] = out.get(name, 0.0) + b - a
    return out


def op_name(hlo: str) -> str:
    name = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", name)[:120]


def _top(totals: dict[str, float], n: int = 10) -> list[list]:
    return [[name, ns / 1e9] for name, ns in
            sorted(totals.items(), key=lambda kv: -kv[1])[:n]]


def reduce(trace: dict) -> dict | None:
    """busy_s, window_s (seconds), device_ops and idle_gaps (at most ten
    [name, seconds] each), n_ops. None where the trace holds no device
    operation: nothing to read is not the same as idle."""
    planes = _device_planes(trace)
    window = _window(trace)
    if not planes or window is None:
        return None
    lo, hi = window
    per_plane = [_clipped(_op_events(p), lo, hi) for p in planes]
    if not any(per_plane):
        return None
    busy_ns = sum(union_length(iv) for iv in per_plane) / len(planes)
    by_op: dict[str, float] = {}
    for p in planes:
        for name, start, dur in _op_events(p):
            span = min(start + dur, hi) - max(start, lo)
            if span > 0:
                name = op_name(name)
                by_op[name] = by_op.get(name, 0.0) + span
    host = [e for e in _host_events(trace)
            if e[2] > 0 and not e[0].startswith(_SKIP_HOST)]
    by_gap: dict[str, float] = {}
    for gap in _gaps(per_plane[0], lo, hi):
        if gap[1] - gap[0] < MICRO_GAP_NS:
            parts = {"(between ops of one program)": gap[1] - gap[0]}
        else:
            parts = _split_gap(gap, host)
        for name, ns in parts.items():
            by_gap[name] = by_gap.get(name, 0.0) + ns
    return {"busy_s": busy_ns / 1e9, "window_s": (hi - lo) / 1e9,
            "n_ops": sum(len(iv) for iv in per_plane),
            "device_ops": _top(by_op), "idle_gaps": _top(by_gap)}


def summary(trace: dict, per_line: int = 12) -> str:
    """What a person looks at first: planes, lines, event counts, and
    each line's names by total time."""
    out = []
    for plane in trace["planes"]:
        out.append(f"PLANE {plane['name']}")
        for line in plane["lines"]:
            ev = line["events"]
            out.append(f"  LINE {line['name']!r}: {len(ev)} events")
            totals: dict[str, list] = {}
            for name, _, dur in ev:
                t = totals.setdefault(name, [0, 0.0])
                t[0] += 1
                t[1] += dur
            for name, (n, ns) in sorted(totals.items(),
                                        key=lambda kv: -kv[1][1])[:per_line]:
                out.append(f"      {ns / 1e6:12.3f} ms  x{n:<6d} {name[:100]}")
    return "\n".join(out)


def _load(path: str) -> dict:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    with open(path, "rb") as f:
        return from_xspace(f.read())


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "dump":
        sys.exit(__doc__)
    loaded = _load(sys.argv[2])
    print(summary(loaded))
    print(json.dumps(reduce(loaded), indent=1))
