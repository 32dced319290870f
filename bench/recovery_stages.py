"""Recovery's spans of the program's span log, as the `recovery.*`
readers take them.

While a profiler session is live every grant of a recovery round logs
`recovery.grant` (`nbytes`: the helper bytes it is to stage) and, inside
it, `recovery.pull` (the helper rows read into the stage buffer),
`recovery.stage`, `recovery.launch` (the dispatch with its copy to the
device; `nbytes` is the helper bytes the launch decodes, k rows an
object), `recovery.fetch` (the wait for the rebuilt rows),
`recovery.push` (the rebuilt rows sent to the new member, acks taken as
the push window fills) and, at a round's end, `recovery.settle`. The
unit here is a rebuilt object, not a client op: the objects whose decode
was launched in the traced seconds, counted from the launches' `nbytes`
over the driver's helper bytes an object (`run["recovery"]`). A program
without the log, or whose launch spans carry no bytes, has nothing to
read.
"""

from __future__ import annotations

from bench.span_stages import tracing

GRANT = "recovery.grant"
LAUNCH = "recovery.launch"
FETCH = "recovery.fetch"
HOST = (GRANT, "recovery.pull", "recovery.stage", LAUNCH, "recovery.push",
        "recovery.settle")


def records(run: dict) -> list | None:
    """The span records of the window; None without a traced run."""
    module = tracing()
    if module is None or not run.get("trace"):
        return None
    return module.span_log(since=run["t0"])


def objects_rebuilt(run: dict) -> float | None:
    """Objects whose decode was launched in the traced seconds."""
    found = records(run)
    per_object = (run.get("recovery") or {}).get("helper_bytes_an_object")
    if not found or not per_object:
        return None
    staged = sum((r.get("nbytes") or 0) for r in found
                 if r["name"] == LAUNCH)
    return staged / per_object if staged else None


def self_ms_per_object(run: dict, names: tuple[str, ...]) -> float | None:
    """Self time of the spans called `names` for each object rebuilt in
    the traced seconds."""
    objects = objects_rebuilt(run)
    if not objects:
        return None
    found = [r["self"] for r in records(run) if r["name"] in names]
    return sum(found) / objects * 1e3 if found else None
