"""The sources' work a rebuilt object, where a repair pulls sub-chunk
ranges: the time of `recovery.serve_ranges` (a helper reads its full
rows, checks them against their hinfo, slices the planned ranges and
checksums them; its parts `.read`, `.verify` and `.slice` lie inside
it), summed over every source, over the objects whose repair was
launched in the traced seconds. A program without the span, or a code
that pulls whole rows, has nothing to read."""

from bench.recovery_stages import objects_rebuilt, records

META = {"layer": "recovery", "source": "program_span",
        "moves": "client_mb_s"}
SERVE = "recovery.serve_ranges"


def compute(run: dict) -> float | None:
    objects = objects_rebuilt(run)
    if not objects:
        return None
    served = [r["dur"] for r in records(run) if r["name"] == SERVE]
    return sum(served) / objects * 1e3 if served else None
