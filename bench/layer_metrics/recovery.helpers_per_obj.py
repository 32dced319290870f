"""Helper rows a rebuilt object: the helper reads the recover launches
of the traced seconds staged (the program's counter
`recover_helper_reads`, as each `recovery.launch` record carries its
part of it beside the objects it decodes, `tags`) over those objects.
RS k=8 m=3 reads 8, Clay k=8 m=4 d=11 reads 11, LRC k=8 m=4 l=3 reads the
3 other members of the lost row's group: a change that breaks locality
shows here first. A program whose launch records carry no such tags has
nothing to read."""

from bench.recovery_stages import LAUNCH, records

META = {"layer": "recovery", "source": "program_counter",
        "moves": "client_mb_s"}
COUNTER = "recover_helper_reads"


def compute(run: dict) -> float | None:
    tags = [r["tags"] for r in records(run) or []
            if r["name"] == LAUNCH and COUNTER in (r.get("tags") or {})]
    objects = sum(t["objects"] for t in tags)
    return sum(t[COUNTER] for t in tags) / objects if objects else None
