"""How long a PG waited for its backfill reservation: the mean of the
primaries' `backfill_reserve_wait_time` (request sent to the PG's targets
-> the last grant taken: the span `recovery.reserve.wait`) over the PGs
reserved between the failure and the run's end. Near 0 for a PG whose
target is free, the length of a neighbour's whole backfill for one that
queues behind it. A program without the counter has nothing to read."""

META = {"layer": "recovery", "source": "program_counter",
        "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    gauges = run.get("gauges_at_end") or {}
    if not gauges.get("reserve_waits"):
        return None
    return gauges["reserve_wait_s"] / gauges["reserve_waits"] * 1e3
