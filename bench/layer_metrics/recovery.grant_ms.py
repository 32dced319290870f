"""How long one grant of a recovery round holds its primary's op-shard
worker, the daemon lock and the PG's lock: the mean duration of
`recovery.grant` in the traced seconds (one fused batch pulled, staged
and launched, the one before it fetched and pushed). A client op queued
on that shard, and every sub-op that daemon serves, waits behind it."""

from bench.recovery_stages import GRANT, records

META = {"layer": "recovery", "source": "program_span",
        "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    grants = [r["dur"] for r in records(run) or () if r["name"] == GRANT]
    return sum(grants) / len(grants) * 1e3 if grants else None
