"""What the PG's metadata persist costs a client op: the self time of
`osd.persist_meta`, one round `OSDDaemon._persist_meta` (the PG's whole
metadata blob encoded and sent to every live shard, the acks awaited)
after each `write_at`. A span log that wrapped in the traced seconds is
not read."""

from bench.span_stages import self_ms_per_op

META = {"layer": "OSD op shard", "source": "program_span",
        "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    if (run.get("notes") or {}).get("span_log_dropped"):
        return None
    return self_ms_per_op(run, ("osd.persist_meta",))
