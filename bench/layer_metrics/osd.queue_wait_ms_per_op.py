"""What a client op waits on its primary before it runs: `osd.queue` (from
the frame's arrival until the PG's one shard worker takes it) and
`osd.pg_lock.wait`."""

from bench.span_stages import self_ms_per_op

META = {"layer": "OSD op shard", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("osd.queue", "osd.pg_lock.wait")


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
