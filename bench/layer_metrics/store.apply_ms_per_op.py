"""Store time a client op over every daemon that held a shard of it: the
wait for the daemon's store lock, `store.apply` round a sub-op, TinStore's
`store.commit` (device write and WAL append) and `store.read`."""

from bench.span_stages import self_ms_per_op

META = {"layer": "store", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("osd.store_lock.wait", "store.apply", "store.commit", "store.read")


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
