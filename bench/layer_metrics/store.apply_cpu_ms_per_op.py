"""The CPU time of `store.apply_ms_per_op`'s spans a client op over every
daemon that held a shard of it: what the store's threads ran, where the
other reads what they ran or waited. `NAMES` is that metric's, copied (a
test holds the two equal). A commit's detail spans take nothing from it."""

from bench.host_usage import cpu_ms_per_op

META = {"layer": "store", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("osd.store_lock.wait", "store.apply", "store.commit", "store.read")


def compute(run: dict) -> float | None:
    return cpu_ms_per_op(run, NAMES)
