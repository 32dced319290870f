"""Programs the EC backends compiled (or loaded from the cache) inside
the window: the rise of `program_cache_misses`. Should read 0."""

META = {"layer": "EC backend", "source": "program_counter",
        "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    return run["counters"].get("program_cache_misses")
