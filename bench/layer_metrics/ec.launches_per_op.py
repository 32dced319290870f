"""Device launches the EC backends counted over the window (fused
write + generic encode + decode, summed over the daemons' `ec`
counters) for each client op completed in it."""

META = {"layer": "EC backend", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    done = run["counters"].get("ops_done")
    if not done or "ec_launches" not in run["counters"]:
        return None
    return run["counters"]["ec_launches"] / done
