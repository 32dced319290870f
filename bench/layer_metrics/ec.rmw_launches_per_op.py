"""Delta launches the EC backends counted over the window
(`rmw_delta_launches`, summed over the daemons' `ec` counters) for each
client op completed in it: 1 where every overwrite is a launch of its
own, less where a primary batches several into one."""

META = {"layer": "EC backend", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    done = run["counters"].get("ops_done")
    if not done or "rmw_delta_launches" not in run["counters"]:
        return None
    return run["counters"]["rmw_delta_launches"] / done
