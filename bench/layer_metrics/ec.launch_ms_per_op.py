"""Host-clock time under the EC backends' launch spans (`encode_time` +
`decode_time` sums: stage, launch, blocking fetch) over the window, for
each client op completed in it. Not a device time."""

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    done = run["counters"].get("ops_done")
    if not done or "ec_launch_seconds" not in run["counters"]:
        return None
    return run["counters"]["ec_launch_seconds"] / done * 1e3
