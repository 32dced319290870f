"""Objects rebuilt a second while the clients write: the rise of
`recovered_objects` (summed over the daemons' `ec` counters, sampled
every 0.2 s by the driver) between the window's open and its close, over
the window's seconds. Recovery runs through the whole window and past it
(the configuration's guarantee (c)), so this is the rate of one steady
backfill under client load. North-star metric #2 (BASELINE.json:
PG-recovery objects/s)."""

META = {"layer": "recovery", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    recovery = run.get("recovery") or {}
    if not recovery.get("window_s"):
        return None
    return recovery["rebuilt_in_window"] / recovery["window_s"]
