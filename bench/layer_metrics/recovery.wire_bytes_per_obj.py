"""Helper bytes on the wire a rebuilt object: the rise of the sources'
`recover_range_bytes_served` over the rise of `recovered_objects` in the
window, both summed over the daemons and sampled on the window's ticks.
A Clay repair at k=8 m=4 d=11 pulls a quarter row from each of 11
helpers, 1,441,792 bytes an object, where RS pulls 8 whole rows; grants
of 16 objects cut at the window's edges move the reading by up to a
grant's worth. A program without the counter has nothing to read."""

META = {"layer": "recovery", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    recovery = run.get("recovery") or {}
    served = recovery.get("range_bytes_served_in_window")
    if served is None or not recovery.get("rebuilt_in_window"):
        return None
    return served / recovery["rebuilt_in_window"]
