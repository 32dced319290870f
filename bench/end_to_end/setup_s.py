"""Process start to the first timed op: imports, chip init, native lib,
cluster boot, data from the seed, warm-up of the cell's own shapes."""


def compute(run: dict) -> float | None:
    return run["set_up_seconds"]
