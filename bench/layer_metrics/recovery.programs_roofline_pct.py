"""Bandwidth-roofline share of the device programs while the pool
backfills: the bytes the algorithm must move for the client writes that
completed in the traced seconds (`work_bytes()` of the driver) and for
the objects whose decode was launched in them (k helper rows in, the
rebuilt row and its crc word out: the driver's `recovery_work_bytes`,
which the window leaves on `run["recovery"]` for one object), over the
chip's HBM peak, as a share of the device's busy time there: the union
of device-op intervals, not the events of a kernel found by name."""

from bench.recovery_stages import objects_rebuilt
from bench.stats import bandwidth_roofline_pct

META = {"layer": "recovery", "source": "device_trace",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    objects = objects_rebuilt(run)
    if not objects:
        return None
    work = (run.get("traced_work_bytes") or 0.0) \
        + objects * run["recovery"]["work_bytes_an_object"]
    return bandwidth_roofline_pct(work, run["peaks"]["hbm_bytes_per_s"],
                                  run["trace"]["busy_s"])
