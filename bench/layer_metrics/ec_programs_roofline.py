"""Bandwidth-roofline share of the device programs in the traced
seconds: the bytes the algorithm must move for the ops completed in them
(`work_bytes()` of the driver, from shapes alone) over the chip's HBM
peak, as a share of the device's busy time there: the union of device-op
intervals, not the events of a kernel found by name."""

from bench.stats import bandwidth_roofline_pct

# one reader for every `<this name>.<part>` of the manifest: each part
# names the end-to-end metric it moves there
META = {"layer": "device programs", "source": "device_trace"}


def compute(run: dict) -> float | None:
    if not run.get("trace"):
        return None
    return bandwidth_roofline_pct(run.get("traced_work_bytes"),
                                  run["peaks"]["hbm_bytes_per_s"],
                                  run["trace"]["busy_s"])
