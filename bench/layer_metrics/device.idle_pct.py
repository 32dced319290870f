"""Share of the traced seconds in which no operation ran on the device:
1 - union of device-op intervals over the traced window."""

# one reader for every `<this name>.<part>` of the manifest: each part
# names the end-to-end metric it moves there
META = {"layer": "device", "source": "device_trace"}


def compute(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
