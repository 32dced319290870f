"""Device busy time in the traced seconds for each codec call that
completed in them."""

META = {"layer": "EC plugin", "source": "device_trace",
        "moves": "codec_gb_s"}


def compute(run: dict) -> float | None:
    if not run.get("trace") or not run.get("traced_ops"):
        return None
    return run["trace"]["busy_s"] / run["traced_ops"] * 1e3
