"""Median latency of the client ops issued in the window, from the
driver's own clock round each `Client.write` / `Client.read`."""

from bench.stats import percentile

META = {"layer": "client", "source": "host_clock", "moves": "op_p95_ms"}


def compute(run: dict) -> float | None:
    lat = [op["end"] - op["start"] for op in run["ops"]
           if op["ok"] and op["start"] >= run["t0"]]
    p50 = percentile(lat, 0.50)
    return None if p50 is None else p50 * 1e3
