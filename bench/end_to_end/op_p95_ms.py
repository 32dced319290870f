"""95th percentile of the latency of every client op issued in the
window, call to return; a failed or timed-out op counts as the window's
length."""

from bench.stats import percentile


def compute(run: dict) -> float | None:
    lat = [(op["end"] - op["start"]) if op["ok"] else run["window_s"]
           for op in run["ops"] if op["start"] >= run["t0"]]
    p95 = percentile(lat, 0.95)
    return None if p95 is None else p95 * 1e3
