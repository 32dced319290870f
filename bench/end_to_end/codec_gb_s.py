"""Object (data) bytes passed through the codec entry, host buffers in
and result back in host memory, over the whole window's wall time."""

from bench.stats import rate


def compute(run: dict) -> float | None:
    done = sum(op["bytes"] for op in run["ops"] if op["ok"])
    value = rate(done, run["window_s"])
    return None if not value else value / 1e9
