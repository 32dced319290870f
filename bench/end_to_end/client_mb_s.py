"""Object bytes of all client ops that completed inside the window
(acked writes, reads returned to the caller) over the window's wall
time. An op still in flight when the window closes is waited for and
counts in the latency, not here."""

from bench.stats import rate


def compute(run: dict) -> float | None:
    done = sum(op["bytes"] for op in run["ops"]
               if op["ok"] and run["t0"] <= op["end"] <= run["t1"])
    value = rate(done, run["window_s"])
    return None if not value else value / 1e6
