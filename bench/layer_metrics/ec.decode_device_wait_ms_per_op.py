"""The degraded read's blocking fetch a client op, on the host's clock:
the wait for the device to reach and finish the decode program and hand
the rebuilt rows back, its queue included. Never a device time."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
NAMES = ("ecbackend.read.decode.fetch",)


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
