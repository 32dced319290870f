"""The EC backend's blocking fetch a client op, on the host's clock: the
wait for the device to reach and finish the op's program (a write's fused
encode and crc, a read's crc verify), its queue included. Never a device
time."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
NAMES = ("ecbackend.write.fetch", "ecbackend.read.verify.fetch")


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
