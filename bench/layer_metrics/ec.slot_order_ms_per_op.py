"""What putting a write's rows in slot order costs an op, where the
code's chunk mapping is not the identity (LRC interleaves data and
parity slots): the self time of `ecbackend.write.slots`, the crcs
permuted and the rows handed to the fan-out as views. Identity-mapped
pools (RS, Clay) pay nothing and log no such span."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
NAMES = ("ecbackend.write.slots",)


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
