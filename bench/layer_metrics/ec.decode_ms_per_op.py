"""The degraded read's decode a client op, host side and wait together:
the self time of `ecbackend.read.decode` and of its three children
(stacking the k helper rows, the dispatch with its copy to the device,
the wait for the rebuilt rows). A read that rebuilds nothing leaves the
parent alone, a few microseconds; a program without the child spans has
nothing to read."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
CHILDREN = ("ecbackend.read.decode.stage", "ecbackend.read.decode.launch",
            "ecbackend.read.decode.fetch")


def compute(run: dict) -> float | None:
    if self_ms_per_op(run, CHILDREN) is None:
        return None
    return self_ms_per_op(run, ("ecbackend.read.decode",) + CHILDREN)
