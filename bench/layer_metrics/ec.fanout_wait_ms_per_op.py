"""A write's fan-out on its primary: encoding and sending the k+m shard
transactions, then the wait for the slowest replica's ack: the self time
of `ecbackend.write.fanout`, so without the frames' seals and the primary's
own commit, which are spans of their own inside it."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("ecbackend.write.fanout",)


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
