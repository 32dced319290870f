"""AES-GCM time a client op: self time of `msgr.seal` (staging the frame
and sealing it) and `msgr.open`, over every frame any daemon or the client
sealed or opened in the traced seconds."""

from bench.span_stages import self_ms_per_op

META = {"layer": "messenger", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("msgr.seal", "msgr.open")


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
