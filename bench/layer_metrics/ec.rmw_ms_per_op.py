"""The parity-delta RMW a client op, host side and waits together: the
self time of `ecbackend.rmw` (one round `ECBackend._delta_group`) and of
its six children: the prefetch round (hinfo and pre-image), the delta's
stage (`new ^ old`, the pad to the bucket), launch (the dispatch with its
H2D) and fetch (the wait for the device), the journal round and the apply
round. A program without the child spans has nothing to read, and a span
log that wrapped in the traced seconds is not read."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}
CHILDREN = ("ecbackend.rmw.prefetch", "ecbackend.rmw.delta.stage",
            "ecbackend.rmw.delta.launch", "ecbackend.rmw.delta.fetch",
            "ecbackend.rmw.journal", "ecbackend.rmw.apply")


def compute(run: dict) -> float | None:
    if (run.get("notes") or {}).get("span_log_dropped") \
            or self_ms_per_op(run, CHILDREN) is None:
        return None
    return self_ms_per_op(run, ("ecbackend.rmw",) + CHILDREN)
