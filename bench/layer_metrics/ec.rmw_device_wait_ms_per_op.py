"""What a small overwrite waits for the device: the self time of
`ecbackend.rmw.delta.fetch`, the `jax.device_get` of the parity deltas
and the crc words of one delta launch, a client op. A span log that
wrapped in the traced seconds is not read."""

from bench.span_stages import self_ms_per_op

META = {"layer": "EC backend", "source": "program_span",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    if (run.get("notes") or {}).get("span_log_dropped"):
        return None
    return self_ms_per_op(run, ("ecbackend.rmw.delta.fetch",))
