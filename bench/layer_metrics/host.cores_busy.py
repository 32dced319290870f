"""Cores the process keeps busy in the traced seconds: its CPU seconds
(user + system, every thread, the runtime's included) between the two
`host.usage` records over the seconds between them. Near 1 under 16 ops
in flight: one interpreter is the cap; several: the work itself is dear."""

from bench.host_usage import usage

META = {"layer": "host", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    ledger = usage(run)
    return ledger["cpu_s"] / ledger["seconds"] if ledger else None
