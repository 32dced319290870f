"""The host work a client op costs, whatever its threads' waits: the
process's CPU seconds between the two `host.usage` records over the ops
that completed in the traced seconds. Times ops a second it is
`host.cores_busy` x 1,000."""

from bench.host_usage import usage

META = {"layer": "host", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    ledger = usage(run)
    if not ledger or not run.get("traced_ops"):
        return None
    return ledger["cpu_s"] / run["traced_ops"] * 1e3
