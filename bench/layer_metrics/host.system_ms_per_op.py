"""System time a client op: the rise of the process's system seconds
(`ru_stime`) between the two `host.usage` records over the ops that
completed in the traced seconds: what the kernel charged for first-touch
page faults, `pwrite`, socket sends and every other call (the capture's
own `thread_time` calls among them). It stands where a count of minor
faults was asked for: the chip host's kernel reports `ru_minflt` 0."""

from bench.host_usage import usage

META = {"layer": "host", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    ledger = usage(run)
    if not ledger or not run.get("traced_ops"):
        return None
    return ledger["system_s"] / run["traced_ops"] * 1e3
