"""What the always-on planes take: the CPU of the threads whose role is
in `host_usage.PLANE_ROLES` (each daemon's sampler and its heartbeat /
report loop) as a share of the process's CPU between the two `host.usage`
records."""

from bench.host_usage import PLANE_ROLES, usage

META = {"layer": "host", "source": "program_counter",
        "moves": "client_mb_s"}


def compute(run: dict) -> float | None:
    ledger = usage(run)
    if not ledger or ledger["cpu_s"] <= 0:
        return None
    planes = sum(s for role, s in ledger["cpu_s_by_role"].items()
                 if role in PLANE_ROLES)
    return planes / ledger["cpu_s"] * 100.0 if planes > 0 else None
