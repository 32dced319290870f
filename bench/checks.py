"""What the drivers' comparisons share: one number beside its limit, and
the device's peak memory as jax reports it."""

from __future__ import annotations


def check(name: str, value, how: str, limit) -> dict:
    """`value` held to `limit`: `how` is "<=" or ">="."""
    ok = value <= limit if how == "<=" else value >= limit
    return {"name": name, "value": value, "how": how, "limit": limit,
            "ok": bool(ok)}


def device_peak_bytes() -> int:
    """Peak bytes in use on the fullest local device; 0 where the
    backend keeps no such count (the CPU)."""
    import jax
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())

