"""Driver `ecbench`: one caller, back-to-back `encode_chunks` calls on
host buffers, as `ceph_erasure_code_benchmark --workload encode` drives
a plugin.

From the program it takes `ec.registry.factory(profile)` and the
coder's `encode_chunks`; data, sampling and the comparison are the
benchmark's own.
"""

from __future__ import annotations

import time

import numpy as np

from bench.checks import check, device_peak_bytes
from bench.reference import gf256


def _shape(config: dict, workload: dict) -> tuple[int, int, int]:
    g = config["geometry"]
    return (workload["objects_per_call"], g["k"], g["object_bytes"] // g["k"])


def work_bytes(config: dict, workload: dict, n_calls: int) -> float:
    """Bytes the algorithm must move through device memory for n_calls:
    every call reads k rows and writes m rows of each object, once."""
    b, k, row = _shape(config, workload)
    return float(n_calls) * b * (k + config["geometry"]["m"]) * row


def make_buffers(config: dict, workload: dict, seed: int) -> list[np.ndarray]:
    shape = _shape(config, workload)
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, shape, dtype=np.uint8)
            for _ in range(workload["distinct_buffers"])]


def setup(config: dict, workload: dict, seed: int, log) -> dict:
    from ceph_tpu.ec.registry import factory
    t0 = time.perf_counter()
    coder = factory(config["profile"])
    buffers = make_buffers(config, workload, seed)
    rng = np.random.default_rng([seed, 1])
    # which calls of the window keep their parity for the comparison:
    # drawn from the seed before the window, so the timed loop only
    # looks a flag up
    keep = rng.random(1 << 16) < workload["verify_share"]
    log(f"ecbench setup: {len(buffers)} buffers of {buffers[0].shape} in "
        f"{time.perf_counter() - t0:.2f} s")
    return {"config": config, "workload": workload, "coder": coder,
            "buffers": buffers, "keep": keep}


def warm(state: dict, log) -> None:
    t0 = time.perf_counter()
    for i in range(state["workload"]["warm_calls"]):
        state["coder"].encode_chunks(state["buffers"][i % len(state["buffers"])])
        log(f"ecbench warm call {i}: {time.perf_counter() - t0:.2f} s")


def window(state: dict, seconds: float, tick, log) -> dict:
    coder, buffers, keep = state["coder"], state["buffers"], state["keep"]
    cap = state["workload"]["verify_max_calls"]
    ops, kept, failed = [], [], 0
    call_bytes = buffers[0].size
    t0 = time.perf_counter()
    n = 0
    last = None
    while True:
        tick()
        start = time.perf_counter()
        if start - t0 >= seconds:
            break
        which = n % len(buffers)
        try:
            parity = coder.encode_chunks(buffers[which])
            ok = True
        except Exception as e:       # a failed call is counted, not hidden
            log(f"ecbench call {n} failed: {e!r}")
            parity, ok = None, False
            failed += 1
        end = time.perf_counter()
        ops.append({"start": start, "end": end, "ok": ok,
                    "bytes": call_bytes if ok else 0})
        if ok:
            last = (n, which, parity)
            if keep[n % len(keep)] and len(kept) < cap:
                kept.append(last)
        n += 1
    t1 = time.perf_counter()
    if last is not None and (not kept or kept[-1][0] != last[0]):
        kept.append(last)
    log(f"ecbench window: {n} calls in {t1 - t0:.3f} s, {len(kept)} kept "
        f"for the comparison")
    return {"ops": ops, "t0": t0, "t1": t1, "window_s": t1 - t0,
            "attempted": n, "failed": failed, "counters": {},
            "kept": kept}


def observe(state: dict, run: dict) -> list[dict]:
    """What the timed path produced, as the comparison takes it."""
    return [{"call": n, "data": state["buffers"][which], "parity": parity}
            for n, which, parity in run["kept"]]


def compare(config: dict, workload: dict, observed: list[dict]) -> list[dict]:
    """Every kept call's parity against the plain reference, byte for
    byte (limit 0 rows wrong), and how many rows were compared."""
    g = config["geometry"]
    matrix = gf256.reed_sol_van(g["k"], g["m"])
    want_shape = (workload["objects_per_call"], g["m"],
                  g["object_bytes"] // g["k"])
    wrong = rows = 0
    for ob in observed:
        got = np.asarray(ob["parity"])
        rows += want_shape[0] * want_shape[1]
        if got.shape != want_shape or got.dtype != np.uint8:
            wrong += want_shape[0] * want_shape[1]
            continue
        want = gf256.rs_encode(matrix, ob["data"])
        wrong += int((got != want).any(axis=-1).sum())
    return [check("parity_rows_wrong", wrong, "<=", 0),
            check("parity_rows_compared", rows, ">=",
                  want_shape[0] * want_shape[1])]


def verify(state: dict, run: dict, log) -> list[dict]:
    t0 = time.perf_counter()
    config, workload = state["config"], state["workload"]
    checks = compare(config, workload, observe(state, run))
    # the device has to have held a whole call: operands and parity
    checks.append(check("device_peak_bytes", device_peak_bytes(), ">=",
                        int(work_bytes(config, workload, 1))))
    checks.append(check("calls_failed", run["failed"], "<=", 0))
    log(f"ecbench verify: {len(run['kept'])} calls against the reference in "
        f"{time.perf_counter() - t0:.2f} s")
    return checks


def close(state: dict, log) -> None:
    state.clear()
