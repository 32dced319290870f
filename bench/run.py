#!/usr/bin/env python3
"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is found by name from `BENCHMARK.json`:
`bench/workloads/<cell>.json` (the traffic mix), `bench/configs/<config>.json`
(the deployment, which names its driver), `bench/drivers/<driver>.py` (one
kind of traffic), `bench/end_to_end/<metric>.py` and
`bench/layer_metrics/<metric>.py` (one reader per metric). This file holds
no cell's, configuration's or metric's name.

A run: gate on the chip, set-up and warm-up (the set-up time), the measured
window (with `--trace 1` a few traced seconds inside it), the comparison
with the plain reference once the window has closed, then one JSON line.
Without a TPU, or with a device kind `bench/peaks.json` does not list, it
exits non-zero before any phase and prints no result.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # before the heavy imports: set-up starts here

import argparse                      # noqa: E402
import importlib.util                # noqa: E402
import json                          # noqa: E402
import os                            # noqa: E402
import sys                           # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import trace_reduce       # noqa: E402
from bench.checks import device_peak_bytes   # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """bench/<kind>/<name>.py as a module; names may hold dots. A metric
    split by what it moves (`<quantity>.<part>`) that has no file of its
    own is read by `<quantity>.py`."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path) and "." in name:
        path = os.path.join(BENCH, kind, name.rsplit(".", 1)[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: dict, name: str) -> dict:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"run.py: BENCHMARK.json has no workload {name!r}")


def metrics_of(entries: list[dict], cell: str) -> list[dict]:
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]


def gate_on_chip(chips: int) -> tuple[dict, dict]:
    """The device as jax reports it and its row of peaks; exits non-zero
    on anything but enough TPU chips of a listed kind."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"run.py: need {chips} tpu chip(s), jax found {len(devices)} x "
              f"{devices[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    peaks = load_json(BENCH, "peaks.json")
    kind = devices[0].device_kind
    if kind not in peaks:
        print(f"run.py: device kind {kind!r} is not in bench/peaks.json",
              file=sys.stderr)
        raise SystemExit(3)
    return ({"platform": devices[0].platform, "kind": kind,
             "count": len(devices)}, peaks[kind])


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path in the checkout (the path
    is part of the key), unless the environment already places it; the
    same rule as the program's `utils/jax_cache`, so the two agree."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_bench_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Tracer:
    """A few traced seconds inside the window. The driver's window calls
    `tick()` from one thread, often; the profiler starts once `after_s`
    of the window have passed and stops `length_s` later. Python tracer
    off: its per-call events flood the profiler's buffer (the lesson of
    `utils/tracing.start_trace`)."""

    def __init__(self, enabled: bool, after_s: float, length_s: float):
        self.enabled = enabled
        self.after_s, self.length_s = after_s, length_s
        self.t_window = None
        self.t_start = self.t_stop = None
        self._session = self._span = None
        self.xspace: bytes | None = None

    def tick(self) -> None:
        if not self.enabled or self.t_stop is not None:
            return
        now = time.perf_counter()
        if self.t_window is None:
            self.t_window = now
        if self._session is None:
            if now - self.t_window >= self.after_s:
                self._start()
        elif now - self.t_start >= self.length_s:
            self.stop()

    def _start(self) -> None:
        from jax._src.lib import _profiler
        from jax.profiler import ProfileOptions, TraceAnnotation
        opts = ProfileOptions()
        opts.python_tracer_level = 0
        self._session = _profiler.ProfilerSession(opts)
        self._span = TraceAnnotation(trace_reduce.WINDOW_SPAN)
        self._span.__enter__()
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if self._session is None or self.t_stop is not None:
            return
        self.t_stop = time.perf_counter()
        self._span.__exit__(None, None, None)
        self.xspace = self._session.stop()
        self._session = None


def disk_written_bytes() -> int | None:
    try:
        with open("/proc/self/io") as f:
            return int(dict(line.split(": ") for line in f)["write_bytes"])
    except (OSError, KeyError, ValueError):
        return None


def read_trace(tracer: Tracer, run: dict, driver, config: dict,
               workload: dict) -> dict:
    """The traced seconds reduced, and put on `run` beside the ops that
    completed in them and the bytes those had to move: before the
    comparison, which may hold the device to them."""
    if tracer.xspace is None:
        raise SystemExit("run.py: the window ended before a trace was taken")
    reduced = trace_reduce.reduce(trace_reduce.from_xspace(tracer.xspace))
    if reduced is None:
        raise SystemExit("run.py: no operation ran on the device in the "
                         "traced window")
    traced_ops = sum(1 for op in run["ops"] if op["ok"]
                     and tracer.t_start <= op["end"] <= tracer.t_stop)
    run.update(trace=reduced, traced_ops=traced_ops,
               traced_work_bytes=driver.work_bytes(config, workload,
                                                   traced_ops))
    log(f"trace: {reduced['n_ops']} device ops, {traced_ops} ops "
        f"completed in {reduced['window_s']:.3f} s")
    return reduced


def run_cell(manifest: dict, cell: dict, workload: dict, config: dict,
             driver, device: dict, peaks: dict, seed: int, seconds: float,
             trace: bool) -> tuple[dict, list[dict]]:
    """Everything after the look for a chip: set-up, warm-up, window,
    comparison, metrics. Returns the result line's object and the
    comparison's numbers."""
    state = driver.setup(config, workload, seed, log)
    try:
        driver.warm(state, log)
        set_up_seconds = time.perf_counter() - T_PROCESS
        log(f"set-up: {set_up_seconds:.3f} s")
        tracer = Tracer(trace, workload.get("trace_after_s", 2.0),
                        workload.get("trace_seconds", 4.0))
        run = driver.window(state, seconds, tracer.tick, log)
        tracer.stop()
        device = dict(device, memory_peak_bytes=device_peak_bytes())
        run.update(set_up_seconds=set_up_seconds, peaks=peaks, trace=None)
        if trace:
            reduced = read_trace(tracer, run, driver, config, workload)
            device["busy_s"], device["window_s"] = (reduced["busy_s"],
                                                    reduced["window_s"])
        checks = driver.verify(state, run, log)
    finally:
        driver.close(state, log)
    log(f"disk written by this process: {disk_written_bytes()} bytes")

    kind = "layer_metrics" if trace else "end_to_end"
    entries = manifest["per_layer"] if trace else manifest["end_to_end"]
    metrics = {}
    for entry in metrics_of(entries, cell["name"]):
        value = load_module(kind, entry["name"]).compute(run)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}

    result = {"correct": all(c["ok"] for c in checks),
              "attempted": run["attempted"], "failed": run["failed"],
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    if run.get("notes"):                 # what a driver has to say of the run
        result["notes"] = run["notes"]
    # the numbers compared, each beside its limit: last in the line
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"], "ok": c["ok"]}
                          for c in checks}
    return result, checks


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = find_cell(manifest, args.workload)
    workload = load_json(BENCH, "workloads", cell["name"] + ".json")
    config = load_json(BENCH, "configs", cell["config"] + ".json")
    driver = load_module("drivers", config["driver"])

    device, peaks = gate_on_chip(cell["chips"])
    cache_dir = enable_compile_cache()
    log(f"device: {json.dumps(device)}  compile cache: {cache_dir}")

    result, checks = run_cell(manifest, cell, workload, config, driver,
                              device, peaks, args.seed, args.seconds,
                              bool(args.trace))
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    for c in checks:
        print(f"compared {c['name']}: {c['value']} (limit {c['how']} "
              f"{c['limit']}) {'ok' if c['ok'] else 'NOT OK'}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
