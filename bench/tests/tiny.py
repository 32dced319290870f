"""The cells at a size a test run can hold: k=2 m=1, 8 KiB objects, four
OSDs, a window of a second. Same files, same drivers, same comparison."""

from __future__ import annotations

import copy
import json

from bench import run as harness

ROOT = harness.ROOT
MANIFEST = harness.load_json(ROOT, "BENCHMARK.json")
SEED = (1 << 31) + 24
CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"hbm_bytes_per_s": 1.0}


def cell_files(name: str) -> tuple[dict, dict, dict, object]:
    cell = harness.find_cell(MANIFEST, name)
    workload = harness.load_json(harness.BENCH, "workloads", name + ".json")
    config = harness.load_json(harness.BENCH, "configs",
                               cell["config"] + ".json")
    return cell, workload, config, harness.load_module("drivers",
                                                       config["driver"])


def tiny(name: str) -> tuple[dict, dict, dict, object]:
    cell, workload, config, driver = cell_files(name)
    config = copy.deepcopy(config)
    config["profile"] = "plugin=jerasure technique=reed_sol_van k=2 m=1"
    config["geometry"].update(k=2, m=1, object_bytes=8192,
                              shard_row_bytes=4096)
    if "cluster" in config:
        config["cluster"].update(n_osds=4, pg_num=4)
        workload = dict(workload, loops=4, distinct_payloads=8,
                        working_set_objects=8, warm_min_s=0.5,
                        warm_quiet_s=0.3, readback_objects=4)
    else:
        workload = dict(workload, objects_per_call=4, distinct_buffers=3,
                        verify_share=0.05, warm_calls=1)
    return cell, workload, config, driver


def run_tiny(name: str, driver=None,
             seconds: float = 1.0) -> tuple[dict, list[dict]]:
    """A whole run but the look for a chip; `driver` where a test has
    patched one."""
    cell, workload, config, fresh = tiny(name)
    return harness.run_cell(MANIFEST, cell, workload, config,
                            driver or fresh, CPU_DEVICE, PEAKS, SEED, seconds,
                            trace=False)


def failed(checks: list[dict]) -> set[str]:
    return {c["name"] for c in checks if not c["ok"]}


def json_line(result: dict) -> dict:
    return json.loads(json.dumps(result))


CELLS = [c["name"] for c in MANIFEST["workloads"]]
RADOS = [c for c in CELLS if cell_files(c)[2]["driver"] == "rados"]
ECBENCH = [c for c in CELLS if cell_files(c)[2]["driver"] == "ecbench"]
