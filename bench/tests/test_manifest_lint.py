"""A lint of BENCHMARK.json and every file it points at."""

import os
import re

import pytest

from bench import run as harness
from tiny import MANIFEST, ROOT, cell_files

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert all(_line(w) for w in MANIFEST["command"])
    n = len(MANIFEST["workloads"])
    assert sum(c["chips"] == 4 for c in MANIFEST["workloads"]) <= max(n // 2, 1)
    # a full check with 24 cells fits the driver's 43,200 s
    s = MANIFEST["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_and_units():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(m["layer"])
    assert any(m["name"] == "setup_s" for m in MANIFEST["end_to_end"])


def test_configs_and_cells_point_at_files():
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    used = set()
    pairs = set()
    for cell in MANIFEST["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(cell["traffic"]) and cell["chips"] in (1, 4)
        assert _line(cell["why"])
        assert cell["config"] in configs
        used.add(cell["config"])
        pairs.add((cell["config"], cell["traffic"]))
        _, workload, config, driver = cell_files(cell["name"])
        assert workload["name"] == cell["name"]
        assert workload["config"] == cell["config"] == config["name"]
        for fn in ("setup", "warm", "window", "verify", "work_bytes", "close"):
            assert callable(getattr(driver, fn))
    assert used == set(configs)
    assert len(pairs) == len(MANIFEST["workloads"])
    files = set()
    for c in MANIFEST["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in MANIFEST["paths"])
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        files.add(c["file"])
        held = harness.load_json(ROOT, c["file"])
        assert held["source"] == c["source"]
        assert sorted(held["reduced"]) == sorted(c["reduced"])
        assert all(NAME.match(k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert "assumed" in held and "guarantees" in held
        assert "impl=" not in held["profile"]
    assert len(files) == len(MANIFEST["configs"])


def _cells_of(metric: dict) -> list[str]:
    return metric.get("workloads", [c["name"] for c in MANIFEST["workloads"]])


def test_every_moves_names_a_metric_its_cells_report():
    cells = {c["name"] for c in MANIFEST["workloads"]}
    e2e = {m["name"]: m for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert set(_cells_of(m)) <= cells, m["name"]
    for m in MANIFEST["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert set(_cells_of(m)) <= set(_cells_of(e2e[m["moves"]])), m["name"]
    for cell in cells:
        mine = [m["name"] for m in MANIFEST["end_to_end"]
                if cell in _cells_of(m)]
        assert "setup_s" in mine and len(mine) >= 2, cell
        assert any(cell in _cells_of(m) for m in MANIFEST["per_layer"]), cell


@pytest.mark.parametrize("kind,group", [("end_to_end", "end_to_end"),
                                        ("layer_metrics", "per_layer")])
def test_every_metric_has_a_reader(kind, group):
    for m in MANIFEST[group]:
        module = harness.load_module(kind, m["name"])
        assert callable(module.compute)
        if group == "per_layer":
            assert module.META["layer"] == m["layer"]
            assert module.META["source"] == m["source"]
            # a reader shared by the parts of a split metric names no `moves`
            assert module.META.get("moves", m["moves"]) == m["moves"]


def test_a_reader_with_nothing_to_read_returns_nothing():
    empty = {"ops": [], "t0": 0.0, "t1": 1.0, "window_s": 1.0,
             "counters": {}, "trace": None, "set_up_seconds": 1.0,
             "peaks": {"hbm_bytes_per_s": 819e9}}
    for m in MANIFEST["per_layer"]:
        assert harness.load_module("layer_metrics",
                                   m["name"]).compute(dict(empty)) is None
    for m in MANIFEST["end_to_end"]:
        if m["name"] != "setup_s":
            assert harness.load_module("end_to_end",
                                       m["name"]).compute(dict(empty)) is None


def test_run_py_holds_no_cell_config_or_metric_name():
    with open(os.path.join(ROOT, "bench", "run.py")) as f:
        text = f.read()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in MANIFEST[group]:
            assert entry["name"] not in text, entry["name"]


def test_file_names_under_paths():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in MANIFEST["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", path)
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert ok.match(rel), rel


def test_peaks_have_sources():
    peaks = harness.load_json(ROOT, "bench", "peaks.json")
    for kind, row in peaks.items():
        assert row["hbm_bytes_per_s"] > 0 and row["source"], kind
