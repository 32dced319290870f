"""`trace_reduce` on a hand-made trace and on a recorded one.

`recorded_trace_ecbench.json` is the first 241 ms of the traced window of
`ecbench_encode_4m_b32` on one TPU v5e (chip run of PR 24, seed
2147483660): two encode programs, 407 events, names cut to 60
characters, times moved to start at 0."""

import json
import os

import pytest

from bench import trace_reduce as tr

HERE = os.path.dirname(__file__)


def hand_made():
    # one chip, times in ns; the window is 1000 us; ops at [100, 300) us
    # and [250, 400) us overlap, [700, 800) us stands alone; the module
    # line covers the same time again and must not be added
    us = 1000
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit_f(1)", 100 * us, 300 * us],
                                               ["jit_f(1)", 700 * us, 100 * us]]},
            {"name": "XLA Ops", "events": [
                ["%fusion.1 = u8[4]{0} fusion(...)", 100 * us, 200 * us],
                ["%fusion.2 = u8[4]{0} fusion(...)", 250 * us, 150 * us],
                ["%copy = u8[4]{0} copy(...)", 700 * us, 100 * us]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "main", "events": [
                [tr.WINDOW_SPAN, 0, 1000 * us],
                ["whole_run", 0, 5000 * us],           # longer than any gap
                ["prepare", 0, 90 * us],               # inside the first gap
                ["fetch", 400 * us, 300 * us],         # the middle gap, all of it
                ["fetch.inner", 450 * us, 100 * us]]}]},   # shorter: fetch stays
    ]}


def test_hand_made_busy_idle_and_ops():
    r = tr.reduce(hand_made())
    assert r["busy_s"] == pytest.approx(400e-6)      # 300 + 100, overlap once
    assert r["window_s"] == pytest.approx(1000e-6)
    assert r["n_ops"] == 3
    assert r["device_ops"][0] == ["fusion", pytest.approx(350e-6)]
    assert r["device_ops"][1] == ["copy", pytest.approx(100e-6)]


def test_hand_made_gaps_go_to_host_spans():
    gaps = dict(tr.reduce(hand_made())["idle_gaps"])
    # gaps: [0, 100) [400, 700) [800, 1000) us
    assert gaps["fetch"] == pytest.approx(300e-6)
    assert gaps["prepare"] == pytest.approx(90e-6)
    # 10 us of the first gap and the last gap have only the run-long span
    assert gaps["whole_run"] == pytest.approx(210e-6)
    assert sum(gaps.values()) == pytest.approx(600e-6)


def test_no_device_operation_reads_as_nothing_not_as_idle():
    trace = hand_made()
    trace["planes"][0]["lines"][1]["events"] = []
    assert tr.reduce(trace) is None
    assert tr.reduce({"planes": []}) is None


def test_op_name():
    assert tr.op_name("%and_xor_fusion.12 = u8[3]{0} fusion(%a, %b)") == \
        "and_xor_fusion"
    assert tr.op_name("copy-start") == "copy-start"


def test_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace_ecbench.json")) as f:
        trace = json.load(f)
    r = tr.reduce(trace)
    # worked out by hand from the file: the two programs on the `XLA
    # Modules` line last 8,531,050 + 8,532,195 ns; their 162 ops, merged,
    # cover 17,062,336 ns (909 ns lie between ops); the window span is
    # 241,491,450 ns
    assert r["n_ops"] == 162
    assert r["busy_s"] == pytest.approx(17_062_336e-9, rel=1e-9)
    assert r["window_s"] == pytest.approx(241_491_450e-9, rel=1e-9)
    idle_pct = 100 * (1 - r["busy_s"] / r["window_s"])
    assert idle_pct == pytest.approx(92.9346, abs=1e-3)
    # the device is idle all through both D2H transpositions, so their
    # spans' own durations are the gap time given to them
    host = [e for p in trace["planes"] if p["name"].startswith("/host:")
            for ln in p["lines"] for e in ln["events"]]
    d2h = sum(e[2] for e in host if e[0] == "XlaDelinearize")
    gaps = dict(r["idle_gaps"])
    assert gaps["XlaDelinearize"] == pytest.approx(d2h * 1e-9, rel=1e-6)
    assert r["idle_gaps"][0][0] == "XlaDelinearize"
    # ten names at the most; what is left out is small (the 909 ns
    # between the ops of the two programs among it)
    assert len(r["idle_gaps"]) == 10
    assert sum(gaps.values()) >= 0.999 * (r["window_s"] - r["busy_s"])
    assert sum(gaps.values()) <= r["window_s"] - r["busy_s"] + 1e-12
