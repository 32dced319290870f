"""Each driver's setup / warm / window / verify at a tiny size on the
CPU, through `run_cell` (everything of a run but the look for a chip).

Sound runs come out correct; the controls (the reference with one
guarantee broken) and the faults (the timed path broken underneath)
come out not correct. Two stand-ins make a CPU run able to read
correct at all, and are the only ones: the EC backend's host-encode
shortcut for the CPU backend is switched off, so that the fused device
program runs (on XLA's CPU backend), and the ecbench driver's reading
of the device's peak memory, which the CPU backend does not keep, is
given a number. No metric of such a run is printed or kept."""

import numpy as np
import pytest

import controls
from tiny import ECBENCH, RADOS, SEED, failed, json_line, run_tiny as _run, tiny


@pytest.fixture
def device_path(monkeypatch):
    from ceph_tpu.osd import ecbackend
    monkeypatch.setattr(ecbackend, "_host_crc_available", lambda: False)


# -- sound runs ---------------------------------------------------------

@pytest.mark.parametrize("name", ECBENCH)
def test_ecbench_sound_run_is_correct(name, monkeypatch):
    _, _, _, driver = tiny(name)
    monkeypatch.setattr(driver, "device_peak_bytes", lambda: 1 << 40)
    result, checks = _run(name, driver)
    assert failed(checks) == set()
    line = json_line(result)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "compared"
    assert set(line["metrics"]) == {"codec_gb_s", "setup_s"}


@pytest.mark.parametrize("name", ECBENCH)
def test_ecbench_without_a_device_counter_is_not_correct(name):
    result, checks = _run(name)
    assert failed(checks) == {"device_peak_bytes"}
    assert result["correct"] is False


@pytest.mark.parametrize("name", RADOS)
def test_rados_sound_run_is_correct(name, device_path):
    result, checks = _run(name, seconds=1.5)
    assert failed(checks) == set()
    line = json_line(result)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"client_mb_s", "op_p95_ms", "setup_s"}
    # every object written and every timed read is compared
    written = line["attempted"] if name == RADOS[0] else 8
    assert line["compared"]["objects_compared"]["value"] >= written - 4
    if name != RADOS[0]:
        assert (line["compared"]["timed_reads_compared"]["value"]
                >= line["attempted"])


def test_rados_seq_traced_with_an_idle_device_is_not_correct(device_path):
    # no counter of the program counts the read path's launch: a traced
    # run holds the device's busy time to the least the reads need
    _, workload, config, driver = tiny("rados_seq_4m_t16")
    state = driver.setup(config, workload, SEED, print)
    try:
        driver.warm(state, print)
        run = driver.window(state, 1.0, lambda: None, print)
        run.update(peaks={"hbm_bytes_per_s": 819e9}, traced_work_bytes=
                   driver.work_bytes(config, workload, 10))
        busy = driver.observe(state, dict(run, trace={"busy_s": 0.5}))
        idle = driver.observe(state, dict(run, trace={"busy_s": 0.0}))
    finally:
        driver.close(state, print)
    assert failed(driver.compare(config, workload, busy)) == set()
    assert failed(driver.compare(config, workload, idle)) == {
        "device_busy_s_traced"}


def test_rados_hung_loop_is_a_failed_op_in_the_tail(device_path, monkeypatch):
    """A loop that never answers counts as a failed op of the window's
    length in the latency sample, not only in `failed`."""
    import threading
    from ceph_tpu.osd.standalone import Client
    real, never = Client.write, threading.Event()
    state = {"n": 0}

    def hangs_once(self, objects):
        state["n"] += 1
        if state["n"] == 12:
            never.wait(600)
        return real(self, objects)
    monkeypatch.setattr(Client, "write", hangs_once)
    cell, workload, config, driver = tiny("rados_write_4m_t16")
    monkeypatch.setattr(driver, "HUNG_AFTER_S", 1.0)
    st = driver.setup(config, workload, SEED, print)
    try:
        driver.warm(st, print)
        run = driver.window(st, 1.0, lambda: None, print)
    finally:
        never.set()
        driver.close(st, print)
    assert run["failed"] == 1
    lost = [op for op in run["ops"] if not op["ok"]]
    assert len(lost) == 1 and lost[0]["start"] >= run["t0"]
    from bench import run as harness
    p95 = harness.load_module("end_to_end", "op_p95_ms").compute(
        dict(run, ops=lost))
    assert p95 == pytest.approx(run["window_s"] * 1e3)


def _suspect_a_peer(state):
    """Every PG's primary holds the last OSD of its acting set for
    unreachable, as a probe that timed out leaves it."""
    cluster, osdmap = state["cluster"], state["client"].osdmap
    for pg in range(state["config"]["cluster"]["pg_num"]):
        acting = osdmap.pg_to_up_acting_osds(1, pg)[2]
        cluster.osds[acting[0]].suspect.add(acting[-1])


@pytest.mark.parametrize("name", RADOS)
def test_rados_boot_that_suspects_a_peer_boots_again(name, device_path,
                                                     monkeypatch):
    """A stall during the boot's peering leaves a primary suspecting a
    live peer, and the pool would ack writes with a shard short: set-up
    boots again and hands over a whole pool."""
    _, _, _, driver = tiny(name)
    real, boots = driver._boot, []

    def first_boot_stalls(state, log):
        real(state, log)
        boots.append(1)
        if len(boots) == 1:
            _suspect_a_peer(state)
    monkeypatch.setattr(driver, "_boot", first_boot_stalls)
    result, checks = _run(name, driver, seconds=1.0)
    assert failed(checks) == set() and len(boots) == 2
    notes = json_line(result)["notes"]
    assert notes["boots"] == 2 and len(notes["suspected_in_set_up"]) == 1
    assert notes["suspected_after_window"] == []
    assert list(json_line(result))[-1] == "compared"


def test_rados_suspicion_in_the_window_is_not_correct(device_path,
                                                      monkeypatch):
    """Once a primary suspects a peer inside the window, the program acks
    objects with k+m-1 shards: not what the configuration guarantees."""
    name = RADOS[0]
    _, _, _, driver = tiny(name)
    real = driver.window

    def stalls(state, seconds, tick, log):
        _suspect_a_peer(state)
        return real(state, seconds, tick, log)
    monkeypatch.setattr(driver, "window", stalls)
    result, checks = _run(name, driver, seconds=1.0)
    assert {"shards_missing", "stored_rows_wrong"} <= failed(checks)
    assert result["correct"] is False
    assert result["notes"]["suspected_after_window"]


@pytest.mark.parametrize("name", RADOS[:1])
def test_rados_host_encode_is_not_correct(name):
    # as the program runs on the CPU backend: the native host codec
    # serves the write path, and verify() has to refuse that
    result, checks = _run(name)
    assert "host_encode_launches" in failed(checks)
    assert result["correct"] is False


# -- faults: the timed path broken underneath ---------------------------

@pytest.mark.parametrize("name", ECBENCH)
def test_ecbench_altered_parity_is_not_correct(name, monkeypatch):
    from ceph_tpu.ec.rs import ReedSolomon
    real = ReedSolomon.encode_chunks

    def altered(self, data):
        parity = np.array(real(self, data))
        parity[0, 0, 0] ^= 1
        return parity
    monkeypatch.setattr(ReedSolomon, "encode_chunks", altered)
    _, _, _, driver = tiny(name)
    monkeypatch.setattr(driver, "device_peak_bytes", lambda: 1 << 40)
    result, checks = _run(name, driver)
    assert failed(checks) == {"parity_rows_wrong"}
    assert result["correct"] is False


def test_rados_write_altered_parity_is_not_correct(device_path, monkeypatch):
    from ceph_tpu.osd.ecbackend import ECBackend
    real = ECBackend._encode_shards_with_crcs

    def altered(self, data_shards, sl):
        shards, crcs = real(self, data_shards, sl)
        shards = np.array(shards)
        shards[0, -1, 0] ^= 1            # one byte of the last parity row
        return shards, crcs
    monkeypatch.setattr(ECBackend, "_encode_shards_with_crcs", altered)
    result, checks = _run("rados_write_4m_t16", seconds=1.5)
    # the crc the device took of the sound row still matches the
    # reference's: the altered row itself is what fails
    assert failed(checks) == {"stored_rows_wrong"}
    assert result["correct"] is False


def test_rados_seq_altered_read_is_not_correct(device_path, monkeypatch):
    from ceph_tpu.osd.standalone import Client
    real = Client.read

    def altered(self, name):
        got = bytearray(real(self, name))
        got[-1] ^= 1
        return bytes(got)
    monkeypatch.setattr(Client, "read", altered)
    result, checks = _run("rados_seq_4m_t16", seconds=1.5)
    assert failed(checks) == {"timed_reads_wrong", "readback_wrong"}
    assert result["correct"] is False


# -- controls: the reference with one guarantee broken ------------------

@pytest.mark.parametrize("name", ECBENCH)
def test_ecbench_controls_are_not_correct(name):
    _, workload, config, driver = tiny(name)
    state = driver.setup(config, workload, SEED, print)
    driver.warm(state, print)
    run = driver.window(state, 0.3, lambda: None, print)
    observed = driver.observe(state, run)
    assert failed(driver.compare(config, workload, observed)) == set()
    for what, control in controls.ecbench_controls(config, observed).items():
        assert "parity_rows_wrong" in failed(
            driver.compare(config, workload, control)), what


@pytest.mark.parametrize("name", RADOS)
def test_rados_controls_are_not_correct(name, device_path):
    _, workload, config, driver = tiny(name)
    state = driver.setup(config, workload, SEED, print)
    try:
        driver.warm(state, print)
        run = driver.window(state, 1.0, lambda: None, print)
        ob = driver.observe(state, run)
    finally:
        driver.close(state, print)
    assert failed(driver.compare(config, workload, ob)) == set()
    made = controls.rados_controls(config, driver, ob)
    assert failed(driver.compare(config, workload,
                                 made.pop("_sound_reference"))) == set()
    expect = {"cauchy_orig_parity": "stored_rows_wrong",
              "crc_seed_zero": "stored_crcs_wrong",
              "ack_before_last_shard": "stored_rows_wrong",
              "stale_read": "readback_wrong"}
    for what, control in made.items():
        bad = failed(driver.compare(config, workload, control))
        assert expect[what] in bad, (what, bad)
        if what == "ack_before_last_shard":
            assert "shards_missing" in bad
        if what == "stale_read" and workload["op"] == "read":
            assert "timed_reads_wrong" in bad
