"""`work_bytes()` against hand counts: from the cells' shapes alone."""

import pytest

from tiny import cell_files


def test_rados_write():
    _, workload, config, driver = cell_files("rados_write_4m_t16")
    # 8 data rows read + 3 parity rows written, 512 KiB each, and one
    # 4-byte crc word per 4 KiB block of all 11 rows (128 blocks a row)
    per_op = 11 * 524288 + 11 * 128 * 4
    assert per_op == 5_772_800
    assert driver.work_bytes(config, workload, 1) == per_op
    assert driver.work_bytes(config, workload, 68) == 68 * per_op
    assert driver.work_bytes(config, workload, 0) == 0


def test_rados_seq():
    _, workload, config, driver = cell_files("rados_seq_4m_t16")
    # a healthy read passes the 8 data rows once for their crc
    assert driver.work_bytes(config, workload, 1) == 8 * 524288 == 4_194_304


def test_ecbench():
    _, workload, config, driver = cell_files("ecbench_encode_4m_b32")
    # 32 objects a call, each 8 rows in and 3 rows out of 512 KiB
    assert driver.work_bytes(config, workload, 1) == 32 * 11 * 524288 \
        == 184_549_376
    assert driver.work_bytes(config, workload, 37) == 37 * 184_549_376


def test_roofline_arithmetic():
    from bench.stats import bandwidth_roofline_pct
    # 184,549,376 bytes at 819e9 B/s are 0.2253 ms; of 8.531 ms busy: 2.64%
    assert bandwidth_roofline_pct(184_549_376, 819e9, 8.531e-3) == \
        pytest.approx(2.6414, abs=1e-3)
    assert bandwidth_roofline_pct(0, 819e9, 1.0) is None
    assert bandwidth_roofline_pct(10, 819e9, 0.0) is None


def test_every_seed_writes_the_same_names_in_another_order():
    from bench import run as harness
    rados = harness.load_module("drivers", "rados")
    order = {seed: [rados._shuffled({"seed": seed}, n) for n in range(192)]
             for seed in (7, 8)}
    again = [rados._shuffled({"seed": 7}, n) for n in range(192)]
    assert order[7] == again and order[7] != order[8]
    for lo in range(0, 192, rados.SHUFFLE):
        block = slice(lo, lo + rados.SHUFFLE)
        assert sorted(order[7][block]) == sorted(order[8][block]) == list(
            range(lo, lo + rados.SHUFFLE))
