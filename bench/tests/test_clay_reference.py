"""The plain Clay reference (`bench/reference/clay_codeword.py`) against
the program's codec at k=8 m=4 d=11: it accepts the codec's codewords on
seeded data and refuses a byte flipped in any of the 12 rows, a codeword
encoded with another gamma, data rows of another object, and a row that
is missing."""

import numpy as np
import pytest

from bench.reference import clay_codeword, crc32c, recovered_pool

K, M, D = 8, 4, 11
UNIT = 64                       # a stripe unit a data row takes in turn
ROW = 64 * 64                   # 64 sub-chunks of 64 bytes


def codec(gamma=2):
    from ceph_tpu.ec.registry import factory
    return factory(f"plugin=clay k={K} m={M} d={D} gamma={gamma}")


def codeword(payload: bytes, gamma=2) -> list:
    data = recovered_pool.data_rows(payload, K, UNIT)
    parity = np.asarray(codec(gamma).encode_chunks(data[None]))[0]
    return list(np.concatenate([data, parity]))


@pytest.fixture(scope="module")
def seeded():
    rng = np.random.default_rng(3801)
    payloads = [rng.integers(0, 256, K * ROW, np.uint8).tobytes()
                for _ in range(2)]
    return payloads, [codeword(p) for p in payloads]


def test_the_grid_of_the_cells_geometry():
    assert clay_codeword.grid(K, M, D) == (4, 3, 0)


def test_the_codecs_codewords_pass(seeded):
    payloads, words = seeded
    for payload, rows in zip(payloads, words):
        assert clay_codeword.check(payload, rows, K, M, D, UNIT) == {
            "data_wrong": [], "planes_wrong": 0}


@pytest.mark.parametrize("slot", range(K + M))
def test_a_byte_flipped_in_any_row_is_refused(seeded, slot):
    payloads, words = seeded
    rows = [r.copy() for r in words[0]]
    rows[slot][ROW // 3] ^= 0x40
    got = clay_codeword.check(payloads[0], rows, K, M, D, UNIT)
    assert got["planes_wrong"] >= 1
    assert got["data_wrong"] == ([slot] if slot < K else [])


def test_a_codeword_of_another_gamma_is_refused(seeded):
    payloads, _ = seeded
    rows = codeword(payloads[0], gamma=3)
    got = clay_codeword.check(payloads[0], rows, K, M, D, UNIT)
    assert got["data_wrong"] == [] and got["planes_wrong"] >= 1
    # ... and it is a codeword of its own gamma
    assert clay_codeword.parity_failures(np.stack(rows), K, M, D,
                                         gamma=3) == 0


def test_a_row_rebuilt_from_another_object_is_refused(seeded):
    payloads, words = seeded
    rows = list(words[0])
    rows[5] = words[1][5]
    got = clay_codeword.check(payloads[0], rows, K, M, D, UNIT)
    assert got["data_wrong"] == [5] and got["planes_wrong"] >= 1


def test_a_missing_row_is_no_codeword(seeded):
    payloads, words = seeded
    rows = list(words[0])
    rows[K + 1] = None
    assert clay_codeword.check(payloads[0], rows, K, M, D, UNIT)[
        "planes_wrong"] is None


def test_the_crcs_are_ceph_crc32c_of_the_rows(seeded):
    _, words = seeded
    rows = np.stack(words[0])
    assert list(clay_codeword.crcs(rows)) == list(
        crc32c.crc32c_rows(0xFFFFFFFF, rows))


def test_gamma_one_does_not_invert():
    with pytest.raises(ValueError, match="gamma"):
        clay_codeword.uncouple(np.zeros((12, 64, 1), np.uint8), 4, 3, gamma=1)
