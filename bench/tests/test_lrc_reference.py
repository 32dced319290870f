"""The plain LRC reference (`bench/reference/lrc_codeword.py`) against
the program's codec at k=8 m=4 l=3: it lays out the codec's mapping,
accepts the codec's codewords on seeded data, refuses a byte flipped in
any of the 16 rows, rows of another object and a missing row, and
rebuilds every row from the other members of its group."""

import numpy as np
import pytest

from bench.reference import crc32c, lrc_codeword, recovered_pool

K, M, L = 8, 4, 3
N = 16
UNIT = 64
ROW = UNIT * 32


def codec():
    from ceph_tpu.ec.registry import factory
    return factory(f"plugin=lrc k={K} m={M} l={L}")


def codeword(payload: bytes) -> list:
    """The codec's 16 rows by position (= slot)."""
    coder = codec()
    data = recovered_pool.data_rows(payload, K, UNIT)
    parity = np.asarray(coder.encode_chunks(data[None]))[0]
    rows = [None] * N
    for j, p in enumerate(coder.get_chunk_mapping()):
        rows[p] = data[j] if j < K else parity[j - K]
    return rows


@pytest.fixture(scope="module")
def seeded():
    rng = np.random.default_rng(4011)
    payloads = [rng.integers(0, 256, K * ROW, np.uint8).tobytes()
                for _ in range(2)]
    return payloads, [codeword(p) for p in payloads]


def test_the_layout_of_the_cells_geometry():
    data, layers = lrc_codeword.layout(K, M, L)
    assert lrc_codeword.mapping(K, M, L) == "__DD__DD__DD__DD" \
        == codec().mapping
    assert data == (2, 3, 6, 7, 10, 11, 14, 15)
    assert layers[0] == (data, (1, 5, 9, 13))
    assert layers[1:] == (((1, 2, 3), (0,)), ((5, 6, 7), (4,)),
                          ((9, 10, 11), (8,)), ((13, 14, 15), (12,)))
    with pytest.raises(ValueError, match="multiple of"):
        lrc_codeword.layout(4, 3, 3)


def test_the_codecs_codewords_pass(seeded):
    payloads, words = seeded
    for payload, rows in zip(payloads, words):
        assert lrc_codeword.check(payload, rows, K, M, L, UNIT) == []
        np.testing.assert_array_equal(
            lrc_codeword.codeword(payload, K, M, L, UNIT), np.stack(rows))


@pytest.mark.parametrize("slot", range(N))
def test_a_byte_flipped_in_any_row_is_refused(seeded, slot):
    payloads, words = seeded
    rows = [r.copy() for r in words[0]]
    rows[slot][ROW // 3] ^= 0x40
    assert lrc_codeword.check(payloads[0], rows, K, M, L, UNIT) == [slot]


@pytest.mark.parametrize("slot", range(N))
def test_every_row_is_rebuilt_from_its_group(seeded, slot):
    _, words = seeded
    np.testing.assert_array_equal(
        lrc_codeword.rebuilt(words[0], slot, K, M, L), words[0][slot])
    ins, parity = lrc_codeword.group_of(slot, K, M, L)
    assert len(ins) == L and slot in ins + (parity,)


def test_a_row_rebuilt_from_another_objects_group_is_refused(seeded):
    payloads, words = seeded
    rows = list(words[0])
    rows[5] = lrc_codeword.rebuilt(words[1], 5, K, M, L)
    assert lrc_codeword.check(payloads[0], rows, K, M, L, UNIT) == [5]


def test_a_missing_row_is_no_codeword(seeded):
    payloads, words = seeded
    rows = list(words[0])
    rows[K + 1] = None
    assert lrc_codeword.check(payloads[0], rows, K, M, L, UNIT) == [K + 1]


def test_the_crcs_are_ceph_crc32c_of_the_rows(seeded):
    _, words = seeded
    rows = np.stack(words[0])
    assert list(lrc_codeword.crcs(rows)) == list(
        crc32c.crc32c_rows(0xFFFFFFFF, rows))
