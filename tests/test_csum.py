"""Checksum subsystem tests.

Mirrors the reference's checksum test tiers: known-vector pinning
(ref: src/test/common/test_crc32c.cc style), oracle-vs-kernel
bit-exactness sweeps, and Checksummer calculate/verify semantics
(ref: src/test/objectstore/ tests of BlueStore _verify_csum behavior).
"""

import numpy as np
import pytest

from ceph_tpu.csum import (CSUM_ALGORITHMS, Checksummer, ceph_crc32c, crc32c,
                           xxh32, xxh64)
from ceph_tpu.csum.kernels import crc32c_blocks, xxh32_blocks, xxh64_blocks
from ceph_tpu.csum.reference import apply_shift


class TestKnownVectors:
    """Published vectors — pin the algorithms, not our own output."""

    def test_crc32c_rfc3720(self):
        # RFC 3720 B.4 test vectors
        assert crc32c(bytes(32)) == 0x8A9136AA
        assert crc32c(b"\xff" * 32) == 0x62A8AB43
        assert crc32c(bytes(range(32))) == 0x46DD794E
        assert crc32c(bytes(range(31, -1, -1))) == 0x113FDB5C

    def test_crc32c_check_string(self):
        assert crc32c(b"123456789") == 0xE3069283
        assert crc32c(b"") == 0
        assert crc32c(b"a") == 0xC1D04330

    def test_xxh32_vectors(self):
        assert xxh32(b"") == 0x02CC5D05
        assert xxh32(b"a") == 0x550D7456
        assert xxh32(b"abc") == 0x32D153FF

    def test_xxh64_vectors(self):
        assert xxh64(b"") == 0xEF46DB3751D8E999
        assert xxh64(b"a") == 0xD24EC4F1A98C6E5B
        assert xxh64(b"abc") == 0x44BC2CF5AD770999

    def test_xxh_seeded(self):
        # seed changes the hash; chaining sanity
        assert xxh32(b"abc", 1) != xxh32(b"abc", 0)
        assert xxh64(b"abc", 1) != xxh64(b"abc", 0)


class TestCephConvention:
    def test_chaining(self):
        a, b = b"hello ", b"world"
        assert ceph_crc32c(ceph_crc32c(5, a), b) == ceph_crc32c(5, a + b)

    def test_shift_is_zero_bytes(self):
        r = ceph_crc32c(0xDEADBEEF, b"xyz")
        for n in (0, 1, 7, 8, 9, 100, 4096):
            assert apply_shift(r, n) == ceph_crc32c(r, bytes(n))


def _registers(seed, rows):
    """ceph_crc32c(seed, row) of every row of (..., L), leading dims kept."""
    flat = rows.reshape(int(np.prod(rows.shape[:-1])), rows.shape[-1])
    return np.array([ceph_crc32c(seed, row.tobytes()) for row in flat],
                    dtype=np.uint32).reshape(rows.shape[:-1])


# 8*3, 8*37, 8*1000 + 5 and 65536 + 8: chunk counts that are odd at some
# level of the halving combine (and so take its front pad)
@pytest.mark.parametrize("length", [0, 1, 5, 8, 16, 63, 64, 100, 4096, 4099,
                                    8 * 3, 8 * 37, 8 * 1000 + 5, 65536 + 8])
def test_crc32c_kernel_matches_oracle(length):
    rng = np.random.default_rng(length)
    data = rng.integers(0, 256, size=(5, length), dtype=np.uint8)
    got = np.asarray(crc32c_blocks(data))
    want = np.array([crc32c(row.tobytes()) for row in data], dtype=np.uint32)
    np.testing.assert_array_equal(got, want)
    # ceph raw-register convention, seed -1 and seed 0 (the parity-delta
    # path's)
    for seed in (0xFFFFFFFF, 0):
        got = np.asarray(crc32c_blocks(data, init=seed, xorout=0))
        np.testing.assert_array_equal(got, _registers(seed, data))


@pytest.mark.parametrize("length", [8, 4096, 65536, 524288 + 3])
def test_crc32c_kernel_row_layouts_match_oracle(length):
    """The layout the TPU needs (contiguous byte planes, leading dims
    kept) at the served path's row counts: the 11 rows of one
    k=8 m=3 object, and a (batch, rebuilt rows, L) recovery stack. The
    longest length takes the unaligned path at a 512 KiB shard."""
    rng = np.random.default_rng(length)
    rows = rng.integers(0, 256, size=(11, length), dtype=np.uint8)
    want = _registers(0xFFFFFFFF, rows)
    got = np.asarray(crc32c_blocks(rows, init=0xFFFFFFFF, xorout=0))
    np.testing.assert_array_equal(got, want)
    stack = rows[:6].reshape(3, 2, length)
    got = np.asarray(crc32c_blocks(stack, init=0xFFFFFFFF, xorout=0))
    np.testing.assert_array_equal(got, want[:6].reshape(3, 2))


@pytest.mark.parametrize("n_rows", [1, 8, 11, 16, 17])
@pytest.mark.parametrize("init, xorout", [(0xFFFFFFFF, 0xFFFFFFFF),
                                          (0x1234ABCD, 0), (0, 0)])
def test_crc32c_kernel_row_counts_match_oracle(n_rows, init, xorout):
    """Row counts on both sides of the tile heights (8 and 16 rows),
    in both conventions, at a length (8 * 37) whose lane count is odd
    at four of the combine's six levels."""
    rng = np.random.default_rng(n_rows)
    rows = rng.integers(0, 256, size=(n_rows, 8 * 37), dtype=np.uint8)
    got = np.asarray(crc32c_blocks(rows, init=init, xorout=xorout))
    np.testing.assert_array_equal(got, _registers(init, rows) ^ xorout)


@pytest.mark.parametrize("lead", [(2,), (16,), (2, 3)])
@pytest.mark.parametrize("length", [8 * 37, 4096, 8 * 100 + 5])
def test_crc32c_kernel_keeps_leading_dims(lead, length):
    """(objects, 11 rows, L) stacks, as the batched fused write and the
    recover program send them: one value a row, leading dims kept,
    every object its own bytes."""
    rng = np.random.default_rng(length + len(lead))
    stack = rng.integers(0, 256, size=lead + (11, length), dtype=np.uint8)
    got = np.asarray(crc32c_blocks(stack, init=0xFFFFFFFF, xorout=0))
    np.testing.assert_array_equal(got, _registers(0xFFFFFFFF, stack))


@pytest.mark.parametrize("length", [0, 1, 3, 4, 15, 16, 17, 31, 32, 33, 100,
                                    4096])
def test_xxh_kernels_match_oracle(length):
    rng = np.random.default_rng(1000 + length)
    data = rng.integers(0, 256, size=(4, length), dtype=np.uint8)
    g32 = np.asarray(xxh32_blocks(data, seed=42))
    w32 = np.array([xxh32(row.tobytes(), 42) for row in data],
                   dtype=np.uint32)
    np.testing.assert_array_equal(g32, w32)
    g64 = np.asarray(xxh64_blocks(data, seed=42)).astype(np.uint64)
    g64v = (g64[:, 0] << np.uint64(32)) | g64[:, 1]
    w64 = np.array([xxh64(row.tobytes(), 42) for row in data],
                   dtype=np.uint64)
    np.testing.assert_array_equal(g64v, w64)


class TestChecksummer:
    @pytest.mark.parametrize("algo", CSUM_ALGORITHMS)
    def test_device_matches_host(self, algo):
        cs = Checksummer(algo, block_size=256)
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, size=8 * 256, dtype=np.uint8)
        np.testing.assert_array_equal(cs.calculate(data),
                                      cs.calculate(data, device=False))

    def test_verify_clean(self):
        cs = Checksummer("crc32c", block_size=128)
        data = np.arange(4 * 128, dtype=np.uint8) % 251
        assert cs.verify(data, cs.calculate(data)) == -1

    def test_verify_reports_first_bad_offset(self):
        cs = Checksummer("crc32c", block_size=128)
        rng = np.random.default_rng(3)
        data = rng.integers(0, 256, size=6 * 128, dtype=np.uint8)
        sums = cs.calculate(data)
        corrupt = data.copy()
        corrupt[2 * 128 + 5] ^= 0x40  # flip a bit in block 2
        corrupt[5 * 128] ^= 0x01      # and block 5
        assert cs.verify(corrupt, sums) == 2 * 128

    def test_truncated_variants(self):
        data = np.arange(512, dtype=np.uint8)
        full = Checksummer("crc32c", 256).calculate(data)
        np.testing.assert_array_equal(
            Checksummer("crc32c_16", 256).calculate(data), full & 0xFFFF)
        np.testing.assert_array_equal(
            Checksummer("crc32c_8", 256).calculate(data), full & 0xFF)

    def test_bad_sizes_rejected(self):
        cs = Checksummer("crc32c", block_size=128)
        with pytest.raises(ValueError):
            cs.calculate(np.zeros(100, np.uint8))
        with pytest.raises(ValueError):
            Checksummer("nope", 128)

    def test_value_sizes(self):
        assert Checksummer("crc32c", 4096).csum_value_size == 4
        assert Checksummer("crc32c_16", 4096).csum_value_size == 2
        assert Checksummer("crc32c_8", 4096).csum_value_size == 1
        assert Checksummer("xxhash64", 4096).csum_value_size == 8


class TestCrc32cExtend:
    """crc32c_extend buckets block length to powers of two and undoes the
    zero-padding shift — must match serial ceph_crc32c for ANY length."""

    def test_arbitrary_lengths_match_serial(self):
        import numpy as np
        from ceph_tpu.csum.kernels import crc32c_extend
        from ceph_tpu.csum.reference import ceph_crc32c
        rng = np.random.default_rng(11)
        for L in [1, 2, 3, 7, 13, 63, 64, 65, 100, 257, 1000]:
            blocks = rng.integers(0, 256, size=(3, L), dtype=np.uint8)
            regs = rng.integers(0, 1 << 32, size=3, dtype=np.uint32)
            got = np.asarray(crc32c_extend(regs, blocks))
            want = [ceph_crc32c(int(r), b) for r, b in zip(regs, blocks)]
            assert got.tolist() == want, L

    # (37, 91): neither is a bucket; (64, 4096): both are; (256, 8 * 37)
    # and (8 * 1000 + 5, 128): one of each, either way round
    @pytest.mark.parametrize("len_a, len_b", [(37, 91), (64, 4096),
                                              (256, 8 * 37),
                                              (8 * 1000 + 5, 128)])
    def test_chaining(self, len_a, len_b):
        import numpy as np
        from ceph_tpu.csum.kernels import crc32c_extend
        from ceph_tpu.csum.reference import ceph_crc32c
        rng = np.random.default_rng(12)
        a = rng.integers(0, 256, size=(2, len_a), dtype=np.uint8)
        b = rng.integers(0, 256, size=(2, len_b), dtype=np.uint8)
        regs = np.array([0xFFFFFFFF, 0x1234ABCD], np.uint32)
        step = crc32c_extend(crc32c_extend(regs, a), b)
        joined = crc32c_extend(regs, np.concatenate([a, b], axis=1))
        whole = [ceph_crc32c(int(regs[i]), np.concatenate([a[i], b[i]]))
                 for i in range(2)]
        assert np.asarray(step).tolist() == whole
        assert np.asarray(joined).tolist() == whole
