"""Chunk-tile streaming tests (SURVEY §2.7 P7): device-side lax.map
tiling and host-side double-buffered streaming must be byte-exact vs
the one-shot kernel and the numpy oracle, for encode AND decode."""

import numpy as np
import pytest

from ceph_tpu.ec.matrices import reed_sol_van_matrix
from ceph_tpu.gf.numpy_ref import decode_matrix, encode_ref
from ceph_tpu.ops.rs_kernels import make_encoder
from ceph_tpu.ops.streaming import StreamingCodec, make_tiled_encoder

K, M = 4, 2


def data(B=2, L=1 << 16, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 256, (B, K, L), dtype=np.uint8)


class TestTiledEncoder:
    def test_matches_oneshot_and_oracle(self):
        mat = reed_sol_van_matrix(K, M)
        d = data(L=1 << 15)
        tiled = np.asarray(make_tiled_encoder(mat, tile=1 << 12)(d))
        oneshot = np.asarray(make_encoder(mat)(d))
        assert np.array_equal(tiled, oneshot)
        want = np.stack([encode_ref(mat, d[b]) for b in range(len(d))])
        assert np.array_equal(tiled, want)

    def test_rejects_ragged_length(self):
        mat = reed_sol_van_matrix(K, M)
        with pytest.raises(ValueError, match="multiple"):
            make_tiled_encoder(mat, tile=1 << 12)(
                data(L=(1 << 12) + 100))


class TestStreamingCodec:
    def test_encode_matches_oracle_exact_tiles(self):
        mat = reed_sol_van_matrix(K, M)
        sc = StreamingCodec(mat, tile=1 << 13)
        d = data(L=1 << 15, seed=1)
        got = sc.encode(d)
        want = np.stack([encode_ref(mat, d[b]) for b in range(len(d))])
        assert np.array_equal(got, want)

    def test_ragged_tail_exact(self):
        mat = reed_sol_van_matrix(K, M)
        sc = StreamingCodec(mat, tile=1 << 12)
        d = data(L=(1 << 12) * 3 + 777, seed=2)
        got = sc.encode(d)
        want = np.stack([encode_ref(mat, d[b]) for b in range(len(d))])
        assert np.array_equal(got, want)

    def test_single_small_object(self):
        mat = reed_sol_van_matrix(K, M)
        sc = StreamingCodec(mat, tile=1 << 12)
        d = data(B=1, L=100, seed=3)
        got = sc.encode(d)
        want = encode_ref(mat, d[0])[None]
        assert np.array_equal(got, want)

    def test_streaming_decode_roundtrip(self):
        # decode is the same streamed matmul with a decode matrix
        mat = reed_sol_van_matrix(K, M)
        d = data(L=(1 << 12) * 2 + 19, seed=4)
        parity = StreamingCodec(mat, tile=1 << 12).encode(d)
        erasures = [1, K]  # one data, one parity shard
        survivors = [i for i in range(K + M) if i not in erasures][:K]
        D = decode_matrix(mat, erasures, K, survivors)
        full = np.concatenate([d, parity], axis=1)
        surv = full[:, survivors]
        rebuilt = StreamingCodec(D, tile=1 << 12).encode(surv)
        assert np.array_equal(rebuilt, full[:, erasures])

    def test_larger_than_tile_budget(self):
        # 3 MiB chunks through 256 KiB tiles: 12 tiles, depth 2 ->
        # never more than 2 tiles in flight; output byte-exact
        mat = reed_sol_van_matrix(K, M)
        sc = StreamingCodec(mat, tile=1 << 18, depth=2)
        d = data(B=1, L=3 << 20, seed=5)
        got = sc.encode(d)
        want = encode_ref(mat, d[0])[None]
        assert np.array_equal(got, want)

    def test_preallocated_out_and_bad_shapes(self):
        mat = reed_sol_van_matrix(K, M)
        sc = StreamingCodec(mat, tile=1 << 12)
        d = data(B=2, L=5000, seed=6)
        out = np.empty((2, M, 5000), dtype=np.uint8)
        got = sc.encode(d, out=out)
        assert got is out
        with pytest.raises(ValueError):
            sc.encode(d[:, :3])  # wrong shard count
        with pytest.raises(ValueError):
            sc.encode(d, out=np.empty((2, M, 4999), dtype=np.uint8))
