"""Device-kernel correctness: both lowerings bit-exact vs the numpy oracle,
and the rule that picks between them.

The rebuild's analog of TestErasureCode round-trip tests (ref:
src/test/erasure-code/TestErasureCode*.cc: encode random buffers, erase
every <= m subset, decode, byte-compare — SURVEY.md §4 tier 1).
"""

from itertools import combinations

import numpy as np
import pytest

from ceph_tpu.ec.matrices import reed_sol_van_matrix
from ceph_tpu.ec.registry import factory
from ceph_tpu.gf import numpy_ref as R
from ceph_tpu.ops import rs_kernels as K

LOWERINGS = [K._apply_bitlinear, K._apply_mxu]
_ids = [f.__name__ for f in LOWERINGS]


def _rand(b, k, L, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=(b, k, L),
                                                dtype=np.uint8)


@pytest.mark.parametrize("lowering", LOWERINGS, ids=_ids)
@pytest.mark.parametrize("k,m", [(4, 2), (8, 3), (2, 1), (8, 4), (10, 4)])
def test_encode_matches_oracle(lowering, k, m):
    mat = reed_sol_van_matrix(k, m)
    data = _rand(3, k, 256)
    want = R.encode_ref(mat, data)
    got = np.asarray(lowering(mat, data))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("lowering", LOWERINGS, ids=_ids)
def test_zero_and_identity_rows(lowering):
    # degenerate coefficients exercise the zero-skip paths
    mat = np.array([[0, 0, 0], [1, 0, 0], [2, 3, 0]], dtype=np.uint8)
    data = _rand(2, 3, 128, seed=1)
    want = R.encode_ref(mat, data)
    got = np.asarray(lowering(mat, data))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("lowering", LOWERINGS, ids=_ids)
def test_decode_roundtrip_all_erasure_patterns(lowering):
    k, m = 4, 2
    mat = reed_sol_van_matrix(k, m)
    data = _rand(2, k, 128, seed=2)
    parity = R.encode_ref(mat, data)
    chunks_all = {i: data[:, i, :] for i in range(k)}
    chunks_all.update({k + i: parity[:, i, :] for i in range(m)})
    for nerased in (1, 2):
        for erased in combinations(range(k + m), nerased):
            have = {i: v for i, v in chunks_all.items() if i not in erased}
            D = R.decode_matrix(mat, list(erased), k)
            survivors = sorted(have)[:k]
            stack = np.stack([have[s] for s in survivors], axis=1)
            rec = np.asarray(lowering(D, stack))
            for idx, e in enumerate(erased):
                np.testing.assert_array_equal(rec[:, idx, :], chunks_all[e],
                                              err_msg=f"erased={erased}")


# -- the rule: which lowering a matrix gets --------------------------------

def _cell_matrix(what):
    """The matrices the benchmark's cells compile (jerasure
    reed_sol_van k=8 m=3): encode, a decode for one to three erasures,
    an RMW delta's columns."""
    coder = factory("plugin=jerasure technique=reed_sol_van k=8 m=3")
    if what == "encode":
        return coder.matrix
    if what == "delta":
        return coder.delta_matrix((1, 6))
    lost = {"decode1": [3], "decode2": [0, 9], "decode3": [2, 5, 10]}[what]
    surv = [i for i in range(11) if i not in lost][:8]
    return R.decode_matrix(coder.matrix, lost, 8, surv)


@pytest.mark.parametrize(
    "what", ["encode", "decode1", "decode2", "decode3", "delta"])
def test_rule_unrolls_every_matrix_of_the_cells(what):
    mat = _cell_matrix(what)
    assert mat.size <= 24
    assert K._lowering(mat) is K._apply_bitlinear


@pytest.mark.parametrize("profile,shape", [
    ("plugin=clay k=4 m=2 d=5", (16, 32)),
    ("plugin=clay k=8 m=4 d=11", (256, 512)),
])
def test_rule_takes_clays_solved_matrices_dense(profile, shape):
    """Through the unrolled form the k=8 m=4 d=11 matrix is 4,096
    traced terms a parity row and its first encode does not finish in
    minutes: that the default path builds and encodes here is the
    rule at work."""
    clay = factory(profile)
    D, _ = clay._affine_decode(tuple(range(clay.k, clay.k + clay.m)),
                               tuple(range(clay.k)))
    assert D.shape == shape
    assert K._lowering(D) is K._apply_mxu
    data = _rand(1, clay.k, clay.sub_chunk_count * 128, seed=5)
    stacked = data.reshape(1, clay.k * clay.sub_chunk_count, 128)
    want = R.encode_ref(D, stacked).reshape(1, clay.m, -1)
    np.testing.assert_array_equal(clay.encode_chunks(data), want)


@pytest.mark.parametrize("side", ["unrolled", "dense"])
def test_make_encoder_each_side_of_the_threshold(side):
    k = K._UNROLL_MAX_ENTRIES // 8 + (side == "dense")   # 8 rows
    mat = np.random.default_rng(6).integers(0, 256, (8, k), np.uint8)
    assert K._lowering(mat) is (K._apply_bitlinear if side == "unrolled"
                                else K._apply_mxu)
    data = _rand(2, k, 128, seed=7)
    np.testing.assert_array_equal(np.asarray(K.make_encoder(mat)(data)),
                                  R.encode_ref(mat, data))


def test_make_encoder_caches():
    mat = reed_sol_van_matrix(4, 2)
    # the jitted program is cached by matrix bytes; the default
    # bucketing wrapper is a thin lambda over that shared program
    assert K.make_encoder(mat, bucket_batch=False) \
        is K.make_encoder(mat.copy(), bucket_batch=False)
    assert K._make_jitted(mat.tobytes(), 2, 4) \
        is K._make_jitted(mat.copy().tobytes(), 2, 4)


def test_bucketed_encoder_matches_exact():
    mat = reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(3)
    for B in (1, 3, 5, 8):
        d = rng.integers(0, 256, (B, 4, 512), np.uint8)
        a = np.asarray(K.make_encoder(mat)(d))               # bucketed
        b = np.asarray(K.make_encoder(mat, bucket_batch=False)(d))
        assert np.array_equal(a, b), B


# ------------------------------------------------------------ the host face

def _face_matrix(which):
    """The pool's 3 x 8 coding matrix, a 1 x k row of it, one of its
    decode matrices (two erasures, a parity row among the helpers)."""
    mat = reed_sol_van_matrix(8, 3)
    if which == "row":
        return mat[1:2]
    if which == "decode":
        return R.decode_matrix(mat, [0, 9], 8, [1, 2, 3, 4, 5, 6, 7, 8])
    return mat


@pytest.mark.parametrize("B", [1, 3, 32])
@pytest.mark.parametrize("L", [1, 3, 4, 7, 512, 4096 + 1])
@pytest.mark.parametrize("which", ["pool", "row", "decode"])
def test_host_face_matches_oracle(which, L, B):
    """Host rows in, host rows out, byte for byte the numpy oracle: odd
    totals, the pad to a whole row of words and the slice off the view,
    the batch's bucket and its slice are all met."""
    mat = _face_matrix(which)
    data = _rand(B, mat.shape[1], L, seed=L + B)
    got = K.make_host_encoder(mat)(data)
    assert isinstance(got, np.ndarray) and got.dtype == np.uint8
    assert got.shape == (B, mat.shape[0], L)
    assert got.flags.c_contiguous
    np.testing.assert_array_equal(got, R.encode_ref(mat, data))


@pytest.mark.parametrize("lowering", LOWERINGS, ids=_ids)
@pytest.mark.parametrize("k,m", [(8, 3), (4, 2), (10, 4)])
def test_lowerings_on_words_match_bytes(lowering, k, m):
    """uint32 words (four bytes a lane) through either lowering give the
    words of what the bytes give: no byte leaves its place in a word."""
    mat = reed_sol_van_matrix(k, m)
    data = _rand(3, k, 256, seed=8)
    got = np.asarray(lowering(mat, data.view(np.uint32)))
    assert got.dtype == np.uint32 and got.shape == (3, m, 64)
    np.testing.assert_array_equal(got.view(np.uint8), R.encode_ref(mat, data))


def test_lowerings_refuse_other_lane_widths():
    mat = reed_sol_van_matrix(4, 2)
    for lowering in LOWERINGS:
        with pytest.raises(ValueError, match="uint8 bytes or uint32 words"):
            lowering(mat, np.zeros((1, 4, 8), np.uint16))


def test_host_face_dense_side():
    """A matrix on the dense side of the rule through the same face."""
    mat = reed_sol_van_matrix(20, 8)
    assert K._lowering(mat) is K._apply_mxu
    data = _rand(2, 20, 516, seed=9)
    np.testing.assert_array_equal(K.make_host_encoder(mat)(data),
                                  R.encode_ref(mat, data))


def test_host_face_takes_strided_rows_and_refuses_bad_shapes():
    mat = reed_sol_van_matrix(4, 2)
    face = K.make_host_encoder(mat)
    wide = _rand(2, 4, 1024, seed=10)
    np.testing.assert_array_equal(face(wide[:, :, ::2]),
                                  R.encode_ref(mat, wide[:, :, ::2]))
    with pytest.raises(ValueError, match="matrix expects 4"):
        face(_rand(1, 3, 8))
    with pytest.raises(ValueError, match=r"\(batch, k, L\)"):
        face(_rand(1, 4, 8)[0])


def test_host_face_compiles_once_a_shape(monkeypatch):
    mat = reed_sol_van_matrix(5, 2)
    before = K._make_jitted_words.cache_info()
    face = K.make_host_encoder(mat)
    jitted = K._make_jitted_words(mat.tobytes(), 2, 5)
    info = K._make_jitted_words.cache_info()
    assert (info.misses, info.hits) == (before.misses + 1, before.hits + 1)
    # L 500 and 512 share a row of words, B 3 and 4 a bucket: one program
    for B, L in [(3, 500), (4, 512), (3, 512), (4, 500)]:
        data = _rand(B, 5, L, seed=11)
        np.testing.assert_array_equal(face(data), R.encode_ref(mat, data))
    assert jitted._cache_size() == 1
    face(_rand(5, 5, 512))                     # bucket 8: a second program
    assert jitted._cache_size() == 2
    # the device-resident program is another function with its own cache
    assert K._make_jitted(mat.tobytes(), 2, 5) is not jitted
    # over the line: sub-batches of 2 rows, and the 4 (or 3 and a padded
    # one, or 3 of a bucket's 4) share ONE program of that shape
    monkeypatch.setattr(K, "_SUB_BATCH_BYTES", 2 * 5 * 512)
    for B in (8, 7, 5):
        data = _rand(B, 5, 512, seed=B)
        np.testing.assert_array_equal(face(data), R.encode_ref(mat, data))
    assert jitted._cache_size() == 3
    # under it again: the whole bucket's program, compiled before
    monkeypatch.undo()
    face(_rand(8, 5, 512))
    assert jitted._cache_size() == 3


@pytest.mark.parametrize("erased", [(0, 9), (3,)])
def test_decode_chunks_round_trip_through_the_face(erased):
    coder = factory("plugin=jerasure technique=reed_sol_van k=8 m=3")
    data = _rand(3, 8, 1027, seed=12)
    parity = coder.encode_chunks(data)
    np.testing.assert_array_equal(parity, R.encode_ref(coder.matrix, data))
    chunks = np.concatenate([data, parity], axis=1)
    have = {i: chunks[:, i, :] for i in range(11) if i not in erased}
    got = coder.decode_chunks(list(erased), have)
    for e in erased:
        assert got[e].dtype == np.uint8 and got[e].shape == (3, 1027)
        np.testing.assert_array_equal(got[e], chunks[:, e, :])
    # one row a chunk (no batch axis) takes the same face
    flat = coder.decode_chunks(list(erased),
                               {i: v[0] for i, v in have.items()})
    for e in erased:
        np.testing.assert_array_equal(flat[e], chunks[0, e, :])
    # the device-resident decoder is untouched: uint8 (B, E, L) on device
    surv = tuple(i for i in range(11) if i not in erased)[:8]
    dev = coder.batch_decoder(erased, surv)(chunks[:, list(surv), :])
    assert dev.dtype == np.uint8 and dev.shape == (3, len(erased), 1027)
    np.testing.assert_array_equal(np.asarray(dev), chunks[:, list(erased), :])


def test_host_face_zero_and_identity_rows():
    # a zero row has no term at all: its words are zeros, not missing
    mat = np.array([[0, 0, 0], [1, 0, 0], [2, 3, 0]], dtype=np.uint8)
    data = _rand(2, 3, 130, seed=13)
    np.testing.assert_array_equal(K.make_host_encoder(mat)(data),
                                  R.encode_ref(mat, data))


# sub-batch bytes, which matrix, B, L, rows strided: the line lowered so
# that a CPU run pipelines at a few KiB
_PIPELINED = [
    (8192, "pool", 8, 512, False),      # 4 sub-batches of 2, none partial
    (8192, "pool", 5, 512, False),      # bucket 8: 2 + 2 + a padded 1, the 4th not sent
    (8192, "pool", 3, 512, False),      # bucket 4: 2 + a padded 1
    (16384, "pool", 6, 700, False),     # L no multiple of 512: rows of 1,024
    (4096, "row", 12, 4, False),        # one object a sub-batch, 12 of a bucket's 16
    (8192, "decode", 7, 516, False),    # a decode matrix
    (8192, "pool", 6, 512, True),       # strided rows
    (32768, "pool", 16, 1024, False),   # B a power of two, S = 4
]


@pytest.mark.parametrize("sub_bytes,which,B,L,strided", _PIPELINED)
def test_host_face_pipelined_equals_single_launch(monkeypatch, sub_bytes,
                                                  which, B, L, strided):
    """A call over the line goes as sub-batches and gives, byte for
    byte, the oracle's parity and the single launch's."""
    mat = _face_matrix(which)
    k = mat.shape[1]
    data = _rand(B, k, 2 * L if strided else L, seed=B + L)
    if strided:
        data = data[:, :, ::2]
    face = K.make_host_encoder(mat)
    single = face(data)
    row_bytes = k * (L + -L % 512)
    monkeypatch.setattr(K, "_SUB_BATCH_BYTES", sub_bytes)
    sub = K._sub_batch_rows(K.pow2_bucket(B), row_bytes)
    assert 2 * sub <= K.pow2_bucket(B)
    before = K._codec_perf.dump()
    got = face(data)
    after = K._codec_perf.dump()
    assert after["host_face_pipelined_calls"] \
        == before["host_face_pipelined_calls"] + 1
    assert after["host_face_sub_batches"] \
        == before["host_face_sub_batches"] + -(-B // sub)
    assert isinstance(got, np.ndarray) and got.dtype == np.uint8
    assert got.shape == (B, mat.shape[0], L) and got.flags.c_contiguous
    np.testing.assert_array_equal(got, R.encode_ref(mat, data))
    np.testing.assert_array_equal(got, single)
    # the caller's own: no later call writes into it, nor is it the
    # operand's memory
    keep = got.copy()
    other = face(_rand(B, k, L, seed=99))
    assert not np.shares_memory(got, other)
    assert not np.shares_memory(got, data)
    np.testing.assert_array_equal(got, keep)


@pytest.mark.parametrize("rows,row_bytes,want", [
    (1, 4 << 20, 1),        # one served object: the single launch
    (2, 4 << 20, 2),        # 8 MiB: one sub-batch's worth
    (4, 4 << 20, 2),        # 16 MiB: two sub-batches of two objects
    (8, 4 << 20, 2),
    (32, 4 << 20, 2),       # the codec cell's call: 16 sub-batches
    (32, 4096, 32),         # 128 KiB in all: far under the line
    (8, 3 << 20, 4),        # rows that do not divide 8 MiB: 12 MiB a sub-batch
    (4, 16 << 20, 1),       # a row over the line: a row a sub-batch
    (1, 64 << 20, 1),       # nothing to split
])
def test_sub_batch_rule_from_the_shape_alone(rows, row_bytes, want):
    assert K._SUB_BATCH_BYTES == 8 << 20
    assert K._sub_batch_rows(rows, row_bytes) == want
