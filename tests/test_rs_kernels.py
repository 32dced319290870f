"""Device-kernel correctness: every lowering bit-exact vs the numpy oracle.

The rebuild's analog of TestErasureCode round-trip tests (ref:
src/test/erasure-code/TestErasureCode*.cc: encode random buffers, erase
every <= m subset, decode, byte-compare — SURVEY.md §4 tier 1).
"""

from itertools import combinations

import numpy as np
import pytest

from ceph_tpu.ec.matrices import reed_sol_van_matrix
from ceph_tpu.gf import numpy_ref as R
from ceph_tpu.ops import rs_kernels as K

IMPLS = ["bitlinear", "mxu", "logexp", "pallas"]


def _apply(mat, data, impl):
    if impl == "pallas":
        # no TPU under the tests: the kernel's interpreter, asked for by
        # name (the served path never interprets)
        from ceph_tpu.ops.pallas_gf import apply_matrix_pallas
        return apply_matrix_pallas(mat, data, interpret=True)
    return K.apply_matrix(mat, data, impl=impl)


def _rand(b, k, L, seed=0):
    return np.random.default_rng(seed).integers(0, 256, size=(b, k, L),
                                                dtype=np.uint8)


@pytest.mark.parametrize("impl", IMPLS)
@pytest.mark.parametrize("k,m", [(4, 2), (8, 3)])
def test_encode_matches_oracle(impl, k, m):
    mat = reed_sol_van_matrix(k, m)
    data = _rand(3, k, 256)
    want = R.encode_ref(mat, data)
    got = np.asarray(_apply(mat, data, impl))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", IMPLS)
def test_zero_and_identity_rows(impl):
    # degenerate coefficients exercise the zero-skip paths
    mat = np.array([[0, 0, 0], [1, 0, 0], [2, 3, 0]], dtype=np.uint8)
    data = _rand(2, 3, 128, seed=1)
    want = R.encode_ref(mat, data)
    got = np.asarray(_apply(mat, data, impl))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("impl", IMPLS)
def test_decode_roundtrip_all_erasure_patterns(impl):
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
            rec = np.asarray(_apply(D, stack, impl))
            for idx, e in enumerate(erased):
                np.testing.assert_array_equal(rec[:, idx, :], chunks_all[e],
                                              err_msg=f"erased={erased} impl={impl}")


def test_traced_matrix_matches_static():
    import jax.numpy as jnp
    k, m = 4, 2
    mat = reed_sol_van_matrix(k, m)
    data = _rand(2, k, 64, seed=3)
    want = R.encode_ref(mat, data)
    got = np.asarray(K.apply_matrix_traced(jnp.asarray(mat), jnp.asarray(data)))
    np.testing.assert_array_equal(got, want)


def test_traced_matrix_batched():
    import jax.numpy as jnp
    rng = np.random.default_rng(4)
    mats = rng.integers(0, 256, size=(3, 2, 4), dtype=np.uint8)
    data = rng.integers(0, 256, size=(3, 4, 32), dtype=np.uint8)
    want = np.stack([R.encode_ref(mats[i], data[i]) for i in range(3)])
    got = np.asarray(K.apply_matrix_traced(jnp.asarray(mats), jnp.asarray(data)))
    np.testing.assert_array_equal(got, want)


def test_make_encoder_caches():
    mat = reed_sol_van_matrix(4, 2)
    # the jitted program is cached by matrix bytes; the default
    # bucketing wrapper is a thin lambda over that shared program
    assert K.make_encoder(mat, bucket_batch=False) \
        is K.make_encoder(mat.copy(), bucket_batch=False)
    assert K._make_jitted(mat.tobytes(), 2, 4, K.DEFAULT_IMPL) \
        is K._make_jitted(mat.copy().tobytes(), 2, 4, K.DEFAULT_IMPL)


def test_bucketed_encoder_matches_exact():
    mat = reed_sol_van_matrix(4, 2)
    rng = np.random.default_rng(3)
    for B in (1, 3, 5, 8):
        d = rng.integers(0, 256, (B, 4, 512), np.uint8)
        a = np.asarray(K.make_encoder(mat)(d))               # bucketed
        b = np.asarray(K.make_encoder(mat, bucket_batch=False)(d))
        assert np.array_equal(a, b), B
