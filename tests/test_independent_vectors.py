"""Independent bit-exactness evidence (r4 verdict item 4).

Every test here checks repo output against arithmetic DERIVED IN THIS
FILE from published definitions only — bitwise carry-less multiply
reduced mod the primitive polynomial 0x11D, brute-force inverses, and
Plank's published Vandermonde column-reduction — sharing no tables,
no exp/log construction, and no kernels with ceph_tpu. The literal
byte vectors below were computed BY this independent arithmetic (not
by the repo's oracle), so a simultaneous bug in the repo's tables and
its numpy reference cannot survive this file.

Refs: src/erasure-code/jerasure/jerasure/src/reed_sol.c
(reed_sol_big_vandermonde_distribution_matrix), cauchy.c
(cauchy_original_coding_matrix), gf-complete w=8 default polynomial;
Plank's 1997 RS tutorial + 2005 correction; ISO/IEC 18004 (QR) GF(256)
antilog table for the same 0x11D field.
"""

import numpy as np
import pytest

# ---------------------------------------------------------------- the
# independent field: carry-less shift-xor multiply mod 0x11D, nothing
# shared with ceph_tpu.gf


def gmul(a: int, b: int) -> int:
    r = 0
    while b:
        if b & 1:
            r ^= a
        a <<= 1
        if a & 0x100:
            a ^= 0x11D
        b >>= 1
    return r


def ginv(a: int) -> int:
    for y in range(1, 256):
        if gmul(a, y) == 1:
            return y
    raise ValueError(f"{a} has no inverse")


def gpow(a: int, n: int) -> int:
    r = 1
    for _ in range(n):
        r = gmul(r, a)
    return r


def indep_rs_van(k: int, m: int) -> list[list[int]]:
    """Plank's construction, implemented here with the independent
    arithmetic: extended Vandermonde V[i][j] = i^j, column-reduce the
    top k x k block to identity, return the bottom m rows."""
    v = [[gpow(i, j) for j in range(k)] for i in range(k + m)]
    for i in range(k):
        if v[i][i] == 0:
            for j in range(i + 1, k):
                if v[i][j] != 0:
                    for r in range(k + m):
                        v[r][i], v[r][j] = v[r][j], v[r][i]
                    break
        if v[i][i] != 1:
            inv = ginv(v[i][i])
            for r in range(k + m):
                v[r][i] = gmul(inv, v[r][i])
        for j in range(k):
            if j != i and v[i][j] != 0:
                c = v[i][j]
                for r in range(k + m):
                    v[r][j] ^= gmul(c, v[r][i])
    return v[k:]


# ------------------------------------------------------- published and
# independently computed literals

# ISO/IEC 18004 (QR code) GF(256)/0x11D antilog table, first 25 entries
# — a PUBLISHED constant, not derived from this repo.
QR_ANTILOG_PREFIX = [1, 2, 4, 8, 16, 32, 64, 128, 29, 58, 116, 232,
                     205, 135, 19, 38, 76, 152, 45, 90, 180, 117, 234,
                     201, 143]

# Known-answer vectors computed by THIS FILE's arithmetic (2026-07-31),
# embedded as literals so drift in gmul() itself is also caught.
RS_VAN_K4M2 = [[27, 28, 18, 20], [28, 27, 20, 18]]
RS_VAN_K8M3_ROWS3 = [[26, 132, 186, 51, 231, 16, 198, 39],
                     [132, 26, 51, 186, 16, 231, 39, 198],
                     [186, 51, 26, 132, 198, 39, 231, 16]]
CAUCHY_ORIG_K4M2 = [[142, 244, 71, 167], [244, 142, 167, 71]]
# data chunks: the AES test vectors of NIST SP 800-38A (published
# constants); parity = RS_VAN_K4M2 applied with gmul
KAT_DATA = ["2b7e151628aed2a6", "abf7158809cf4f3c",
            "762e7160f38b4da5", "6a784d9045190cfe"]
KAT_PARITY = ["f39547b03e3f3da7", "1ce4cf574a4e5281"]


# ------------------------------------------------------------ GF layer

def test_mul_table_vs_independent_bitwise():
    """All 65536 products: repo tables vs shift-xor reduction."""
    from ceph_tpu.gf.tables import mul_table
    mt = np.asarray(mul_table())
    want = np.array([[gmul(a, b) for b in range(256)]
                     for a in range(256)], np.uint8)
    assert (mt == want).all()


def test_antilog_prefix_matches_published_qr_table():
    from ceph_tpu.gf.tables import gf_mul_scalar
    x, got = 1, []
    for _ in range(len(QR_ANTILOG_PREFIX)):
        got.append(x)
        x = gf_mul_scalar(x, 2)
    assert got == QR_ANTILOG_PREFIX


# ------------------------------------------------------- matrix layer

@pytest.mark.parametrize("k,m", [(4, 2), (8, 3), (8, 4), (6, 3), (10, 4)])
def test_reed_sol_van_equals_independent_derivation(k, m):
    from ceph_tpu.ec.matrices import reed_sol_van_matrix
    got = reed_sol_van_matrix(k, m).tolist()
    assert got == indep_rs_van(k, m)


def test_reed_sol_van_literals():
    from ceph_tpu.ec.matrices import reed_sol_van_matrix
    assert reed_sol_van_matrix(4, 2).tolist() == RS_VAN_K4M2
    assert reed_sol_van_matrix(8, 3).tolist() == RS_VAN_K8M3_ROWS3


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3), (8, 4)])
def test_cauchy_orig_equals_closed_form(k, m):
    from ceph_tpu.ec.matrices import cauchy_orig_matrix
    want = [[ginv(i ^ (m + j)) for j in range(k)] for i in range(m)]
    assert cauchy_orig_matrix(k, m).tolist() == want


def test_cauchy_orig_literal():
    from ceph_tpu.ec.matrices import cauchy_orig_matrix
    assert cauchy_orig_matrix(4, 2).tolist() == CAUCHY_ORIG_K4M2


def test_cauchy_good_rows_are_scalings_of_orig():
    """cauchy_good only ever divides rows/columns by field elements
    (jerasure cauchy.c improvement pass): row 0 must be all ones and
    every row a scalar multiple of the corresponding ORIG row under
    the column scaling — verified with independent arithmetic."""
    from ceph_tpu.ec.matrices import cauchy_good_matrix, cauchy_orig_matrix
    k, m = 6, 3
    orig = cauchy_orig_matrix(k, m).tolist()
    good = cauchy_good_matrix(k, m).tolist()
    assert good[0] == [1] * k
    # column scaling factors are fixed by row 0 of orig
    col = [ginv(orig[0][j]) for j in range(k)]
    for i in range(1, m):
        scaled = [gmul(orig[i][j], col[j]) for j in range(k)]
        # the row then gets one per-row divisor: recover it and check
        # consistency across all columns
        d_candidates = {gmul(scaled[j], ginv(good[i][j]))
                        for j in range(k)}
        assert len(d_candidates) == 1, \
            f"row {i} is not a uniform scaling of orig"


# ------------------------------------------------- encode-path layer

def _kat_arrays():
    data = np.stack([np.frombuffer(bytes.fromhex(h), np.uint8)
                     for h in KAT_DATA])[None]        # (1, 4, 8)
    parity = np.stack([np.frombuffer(bytes.fromhex(h), np.uint8)
                       for h in KAT_PARITY])[None]    # (1, 2, 8)
    return data, parity


def test_known_answer_parity_jax_kernels():
    """Encode the published data constants through every device
    lowering; the expected parity literals were computed by this
    file's independent arithmetic, NOT the repo oracle."""
    from ceph_tpu.ec.matrices import reed_sol_van_matrix
    from ceph_tpu.ops import rs_kernels
    data, parity = _kat_arrays()
    matrix = reed_sol_van_matrix(4, 2)
    for lowering in (rs_kernels._apply_bitlinear, rs_kernels._apply_mxu):
        got = np.asarray(lowering(matrix, data))
        np.testing.assert_array_equal(got, parity,
                                      err_msg=lowering.__name__)


def test_known_answer_parity_native_codec():
    from ceph_tpu.native import NativeReedSolomon
    data, parity = _kat_arrays()
    nc = NativeReedSolomon({"k": "4", "m": "2"})
    np.testing.assert_array_equal(nc.encode_chunks(data), parity)


def test_known_answer_parity_numpy_oracle():
    from ceph_tpu.ec.matrices import reed_sol_van_matrix
    from ceph_tpu.gf.numpy_ref import encode_ref
    data, parity = _kat_arrays()
    got = encode_ref(reed_sol_van_matrix(4, 2), data[0])
    np.testing.assert_array_equal(got, parity[0])


# ---------------------------------------- native vs JAX random sweeps

@pytest.mark.parametrize("k,m,tech", [
    (3, 2, "reed_sol_van"), (5, 3, "reed_sol_van"), (9, 4, "reed_sol_van"),
    (4, 2, "cauchy_orig"), (7, 3, "cauchy_good"), (6, 2, "cauchy_good"),
])
def test_native_vs_jax_random_geometries(k, m, tech):
    """Two independent implementation paths (self-contained C codec vs
    JAX kernels) must agree on encode AND every single-erasure decode
    for random data across geometries (r4 verdict item 4 cross-check).
    The native codec builds its own tables in C; the JAX path uses
    gf/tables — agreement corroborates both."""
    from ceph_tpu.ec.matrices import coding_matrix
    from ceph_tpu.gf.numpy_ref import decode_matrix
    from ceph_tpu.native import NativeReedSolomon
    from ceph_tpu.ops.rs_kernels import make_encoder
    rng = np.random.default_rng(k * 100 + m * 10)
    data = rng.integers(0, 256, (2, k, 512), np.uint8)
    nc = NativeReedSolomon({"k": str(k), "m": str(m),
                            "technique": tech})
    matrix = coding_matrix(tech, k, m)
    np.testing.assert_array_equal(np.asarray(matrix),
                                  np.asarray(nc.matrix))
    native_parity = np.asarray(nc.encode_chunks(data))
    jax_parity = np.asarray(make_encoder(matrix)(data))
    np.testing.assert_array_equal(native_parity, jax_parity)
    # single-erasure decodes through both paths
    full = np.concatenate([data, jax_parity], axis=1)
    for lost in (0, k - 1, k):
        surv = [i for i in range(k + m) if i != lost][:k]
        D = decode_matrix(matrix, [lost], k, surv)
        jax_rec = np.asarray(make_encoder(D)(full[:, surv]))
        native_rec = nc.decode_chunks([lost],
                                      {s: full[:, s] for s in surv})
        np.testing.assert_array_equal(jax_rec[:, 0], full[:, lost])
        np.testing.assert_array_equal(
            np.asarray(native_rec[lost]), full[:, lost])
