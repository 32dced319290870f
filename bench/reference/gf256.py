"""Plain GF(2^8) Reed-Solomon encode, the benchmark's own copy.

Independent of `ceph_tpu/`: builds its own field tables (primitive
polynomial 0x11D, generator 2, as gf-complete's w=8 default), its own
`reed_sol_van` coding matrix, and encodes by table look-up and XOR.

The matrix is the program's variant, which the configurations state:
the (k+m) x k Vandermonde matrix V[i, j] = i**j reduced by column
operations until its top k x k block is the identity; the bottom m rows
are the coding matrix. Upstream jerasure's
`reed_sol_big_vandermonde_distribution_matrix` goes on from there to
scale columns until the first coding row is all ones and rows until the
first coding column is: an MDS code either way, but its parity bytes
differ from these (for k=8 m=3 this matrix's first coding row is
26 132 186 51 231 16 198 39). `bench/tests/test_reference.py` checks it against
the program's oracles on seeded inputs so that it starts equal.
"""

from __future__ import annotations

import functools

import numpy as np

POLY = 0x11D


@functools.cache
def mul_table() -> np.ndarray:
    """(256, 256) uint8 table of GF(2^8) products."""
    exp = np.zeros(510, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:] = exp[:255]
    a = np.arange(256)
    table = exp[log[a][:, None] + log[a][None, :]].astype(np.uint8)
    table[0, :] = 0
    table[:, 0] = 0
    return table


def _inverse(x: int) -> int:
    return int(np.nonzero(mul_table()[x] == 1)[0][0])


def _power(base: int, n: int) -> int:
    out = 1
    for _ in range(n):
        out = int(mul_table()[out, base])
    return out


def reed_sol_van(k: int, m: int) -> np.ndarray:
    """(m, k) coding matrix of the program's reed_sol_van: the column-
    reduced Vandermonde matrix, without upstream jerasure's last scaling
    of columns and rows (module docstring)."""
    mt = mul_table()
    v = np.array([[_power(i, j) for j in range(k)]
                  for i in range(k + m)], np.uint8)
    for i in range(k):
        if v[i, i] == 0:
            j = next(j for j in range(i + 1, k) if v[i, j])
            v[:, [i, j]] = v[:, [j, i]]
        if v[i, i] != 1:
            v[:, i] = mt[_inverse(int(v[i, i])), v[:, i]]
        for j in range(k):
            if j != i and v[i, j]:
                v[:, j] ^= mt[int(v[i, j]), v[:, i]]
    if not np.array_equal(v[:k], np.eye(k, dtype=np.uint8)):
        raise ArithmeticError("reed_sol_van: top block is not the identity")
    return v[k:].copy()


def cauchy_orig(k: int, m: int) -> np.ndarray:
    """jerasure's cauchy_orig matrix, 1 / (i ^ (m + j)): a different
    byte format under the same k and m. Only the controls use it."""
    return np.array([[_inverse(i ^ (m + j)) for j in range(k)]
                     for i in range(m)], np.uint8)


def rs_encode(matrix: np.ndarray, data: np.ndarray) -> np.ndarray:
    """(..., k, L) uint8 data rows -> (..., m, L) parity rows."""
    mt = mul_table()
    m, k = matrix.shape
    if data.shape[-2] != k:
        raise ValueError(f"data has {data.shape[-2]} rows, matrix wants {k}")
    out = np.zeros(data.shape[:-2] + (m, data.shape[-1]), np.uint8)
    for i in range(m):
        for j in range(k):
            if matrix[i, j]:
                out[..., i, :] ^= mt[matrix[i, j]][data[..., j, :]]
    return out
