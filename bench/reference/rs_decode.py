"""Plain GF(2^8) Reed-Solomon decode, the benchmark's own copy.

Independent of `ceph_tpu/`; only `gf256.py` beside it is imported (the
field's product table, polynomial 0x11D, and the encode). The same
semantics as the program's degraded read by another route: the program
inverts the k x k submatrix of the surviving rows on the host and
multiplies by the inverse on the device; this solves the system
`G[present] . data = rows` directly, by Gauss-Jordan elimination whose
row operations are applied to the byte rows themselves, so no inverse
matrix is ever formed.
"""

from __future__ import annotations

import numpy as np

from bench.reference import gf256


def rs_decode(matrix: np.ndarray, rows: np.ndarray, present, want) -> np.ndarray:
    """The rows of the shards `want` of each stripe.

    `matrix` is the (m, k) coding matrix; shard i < k is data row i,
    shard k + i is parity row i. `rows` is (..., len(present), L) uint8:
    the stored rows of the shards `present`, in that order; the first k
    of them are used. Returns (..., len(want), L)."""
    mt = gf256.mul_table()
    m, k = matrix.shape
    present = [int(p) for p in present][:k]
    if len(present) < k or len(set(present)) < k:
        raise ValueError(f"need {k} distinct present shards, got {present}")
    if rows.shape[-2] < k:
        raise ValueError(f"rows has {rows.shape[-2]} rows, need {k}")
    generator = np.concatenate([np.eye(k, dtype=np.uint8),
                                np.asarray(matrix, np.uint8)])
    a = generator[present].copy()                   # (k, k)
    b = [np.array(rows[..., i, :], np.uint8) for i in range(k)]
    for col in range(k):
        pivot = next((r for r in range(col, k) if a[r, col]), None)
        if pivot is None:
            raise ArithmeticError(f"shards {present} do not determine "
                                  f"the data: singular at column {col}")
        if pivot != col:
            a[[col, pivot]] = a[[pivot, col]]
            b[col], b[pivot] = b[pivot], b[col]
        if a[col, col] != 1:
            inv = int(np.nonzero(mt[a[col, col]] == 1)[0][0])
            a[col] = mt[inv][a[col]]
            b[col] = mt[inv][b[col]]
        for r in range(k):
            f = int(a[r, col])
            if r != col and f:
                a[r] ^= mt[f][a[col]]
                b[r] ^= mt[f][b[col]]
    data = np.stack(b, axis=-2)                     # (..., k, L)
    out = []
    for w in (int(w) for w in want):
        if not 0 <= w < k + m:
            raise ValueError(f"no shard {w} in a {k}+{m} code")
        out.append(data[..., w, :] if w < k
                   else gf256.rs_encode(matrix[w - k:w - k + 1],
                                        data)[..., 0, :])
    return np.stack(out, axis=-2)
