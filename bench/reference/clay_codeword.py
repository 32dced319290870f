"""What a Clay codeword is, held to its definition: the benchmark's own
statement, in numpy.

Independent of `ceph_tpu/`: no matrix is solved or cached here, and
nothing of the program's Clay coder is imported. Only `gf256.py` (the
field and the base code's reed_sol_van), `crc32c.py` and
`recovered_pool.data_rows` beside it are. The coupled-layer code
(Vajha et al., FAST'18; Ceph's clay plugin) at k, m, d:

* q = d - k + 1 and t = ceil((k + m) / q); the q*t nodes of a q x t grid
  are the k data chunks, nu = q*t - (k + m) virtual all-zero chunks, then
  the m parity chunks; node i sits at (x, y) = (i % q, i // q);
* a chunk is P = q**t sub-chunks, one a plane z in [0, P) with base-q
  digits z_y;
* in plane z, node (x, y) with z_y != x is paired with node (z_y, y) in
  the plane z' that is z with digit y set to x. The coupled sub-chunks C
  stored are the uncoupled U through the symmetric [[1, g], [g, 1]]
  (g = gamma, g^2 != 1): C1 = U1 + g U2, C2 = g U1 + U2. A node with
  z_y == x is unpaired there: C = U;
* in every plane the uncoupled column of the q*t nodes is a codeword of
  the systematic MDS base code: H U = 0, H = [C | I_m] with C the base
  code's (m, k + nu) coding matrix.

The code is MDS and systematic, so k data rows that are the object
striped and a parity check that holds in every plane pin all k + m rows:
`check` is exact, with no tolerance. The codeword spans the whole shard
row the program encodes (P sub-chunks of row / P bytes).
"""

from __future__ import annotations

import numpy as np

from bench.reference import crc32c, gf256
from bench.reference.recovered_pool import CRC_SEED, data_rows

GAMMA = 2


def grid(k: int, m: int, d: int) -> tuple[int, int, int]:
    """(q, t, nu) of the code."""
    q = d - k + 1
    t = -(-(k + m) // q)
    return q, t, q * t - (k + m)


def _digit(z: np.ndarray, y: int, q: int) -> np.ndarray:
    return (z // q ** y) % q


def uncouple(coupled: np.ndarray, q: int, t: int,
             gamma: int = GAMMA) -> np.ndarray:
    """(q*t, P, s) coupled sub-chunks of every node (virtual ones zero) ->
    the uncoupled (q*t, P, s): each pair through the inverse of the
    symmetric transform, U1 = (C1 + g C2) / (1 + g^2)."""
    mt = gf256.mul_table()
    nn, P, _ = coupled.shape
    det = 1 ^ int(mt[gamma, gamma])
    if det == 0:
        raise ValueError(f"gamma {gamma}: g^2 = 1, the pairs do not invert")
    inv = int(np.nonzero(mt[det] == 1)[0][0])
    z = np.arange(P)
    out = coupled.copy()
    for n in range(nn):
        x, y = n % q, n // q
        zy = _digit(z, y, q)
        paired = zy != x
        partner = y * q + zy[paired]
        plane = z[paired] + (x - zy[paired]) * q ** y
        mixed = coupled[n, paired] ^ mt[gamma][coupled[partner, plane]]
        out[n, paired] = mt[inv][mixed]
    return out


def parity_failures(rows: np.ndarray, k: int, m: int, d: int,
                    gamma: int = GAMMA) -> int:
    """Planes of the (k+m, L) rows whose uncoupled column fails the base
    code's parity check: 0 for a Clay codeword."""
    q, t, nu = grid(k, m, d)
    P = q ** t
    rows = np.asarray(rows, np.uint8)
    if rows.shape[0] != k + m or rows.shape[1] % P:
        raise ValueError(f"rows {rows.shape}: want ({k + m}, a multiple "
                         f"of {P})")
    s = rows.shape[1] // P
    nodes = np.zeros((q * t, P, s), np.uint8)
    nodes[:k] = rows[:k].reshape(k, P, s)
    nodes[k + nu:] = rows[k:].reshape(m, P, s)
    u = uncouple(nodes, q, t, gamma)
    H = np.concatenate([gf256.reed_sol_van(k + nu, m),
                        np.eye(m, dtype=np.uint8)], axis=1)
    mt = gf256.mul_table()
    bad = np.zeros(P, bool)
    for r in range(m):
        syndrome = np.zeros((P, s), np.uint8)
        for n in range(q * t):
            if H[r, n]:
                syndrome ^= mt[H[r, n]][u[n]]
        bad |= syndrome.any(axis=1)
    return int(bad.sum())


def check(payload: bytes, rows: list, k: int, m: int, d: int,
          stripe_unit: int, gamma: int = GAMMA) -> dict:
    """One object's k+m stored rows against the definition: which data
    rows are not the payload striped (`data_wrong`), and how many planes
    fail the parity check (`planes_wrong`; None where a row is missing)."""
    want = data_rows(payload, k, stripe_unit)
    data_wrong = [s for s in range(k)
                  if rows[s] is None or not np.array_equal(rows[s], want[s])]
    if any(row is None for row in rows) \
            or len({len(row) for row in rows}) != 1:
        return {"data_wrong": data_wrong, "planes_wrong": None}
    return {"data_wrong": data_wrong,
            "planes_wrong": parity_failures(np.stack(rows), k, m, d, gamma)}


def crcs(rows: np.ndarray) -> np.ndarray:
    """The hinfo crc of each row: ceph_crc32c, seed 0xFFFFFFFF, no final
    xor."""
    return crc32c.crc32c_rows(CRC_SEED, np.asarray(rows, np.uint8))
