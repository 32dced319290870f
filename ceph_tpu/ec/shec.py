"""SHEC — shingled erasure code with local parity groups.

Rebuild of the reference's shec plugin (ref: src/erasure-code/shec/
ErasureCodeShec.{h,cc} — ErasureCodeShecReedSolomonVandermonde with its
own decode-matrix search, plus ErasureCodeShecTableCache): a non-MDS
code trading storage efficiency for recovery I/O. Each of the m parity
chunks covers only a short "shingle" window of l = ceil(k*c/m)
consecutive data chunks (wrapping mod k, windows overlapping like roof
shingles), so a single lost chunk is rebuilt from ~l reads instead of k,
while any c concurrent failures stay recoverable.

Profile: k, m, c (durability estimator; c <= m). The coding matrix is a
reed_sol_van matrix masked to the shingle windows; init() verifies the
all-<=c-erasures guarantee exhaustively (budgeted) rather than trusting
the masked construction blindly.

Decode is a rowspace solve: with generator G = [I_k ; M], a chunk o is
recoverable from survivors S iff G[o] lies in the rowspace of G[S]; the
expressing combination IS the decode matrix, cached per erasure pattern
and applied as a batched GF(2^8) kernel. minimum_to_decode searches
parity subsets in increasing read-cost order — the reference's
"decode-matrix search", reshaped: cost ranking first, rank check via the
same rowspace solve.
"""

from __future__ import annotations

from itertools import combinations
from math import comb
from typing import Mapping, Sequence

import numpy as np

from ..gf.tables import inv_table, mul_table
from .interface import ErasureCode
from .matrices import reed_sol_van_matrix
from .registry import register


def gf_express(A: np.ndarray, B: np.ndarray) -> np.ndarray | None:
    """Find X with X @ A = B over GF(2^8), or None if some row of B is
    outside A's rowspace. A: (s, k), B: (r, k) -> X: (r, s)."""
    A = np.asarray(A, np.uint8)
    B = np.asarray(B, np.uint8)
    s, k = A.shape
    mt = mul_table()
    invt = inv_table()
    R = A.copy()
    T = np.eye(s, dtype=np.uint8)  # R = T @ A invariant
    pivots: list[tuple[int, int]] = []
    row = 0
    for col in range(k):
        p = row
        while p < s and R[p, col] == 0:
            p += 1
        if p == s:
            continue
        if p != row:
            R[[row, p]] = R[[p, row]]
            T[[row, p]] = T[[p, row]]
        pv = R[row, col]
        if pv != 1:
            pinv = invt[pv]
            R[row] = mt[pinv, R[row]]
            T[row] = mt[pinv, T[row]]
        f = R[:, col].copy()
        f[row] = 0
        nz = f.nonzero()[0]
        if nz.size:
            R[nz] ^= mt[f[nz, None], R[row][None, :]]
            T[nz] ^= mt[f[nz, None], T[row][None, :]]
        pivots.append((col, row))
        row += 1
        if row == s:
            break
    X = np.zeros((B.shape[0], s), np.uint8)
    for i in range(B.shape[0]):
        r = B[i].copy()
        for col, prow in pivots:
            f = r[col]
            if f:
                r ^= mt[f, R[prow]]
                X[i] ^= mt[f, T[prow]]
        if r.any():
            return None
    return X


@register("shec")
class Shec(ErasureCode):
    """Shingled EC: m local parities over overlapping windows of l data
    chunks; guaranteed recovery of any <= c erasures."""

    # exhaustive durability verification budget (subsets tested at init)
    _VERIFY_BUDGET = 100_000
    has_ref_oracle = True

    def init(self, profile: Mapping[str, str]) -> None:
        self.k = int(profile.get("k", 4))
        self.m = int(profile.get("m", 3))
        self.c = int(profile.get("c", 2))
        if not 1 <= self.c <= self.m:
            raise ValueError(f"shec c={self.c}: need 1 <= c <= m={self.m}")
        if self.m > self.k:
            raise ValueError(f"shec m={self.m} > k={self.k} unsupported")
        if self.k + self.m > 256:
            raise ValueError(f"bad geometry k={self.k} m={self.m} (w=8)")
        self.l = -(-self.k * self.c // self.m)  # ceil(k*c/m) window width
        base = reed_sol_van_matrix(self.k, self.m)
        M = np.zeros_like(base)
        self.windows: list[tuple[int, ...]] = []
        for i in range(self.m):
            start = i * self.k // self.m
            win = tuple(sorted((start + j) % self.k for j in range(self.l)))
            self.windows.append(win)
            for j in win:
                M[i, j] = base[i, j]
        self.matrix = M
        self.G = np.vstack([np.eye(self.k, dtype=np.uint8), M])
        self._decode_cache: dict[tuple, tuple] = {}
        self._mtd_cache: dict[tuple, set[int]] = {}
        self._fn_cache: dict[int, object] = {}
        self._verify_durability()
        self._encode_fn = self._matrix_fn(self.matrix)

    def _matrix_fn(self, X: np.ndarray):
        """Host (B, cols, L) -> host X (GF@) it: the matrix's host face
        (rs_kernels.make_host_encoder), or for impl=ref the numpy
        oracle."""
        if self.ref_oracle:
            from functools import partial

            from ..gf.numpy_ref import encode_ref
            return partial(encode_ref, X)
        from ..ops.rs_kernels import make_host_encoder
        return make_host_encoder(X)

    def _verify_durability(self) -> None:
        n = self.k + self.m
        if comb(n, self.c) > self._VERIFY_BUDGET:
            return  # too big to verify exhaustively; constructions this
            # large should be validated offline (mirrors the isa MDS gate)
        for erased in combinations(range(n), self.c):
            surv = [i for i in range(n) if i not in erased]
            if gf_express(self.G[surv], self.G[list(erased)]) is None:
                raise ValueError(
                    f"shec k={self.k} m={self.m} c={self.c}: erasure "
                    f"{erased} unrecoverable — masked matrix degenerate "
                    f"for this geometry")

    # -- recovery planning --------------------------------------------------

    def _plan(self, unknown_data: frozenset[int], want: frozenset[int],
              avail: frozenset[int],
              costs: Mapping[int, int] | None = None
              ) -> tuple[set[int], tuple[int, ...]]:
        """Choose the cheapest survivor set able to produce `want`.

        Search: parity subsets of the available parities in increasing
        total-read order; a subset works if every wanted chunk's G row
        lies in the rowspace of [available window data rows + parity
        rows]. Returns (chunks to read, survivor order for decode).
        With `costs`, fewest reads still wins first (the shingle
        locality is the point of SHEC) and per-chunk costs break ties
        among equal-sized candidate sets.
        """
        avail_par = sorted(p for p in avail if p >= self.k)
        avail_data = frozenset(j for j in avail if j < self.k)
        want_rows = self.G[sorted(want)]
        best: tuple[tuple, set[int], tuple[int, ...]] | None = None
        # re-encoding a wanted (lost) parity consumes its own window data
        want_par_data: set[int] = set()
        for w in want:
            if w >= self.k:
                want_par_data.update(self.windows[w - self.k])
        for r in range(0, len(avail_par) + 1):
            for P in combinations(avail_par, r):
                need_data = set(want_par_data)
                for p in P:
                    need_data.update(self.windows[p - self.k])
                need_data -= unknown_data
                if not need_data <= avail_data:
                    continue
                surv = tuple(sorted(need_data) + list(P))
                # wanted data already available reads itself directly
                direct = {w for w in want if w in avail}
                surv_all = tuple(sorted(set(surv) | direct))
                if not surv_all:
                    continue
                if gf_express(self.G[list(surv_all)], want_rows) is None:
                    continue
                cost = (len(surv_all),
                        sum(int(costs.get(c, 0)) for c in surv_all)
                        if costs else 0)
                if best is None or cost < best[0]:
                    best = (cost, set(surv_all), surv_all)
            if best is not None:
                break  # smaller parity subsets tried first; cost ~ reads
        if best is None:
            raise ValueError(
                f"shec cannot produce {sorted(want)} from {sorted(avail)}")
        return best[1], best[2]

    def minimum_to_decode(self, want_to_read: Sequence[int],
                          available: Sequence[int]) -> set[int]:
        want = frozenset(want_to_read)
        avail = frozenset(available)
        n = self.get_chunk_count()
        bad = [i for i in want | avail if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), got {sorted(bad)}")
        if want <= avail:
            return set(want)
        key = (want, avail)
        hit = self._mtd_cache.get(key)
        if hit is None:
            unknown = frozenset(j for j in range(self.k) if j not in avail)
            hit = self._plan(unknown, want, avail)[0]
            self._mtd_cache[key] = hit
        return set(hit)

    def minimum_to_decode_with_cost(self, want_to_read: Sequence[int],
                                    available: Mapping[int, int]) -> set[int]:
        """Structural like minimum_to_decode — the MDS default's 'k
        cheapest' can be an undecodable set for a shingled matrix —
        with per-chunk costs breaking ties among the smallest
        workable survivor sets."""
        want = frozenset(want_to_read)
        avail = frozenset(available)
        n = self.get_chunk_count()
        bad = [i for i in want | avail if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), got {sorted(bad)}")
        if want <= avail:
            return set(want)
        unknown = frozenset(j for j in range(self.k) if j not in avail)
        return set(self._plan(unknown, want, avail,
                              costs=available)[0])

    # -- codec --------------------------------------------------------------

    def encode_chunks(self, data: np.ndarray) -> np.ndarray:
        return self._encode_fn(np.asarray(data, np.uint8))

    def _decoder_for(self, want: tuple[int, ...], surv: tuple[int, ...]):
        key = (want, surv)
        hit = self._decode_cache.get(key)
        if hit is None:
            X = gf_express(self.G[list(surv)], self.G[list(want)])
            if X is None:
                raise ValueError(
                    f"shec cannot decode {list(want)} from {list(surv)}")
            hit = (self._matrix_fn(X), surv)
            self._decode_cache[key] = hit
        return hit

    def decode_chunks(self, want_to_read: Sequence[int],
                      chunks: Mapping[int, np.ndarray]) -> dict[int, np.ndarray]:
        want = tuple(sorted(set(want_to_read)))
        surv = tuple(sorted(chunks))
        fn, order = self._decoder_for(want, surv)
        arrs = [np.asarray(chunks[s], np.uint8) for s in order]
        squeeze = arrs[0].ndim == 1
        if squeeze:
            arrs = [a[None] for a in arrs]
        stack = np.stack(arrs, axis=-2)
        rec = fn(stack)
        if squeeze:
            rec = rec[0]
        return {w: rec[..., i, :] for i, w in enumerate(want)}

    # -- introspection ------------------------------------------------------

    def recovery_read_count(self, failed: int) -> int:
        """Chunks read to rebuild one lost chunk — the SHEC selling point
        (~l for a data chunk vs k for RS)."""
        avail = [i for i in range(self.get_chunk_count()) if i != failed]
        return len(self.minimum_to_decode([failed], avail))
