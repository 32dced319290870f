"""Batched GF(2^8) encode/decode kernels for TPU.

The device-side replacement for the reference's CPU hot loops
(ref: gf-complete gf_w8_split_4_8 SIMD multiply regions called from
jerasure_matrix_encode / jerasure_matrix_decode — see SURVEY.md §3.1).

Unit of work: uint8 tensors shaped (batch, shard, chunk_bytes). The
coding/decoding matrix is STATIC (baked into the compiled program) —
codes are fixed per pool, and it lets every GF coefficient become a
compile-time constant (no gathers).

A caller hands over a matrix and gets a program (`apply_matrix`,
`make_encoder`). Two lowerings, both bit-exact vs the numpy oracle
(gf/numpy_ref), and ONE rule that picks between them from the matrix
alone (`_lowering`, `_UNROLL_MAX_ENTRIES`):

  unrolled (`_apply_bitlinear`) — GF(2^8) multiply by a constant c is
      GF(2)-linear in x:  c*x = XOR_{b set in x} (c * 2^b).  Each term is
      a shift/AND/select/XOR over uint8 lanes on the VPU; no gathers, no
      table memory traffic. The XOR tree over (j, b) is unrolled at trace
      time (k*8 terms, static): the form of every small matrix — a
      pool's RS/LRC/SHEC encode, decode and delta matrices.

  dense (`_apply_mxu`) — unpack bytes to GF(2) bit-planes, multiply by
      the (m*8, k*8) bit-expansion of the matrix on the MXU as an int8
      matmul with int32 accumulation, take the low bit (sum mod 2 ==
      XOR), re-pack to bytes. Program size does not grow with the
      matrix: the form of Clay's solved plane matrices (256 x 512 at
      k=8 m=4 d=11), which the unrolled form cannot compile in minutes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..gf.tables import bit_powers, matrix_to_bitmatrix

Array = jax.Array


def _check(data: Array, k: int) -> None:
    if data.ndim != 3:
        raise ValueError(f"data must be (batch, k, L) uint8, got {data.shape}")
    if data.shape[1] != k:
        raise ValueError(f"data has {data.shape[1]} shards, matrix expects {k}")


# ---------------------------------------------------------------- bitlinear

def _apply_bitlinear(matrix: np.ndarray, data: Array) -> Array:
    """parity[i] = XOR_j XOR_b bit_b(data[j]) ? (matrix[i,j] * 2^b) : 0."""
    m, k = matrix.shape
    _check(data, k)
    P = bit_powers()[matrix]  # (m, k, 8) uint8 numpy constants
    acc = None
    for j in range(k):
        dj = data[:, j, :]  # (B, L)
        for b in range(8):
            coefs = P[:, j, b]  # (m,) host constants
            if not coefs.any():
                continue
            # 0x00/0xFF lane mask from bit b; uint8 negate wraps mod 256
            mask = (jnp.zeros_like(dj) - ((dj >> b) & 1))  # (B, L)
            term = mask[:, None, :] & jnp.asarray(coefs)[None, :, None]
            acc = term if acc is None else acc ^ term
    if acc is None:
        B, _, L = data.shape
        acc = jnp.zeros((B, m, L), jnp.uint8)
    return acc


# ---------------------------------------------------------------- mxu

def _apply_mxu(matrix: np.ndarray, data: Array) -> Array:
    """Bit-plane int8 matmul on the MXU; sum mod 2 == XOR accumulate."""
    m, k = matrix.shape
    _check(data, k)
    B, _, L = data.shape
    bm = matrix_to_bitmatrix(matrix)  # (m*8, k*8) in {0,1}
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (data[:, :, None, :] >> shifts[None, None, :, None]) & 1  # (B,k,8,L)
    x = bits.reshape(B, k * 8, L).astype(jnp.int8)
    w = jnp.asarray(bm, dtype=jnp.int8)
    # (m*8, k*8) @ (B, k*8, L) -> (B, m*8, L); max dot length k*8 <= 2048 << int32
    pbits = jax.lax.dot_general(
        w, x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (m*8, B, L)
    pbits = (pbits & 1).astype(jnp.uint8).transpose(1, 0, 2).reshape(B, m, 8, L)
    return jnp.bitwise_xor.reduce(pbits << shifts[None, None, :, None], axis=2)


# ---------------------------------------------------------------- the rule

# Matrices of up to this many entries take the unrolled form, larger
# ones the dense one. One v5e, 32 MiB of data, first call / device time
# a call (PR 29's chip run; PERF.md has the table): 3 x 8 unrolled
# 4.6 s / 1.61 ms, dense 4.2 s / 0.51 ms; Clay's 16 x 32 unrolled
# 12.3 s / 6.03 ms, dense 5.8 s / 0.53 ms; Clay's 256 x 512 unrolled
# not compiled in 120 s, dense 8.8 s / 3.60 ms. The dense form is ahead
# on the chip at every size; the line stands between the largest pool
# matrix (RS k=12 m=5: 60 entries) and Clay's smallest solved one (512)
# because every ledger line measured the pools on the unrolled form —
# moving them is a perf_opt with a claim in ecbench_encode_4m_b32
# (ROADMAP A2), and this constant is all it has to change — and because
# the CPU backend, which runs the tests, is several times slower
# through the dense form at small matrices.
_UNROLL_MAX_ENTRIES = 128


def _lowering(matrix: np.ndarray):
    """The one place a lowering is chosen: from the matrix alone."""
    if matrix.size <= _UNROLL_MAX_ENTRIES:
        return _apply_bitlinear
    return _apply_mxu


def apply_matrix(matrix: np.ndarray, data: Array) -> Array:
    """out = matrix (GF) @ data along the shard axis. matrix is static."""
    matrix = np.asarray(matrix, dtype=np.uint8)
    return _lowering(matrix)(matrix, data)


@functools.lru_cache(maxsize=128)
def _make_jitted(matrix_bytes: bytes, m: int, k: int):
    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k)
    return jax.jit(functools.partial(_lowering(matrix), matrix))


def pow2_bucket(n: int) -> int:
    """Next power of two >= n (>= 1): the shared batch-bucketing rule
    that keeps variable per-PG batch sizes from compiling one XLA
    program per distinct B."""
    return 1 << max(0, int(n - 1).bit_length())


def pad_to_bucket(arr):
    """`arr` on the device with its leading dim padded to the pow2
    bucket, and the leading dim it had."""
    arr = jnp.asarray(arr)
    B = arr.shape[0]
    bucket = pow2_bucket(B)
    if bucket != B:
        arr = jnp.pad(arr, [(0, bucket - B)] + [(0, 0)] * (arr.ndim - 1))
    return arr, B


def run_bucketed(fn, arr):
    """Call `fn` with `arr`'s leading dim padded to the pow2 bucket and
    slice the result back — the ONE implementation of the bucketing
    idiom (encoders, CRC stacks, anything row-batched)."""
    arr, B = pad_to_bucket(arr)
    return fn(arr)[:B]


def make_encoder(matrix: np.ndarray, bucket_batch: bool = True):
    """Jitted closure computing matrix @ data for a fixed matrix.

    Works for encode (coding matrix) and decode (decode matrix) alike —
    both are static-matrix GF matmuls over (batch, shard, L) uint8.

    bucket_batch (DEFAULT ON): pad the batch dim up to the next power
    of two (and slice the result back). Cluster write/recovery paths
    see arbitrary per-PG batch sizes; without bucketing every distinct
    B compiles its own program (XLA shapes are static), turning small
    mixed batches into compile churn. Benchmarks pass False so their
    measured bytes match the computed bytes exactly.
    """
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    jitted = _make_jitted(matrix.tobytes(), *matrix.shape)
    if not bucket_batch:
        return jitted
    return lambda data: run_bucketed(jitted,
                                     jnp.asarray(data, jnp.uint8))
