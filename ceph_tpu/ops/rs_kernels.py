"""Batched GF(2^8) encode/decode kernels for TPU.

The device-side replacement for the reference's CPU hot loops
(ref: gf-complete gf_w8_split_4_8 SIMD multiply regions called from
jerasure_matrix_encode / jerasure_matrix_decode — see SURVEY.md §3.1).

Unit of work: uint8 tensors shaped (batch, shard, chunk_bytes). The
coding/decoding matrix is STATIC (baked into the compiled program) on the
fast paths — codes are fixed per pool, so this is the common case, and it
lets every GF coefficient become a compile-time constant (no gathers).

Three interchangeable lowerings, all bit-exact vs the numpy oracle:

  impl="bitlinear"  (default) — GF(2^8) multiply by a constant c is
      GF(2)-linear in x:  c*x = XOR_{b set in x} (c * 2^b).  Each term is
      a shift/AND/select/XOR over uint8 lanes on the VPU; no gathers, no
      table memory traffic. The XOR tree over (j, b) is unrolled at trace
      time (k*8 terms, static).

  impl="mxu" — unpack bytes to GF(2) bit-planes, multiply by the (m*8,
      k*8) bit-expansion of the coding matrix on the MXU as an int8
      matmul with int32 accumulation, take the low bit (sum mod 2 == XOR),
      re-pack to bytes. Rides the systolic array instead of the VPU.

  impl="logexp" — classic log/antilog table gathers. Slowest on TPU but
      the simplest; also the only path that supports a *traced* (runtime)
      matrix, which mixed-erasure-pattern decode batches use.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..gf.tables import GF_EXP, GF_LOG, bit_powers, matrix_to_bitmatrix

Array = jax.Array

# host tables; jnp.asarray at the point of use makes them constants of
# the traced program, so importing this module creates no backend
_LOG_T = GF_LOG.astype(np.int32)
_EXP_T = GF_EXP[:512].astype(np.uint8)


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


# ---------------------------------------------------------------- logexp

def _apply_logexp_static(matrix: np.ndarray, data: Array) -> Array:
    m, k = matrix.shape
    _check(data, k)
    logs = GF_LOG[matrix].astype(np.int32)  # (m, k) host constants
    zero = matrix == 0
    ld = jnp.take(jnp.asarray(_LOG_T), data.astype(jnp.int32))  # (B, k, L)
    exp_t = jnp.asarray(_EXP_T)
    acc = None
    for i in range(m):
        row = None
        for j in range(k):
            if zero[i, j]:
                continue
            prod = jnp.take(exp_t, ld[:, j, :] + int(logs[i, j]))
            prod = jnp.where(data[:, j, :] == 0, jnp.uint8(0), prod)
            row = prod if row is None else row ^ prod
        if row is None:
            row = jnp.zeros_like(data[:, 0, :])
        row = row[:, None, :]
        acc = row if acc is None else jnp.concatenate([acc, row], axis=1)
    return acc


def apply_matrix_traced(matrix: Array, data: Array) -> Array:
    """GF matmul with a RUNTIME (traced) matrix — per-batch decode matrices.

    matrix: (..., m, k) uint8 (may carry a leading batch dim matching data).
    data:   (..., k, L) uint8.
    Returns (..., m, L).
    """
    log_t = jnp.asarray(_LOG_T)
    lm = jnp.take(log_t, matrix.astype(jnp.int32))           # (..., m, k)
    ld = jnp.take(log_t, data.astype(jnp.int32))             # (..., k, L)
    s = lm[..., :, :, None] + ld[..., None, :, :]            # (..., m, k, L)
    prod = jnp.take(jnp.asarray(_EXP_T), s)
    nz = (matrix[..., :, :, None] != 0) & (data[..., None, :, :] != 0)
    prod = jnp.where(nz, prod, jnp.uint8(0))
    return jnp.bitwise_xor.reduce(prod, axis=-2)


def _apply_pallas(matrix: np.ndarray, data: Array) -> Array:
    from .pallas_gf import apply_matrix_pallas
    return apply_matrix_pallas(matrix, data)


_IMPLS = {
    "bitlinear": _apply_bitlinear,
    "mxu": _apply_mxu,
    "logexp": _apply_logexp_static,
    "pallas": _apply_pallas,
}

DEFAULT_IMPL = "bitlinear"


def apply_matrix(matrix: np.ndarray, data: Array, impl: str = DEFAULT_IMPL) -> Array:
    """out = matrix (GF) @ data along the shard axis. matrix is static."""
    return _IMPLS[impl](np.asarray(matrix, dtype=np.uint8), data)


@functools.lru_cache(maxsize=128)
def _make_jitted(matrix_bytes: bytes, m: int, k: int, impl: str):
    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k)
    fn = functools.partial(_IMPLS[impl], matrix)
    return jax.jit(fn)


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


def make_encoder(matrix: np.ndarray, impl: str = DEFAULT_IMPL,
                 bucket_batch: bool = True):
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
    jitted = _make_jitted(matrix.tobytes(), *matrix.shape, impl)
    if not bucket_batch:
        return jitted
    return lambda data: run_bucketed(jitted,
                                     jnp.asarray(data, jnp.uint8))
