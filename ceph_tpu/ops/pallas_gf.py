"""Pallas VPU kernel for batched GF(2^8) matrix-apply (encode/decode).

STATUS: EXPERIMENT, not the production path. Kept oracle-pinned and
selectable (`impl=pallas`) as the repo's worked example of a Pallas
kernel; it has no measurement on the current chip, and ROADMAP C2
decides whether it stays.

The hand-scheduled replacement for the reference's CPU hot loop
(ref: gf-complete gf_w8_split_4_8 SIMD region multiply called from
jerasure_matrix_encode — SURVEY.md §2.1/§7.1). Where gf-complete keeps
two 16-entry nibble tables per coefficient in SSE registers, a TPU has
no byte shuffle — so this kernel uses the bit-linear form instead, on
uint32 lanes holding FOUR field bytes each:

  c * x  ==  XOR_{b: bit b of x set}  (c * 2^b)

For a uint32 word w packing 4 bytes, the b-th bit of every byte is
  v = (w >> b) & 0x01010101
and the canonical SWAR widening turns those per-byte bits into per-byte
0x00/0xFF masks with two ops (wrapping uint32 arithmetic):
  mask = (v << 8) - v
The per-(i,j,b) term is then `mask & coef_word` with coef_word =
matrix[i,j]*2^b replicated to 4 bytes — a trace-time PYTHON constant
(the matrix is static per pool), so zero coefficients cost nothing and
no table memory is touched at runtime. The whole product is an unrolled
XOR accumulation — no gathers, no MXU, pure VPU.

Layout is the whole game on the VPU. Each object's shard j is viewed as
a 2-D (sublane, lane) slab, so every op fills full 8x128 vregs and —
critically — NO op crosses sublanes: an earlier formulation that kept
shards stacked on the sublane axis and XOR-folded across them spent its
time in Mosaic relayouts and topped out at ~10 GB/s; this slab form
hits VPU-bound throughput. Accumulators live per output row i, so the
kernel emits exactly nnz(matrix bits) AND+XOR pairs plus 8 mask
computations per shard.

Grid: (batch, slab-tile). Bit-exact vs the numpy oracle
(tests/test_rs_kernels.py) and vs the jnp `bitlinear`/`mxu` lowerings.
The kernel is compiled for the TPU unless the caller asks for the
interpreter by name (`interpret=True`, which only tests do): a served
path that interpreted silently would hide the device.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..gf.tables import bit_powers

Array = jax.Array

_LANES = 512           # lane-dim words per slab row (2 KiB)
_SUBLANES = 64         # sublane rows per tile
_REP = 0x01010101


def _kernel_body(coefs: np.ndarray, x_ref, o_ref):
    """coefs: (m, k, 8) uint32 host constants (matrix[i,j]*2^b repl.).

    x_ref block: (1, k, S, C) uint32 — k shard slabs of one object.
    o_ref block: (1, m, S, C).
    """
    m, k, _ = coefs.shape
    accs = [None] * m
    for j in range(k):
        xj = x_ref[0, j]  # (S, C) — major-dim slice, no relayout
        for b in range(8):
            col = coefs[:, j, b]
            if not col.any():
                continue
            v = (xj >> np.uint32(b)) & np.uint32(_REP)
            mask = (v << np.uint32(8)) - v          # per-byte 0x00/0xFF
            for i in range(m):
                c = int(col[i])
                if c == 0:
                    continue
                term = mask if c == 0xFFFFFFFF else mask & np.uint32(c)
                accs[i] = term if accs[i] is None else accs[i] ^ term
    for i in range(m):
        o_ref[0, i] = accs[i] if accs[i] is not None \
            else jnp.zeros_like(x_ref[0, 0])


@functools.lru_cache(maxsize=128)
def _build(matrix_bytes: bytes, m: int, k: int, n_slabs: int,
           sub: int, interpret: bool):
    matrix = np.frombuffer(matrix_bytes, np.uint8).reshape(m, k)
    P = bit_powers()[matrix].astype(np.uint32)  # (m, k, 8)
    coefs = P * np.uint32(_REP)
    kernel = functools.partial(_kernel_body, coefs)
    tiles = n_slabs // sub

    def apply(x32: Array) -> Array:  # (B, k, n_slabs, _LANES) uint32
        B = x32.shape[0]
        return pl.pallas_call(
            kernel,
            grid=(B, tiles),
            in_specs=[pl.BlockSpec((1, k, sub, _LANES),
                                   lambda bi, ti: (bi, 0, ti, 0))],
            out_specs=pl.BlockSpec((1, m, sub, _LANES),
                                   lambda bi, ti: (bi, 0, ti, 0)),
            out_shape=jax.ShapeDtypeStruct((B, m, n_slabs, _LANES),
                                           jnp.uint32),
            interpret=interpret,
        )(x32)

    return apply


def apply_matrix_pallas(matrix: np.ndarray, data: Array,
                        sublanes: int | None = None,
                        interpret: bool = False) -> Array:
    """out = matrix (GF) @ data along the shard axis; matrix static.

    data: (B, k, L) uint8, L % 4 == 0 (CHUNK_ALIGNMENT guarantees it).
    Chunks are zero-padded up to a whole number of (sublanes x _LANES)
    slabs for the launch and sliced back — GF parity of zeros is zero,
    so padding is inert.
    """
    matrix = np.ascontiguousarray(matrix, np.uint8)
    m, k = matrix.shape
    B, kk, L = data.shape
    if kk != k:
        raise ValueError(f"data has {kk} shards, matrix expects {k}")
    if L % 4:
        raise ValueError(f"chunk length {L} not a multiple of 4")
    n_words = L // 4
    n_slabs_raw = -(-n_words // _LANES)
    sub = sublanes or min(_SUBLANES, n_slabs_raw)
    n_slabs = n_slabs_raw + ((-n_slabs_raw) % sub)
    pad = n_slabs * _LANES - n_words
    x32 = jax.lax.bitcast_convert_type(
        data.reshape(B, k, n_words, 4), jnp.uint32)
    if pad:
        x32 = jnp.pad(x32, ((0, 0), (0, 0), (0, pad)))
    x32 = x32.reshape(B, k, n_slabs, _LANES)
    out32 = _build(matrix.tobytes(), m, k, n_slabs, sub, interpret)(x32)
    out32 = out32.reshape(B, m, n_slabs * _LANES)
    if pad:
        out32 = out32[:, :, :n_words]
    out8 = jax.lax.bitcast_convert_type(out32, jnp.uint8)  # (B,m,n_words,4)
    return out8.reshape(B, m, L)
