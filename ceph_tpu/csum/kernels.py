"""Batched checksum kernels for TPU.

The device-side replacement for the reference's CPU checksum hot loops
(ref: src/common/crc32c_intel_fast_asm.s PCLMUL folding,
src/common/crc32c_aarch64.c, bundled src/xxHash) — the bulk path behind
deep-scrub (ref: src/osd/scrubber + ECBackend::be_deep_scrub) and
BlueStore per-block verify (ref: src/os/bluestore/Checksummer.h).

Unit of work: (batch, block_len) uint8 — many equal-sized blocks checked
in one launch (exactly the Checksummer csum_block_size model).

crc32c lowering: CRC is GF(2)-linear in the message, so instead of the
CPU's serial byte loop we
  1. compute the 8-byte chunk CRCs of all chunks in parallel
     (slicing-by-8 tables as vectorized gathers),
  2. reduce across the chunk axis in log2(n) levels; the "advance
     register by S zero bytes" operator of each level is a constant
     32x32 GF(2) matrix applied as 32 masked-XOR ops on uint32 lanes,
  3. fold in the (static) init/xorout contribution as host constants.
No per-byte dependency chain remains — wall time scales with the VPU,
not the byte count.

xxhash is NOT linear (mod-2^32/64 mul/add/rot), so it keeps its stripe
recurrence: lax.fori_loop over 16/32-byte stripes, batch-parallel.
XXH64's 64-bit arithmetic is built from uint32 limb pairs so the kernel
never needs the global x64 flag.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .reference import (apply_shift, crc32c_slice8_tables, crc32c_table,
                        inv_shift_matrix, matrix_cols_u32, shift_matrix)

Array = jax.Array

# host tables; they become device constants inside the traced programs,
# so importing this module creates no backend
_SLICE8 = crc32c_slice8_tables()  # (8, 256) uint32
_T0 = crc32c_table()              # (256,) uint32


def _apply_bitmatrix32(cols: np.ndarray, x: Array) -> Array:
    """y = M @ x over GF(2), M given as 32 uint32 column constants."""
    acc = jnp.zeros_like(x)
    for b in range(32):
        c = int(cols[b])
        if c == 0:
            continue
        mask = jnp.uint32(0) - ((x >> np.uint32(b)) & np.uint32(1))
        acc = acc ^ (mask & np.uint32(c))
    return acc


def _crc32c_linear(blocks: Array) -> Array:
    """Zero-init CRC register over each row of (..., L) uint8, L % 8 == 0.

    Layout is sized for the TPU tiling: the chunk axis (long) stays
    minor through the widening and the gathers — byte planes
    (8, ..., n), not (..., n, 8) int32, whose minor dim of 8 padded
    every tile 16x (3 GiB of scratch for 5.5 MiB of rows at 512 KiB
    shards)."""
    lead, n = blocks.shape[:-1], blocks.shape[-1] // 8
    planes = jnp.moveaxis(blocks.reshape(lead + (n, 8)), -1, 0)
    # chunk CRC: XOR_i T[7-i][byte_i]  (slicing-by-8, zero-init)
    c = jnp.zeros(lead + (n,), dtype=jnp.uint32)
    for i in range(8):
        c = c ^ jnp.take(jnp.asarray(_SLICE8[7 - i]),
                         planes[i].astype(jnp.int32), axis=0)
    # log-depth combine; pad FRONT with zero chunks (zero-init register
    # stays 0 through a zero prefix, so the result is unchanged)
    span = 8
    while c.shape[-1] > 1:
        m = c.shape[-1]
        if m % 2:
            c = jnp.concatenate(
                [jnp.zeros(lead + (1,), dtype=jnp.uint32), c], axis=-1)
            m += 1
        pairs = c.reshape(lead + (m // 2, 2))
        cols = matrix_cols_u32(shift_matrix(span))
        c = _apply_bitmatrix32(cols, pairs[..., 0]) ^ pairs[..., 1]
        span *= 2
    return c[..., 0]


def _crc32c_zero_seed(blocks: Array) -> Array:
    """Zero-seed CRC register over each row of (..., R, L) uint8, any L:
    parallel slicing + log-depth combine for the 8-aligned head, <=7
    unrolled byte steps for the tail.

    Three things keep the TPU program small and its compile to seconds
    at 512 KiB rows, whatever the caller's row count (sized by
    compiling for a described v5e, tests/test_tpu_compile.py):
    the rows are materialised first (fused into the gathers, the
    recovery decode took the planes' layout and 1 GiB of scratch per
    4 MiB object); leading dims are kept, never merged into R (the
    relayout of (16, 11, L) to (176, L) alone compiled for 55 s); and R
    is zero-padded to a multiple of 8, 16 at least (11 rows compiled
    for 62 s, 8 for 9 s, 16 for 3 s)."""
    n_rows, block_len = blocks.shape[-2:]
    row_pad = max(16, n_rows + (-n_rows % 8)) - n_rows
    if row_pad:
        blocks = jnp.pad(blocks, [(0, 0)] * (blocks.ndim - 2)
                         + [(0, row_pad), (0, 0)])
    blocks = jax.lax.optimization_barrier(blocks)
    main = (block_len // 8) * 8
    if main:
        reg = _crc32c_linear(blocks[..., :main])
    else:
        reg = jnp.zeros(blocks.shape[:-1], dtype=jnp.uint32)
    t0 = jnp.asarray(_T0)
    for t in range(main, block_len):
        byte = blocks[..., t].astype(jnp.uint32)
        reg = (reg >> np.uint32(8)) ^ jnp.take(
            t0, ((reg ^ byte) & np.uint32(0xFF)).astype(jnp.int32))
    return reg[..., :n_rows]


@functools.lru_cache(maxsize=64)
def _crc32c_jit(block_len: int, init: int, xorout: int):
    # init contribution: shift^{block_len}(init), a host constant
    const = apply_shift(init, block_len) ^ xorout if block_len else init ^ xorout

    def fn(blocks: Array) -> Array:
        if blocks.dtype != jnp.uint8 or blocks.ndim < 2:
            raise ValueError(f"blocks must be (..., B, {block_len}) uint8")
        return _crc32c_zero_seed(blocks) ^ np.uint32(const)

    return jax.jit(fn)


def crc32c_blocks(blocks, init: int = 0xFFFFFFFF,
                  xorout: int = 0xFFFFFFFF) -> Array:
    """CRC-32C of each row of (..., B, L) uint8, one value per row with
    the leading dims kept. Defaults = standard CRC-32C; use init=seed,
    xorout=0 for the reference's raw ceph_crc32c(seed, ·) convention
    (what BlueStore/HashInfo store, seed -1)."""
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    return _crc32c_jit(int(blocks.shape[-1]), init & 0xFFFFFFFF,
                       xorout & 0xFFFFFFFF)(blocks)


@functools.lru_cache(maxsize=64)
def _crc32c_extend_jit(block_len: int):
    shift_cols = matrix_cols_u32(shift_matrix(block_len))

    def fn(regs: Array, blocks: Array) -> Array:
        # register after block with runtime seed r: shift^{len}(r) ^ L(block)
        return _apply_bitmatrix32(shift_cols, regs) ^ _crc32c_zero_seed(blocks)

    return jax.jit(fn)


@functools.lru_cache(maxsize=256)
def _inv_shift_cols(pad: int) -> np.ndarray:
    return matrix_cols_u32(inv_shift_matrix(pad))


def _unshift_host(regs: np.ndarray, pad: int) -> np.ndarray:
    """Un-advance registers through `pad` zero bytes — a 32-constant XOR
    on host uint32s, no device dispatch."""
    cols = _inv_shift_cols(pad)
    bits = (regs[:, None] >> np.arange(32, dtype=np.uint32)) & np.uint32(1)
    terms = np.where(bits.astype(bool), cols[None, :], np.uint32(0))
    return np.bitwise_xor.reduce(terms, axis=1)


def crc32c_extend(regs, blocks) -> Array:
    """Advance raw CRC registers through one block each: regs (B,) uint32
    current registers (the ceph_crc32c chaining state), blocks (B, L)
    uint8. Returns the new registers — the batched form of
    ceph_crc32c(reg, block), used by HashInfo appends across shards.

    The kernel specializes on block length; arbitrary lengths would
    compile (and cache-thrash) one program each, so blocks are zero-
    padded up to the next power of two and the padding's register shift
    is undone afterwards with the cached inverse GF(2) shift matrix —
    a 32-bit host fixup, not a data pass.
    """
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    regs = jnp.asarray(regs, dtype=jnp.uint32)
    L = int(blocks.shape[1])
    bucket = max(64, 1 << (L - 1).bit_length()) if L else 0
    pad = bucket - L
    if pad:
        blocks = jnp.pad(blocks, ((0, 0), (0, pad)))
    out = _crc32c_extend_jit(bucket)(regs, blocks)
    if pad:
        # out = shift^pad(true): undo the zero-padding's linear shift
        # (host fixup, then back to a device array so the return type is
        # a jax Array on every path)
        out = jnp.asarray(_unshift_host(np.asarray(out, np.uint32), pad))
    return out


# ----------------------------------------------------------------- xxh32

_P32 = tuple(np.uint32(p) for p in
             (2654435761, 2246822519, 3266489917, 668265263, 374761393))


def _rotl32(x: Array, r: int) -> Array:
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def _lanes_u32(blocks: Array) -> Array:
    """(B, L) uint8 -> (B, L//4) uint32 little-endian lanes."""
    B, L = blocks.shape
    b = blocks.reshape(B, L // 4, 4).astype(jnp.uint32)
    return (b[..., 0] | (b[..., 1] << np.uint32(8)) |
            (b[..., 2] << np.uint32(16)) | (b[..., 3] << np.uint32(24)))


@functools.lru_cache(maxsize=64)
def _xxh32_jit(block_len: int, seed: int):
    s = np.uint32(seed)
    n_stripes = block_len // 16
    after = n_stripes * 16

    def fn(blocks: Array) -> Array:
        B = blocks.shape[0]
        if n_stripes:
            lanes = _lanes_u32(blocks[:, :after]).reshape(B, n_stripes, 4)

            def body(i, vs):
                v1, v2, v3, v4 = vs
                ln = lanes[:, i, :]

                def rnd(v, lane):
                    return _rotl32(v + lane * _P32[1], 13) * _P32[0]
                return (rnd(v1, ln[:, 0]), rnd(v2, ln[:, 1]),
                        rnd(v3, ln[:, 2]), rnd(v4, ln[:, 3]))

            init = (jnp.full((B,), (seed + 2654435761 + 2246822519)
                            & 0xFFFFFFFF, jnp.uint32),
                    jnp.full((B,), (seed + 2246822519) & 0xFFFFFFFF,
                             jnp.uint32),
                    jnp.full((B,), s, jnp.uint32),
                    jnp.full((B,), (seed - 2654435761) & 0xFFFFFFFF,
                             jnp.uint32))
            v1, v2, v3, v4 = jax.lax.fori_loop(0, n_stripes, body, init)
            h = (_rotl32(v1, 1) + _rotl32(v2, 7) + _rotl32(v3, 12) +
                 _rotl32(v4, 18))
        else:
            h = jnp.full((B,), s + _P32[4], jnp.uint32)
        h = h + np.uint32(block_len)
        p = after
        while p + 4 <= block_len:
            lane = _lanes_u32(blocks[:, p:p + 4])[:, 0]
            h = _rotl32(h + lane * _P32[2], 17) * _P32[3]
            p += 4
        while p < block_len:
            h = _rotl32(h + blocks[:, p].astype(jnp.uint32) * _P32[4],
                        11) * _P32[0]
            p += 1
        h = h ^ (h >> np.uint32(15))
        h = h * _P32[1]
        h = h ^ (h >> np.uint32(13))
        h = h * _P32[2]
        return h ^ (h >> np.uint32(16))

    return jax.jit(fn)


def xxh32_blocks(blocks, seed: int = 0) -> Array:
    """XXH32 of each row of (B, L) uint8."""
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    return _xxh32_jit(int(blocks.shape[1]), seed & 0xFFFFFFFF)(blocks)


# ----------------------------------------------------------------- xxh64
# uint64 as (hi, lo) uint32 limb pairs — no dependence on jax_enable_x64.

_P64 = (11400714785074694791, 14029467366897019727, 1609587929392839161,
        9650029242287828579, 2870177450012600261)


def _c64(v: int):
    v &= (1 << 64) - 1
    return (np.uint32(v >> 32), np.uint32(v & 0xFFFFFFFF))


def _add64(a, b):
    ah, al = a
    bh, bl = b
    lo = al + bl
    carry = (lo < al).astype(jnp.uint32)
    return (ah + bh + carry, lo)


def _xor64(a, b):
    return (a[0] ^ b[0], a[1] ^ b[1])


def _mulhi32(a: Array, b: Array) -> Array:
    a0, a1 = a & np.uint32(0xFFFF), a >> np.uint32(16)
    b0, b1 = b & np.uint32(0xFFFF), b >> np.uint32(16)
    lo = a0 * b0
    m1 = a1 * b0
    m2 = a0 * b1
    t = (lo >> np.uint32(16)) + (m1 & np.uint32(0xFFFF)) + \
        (m2 & np.uint32(0xFFFF))
    return a1 * b1 + (m1 >> np.uint32(16)) + (m2 >> np.uint32(16)) + \
        (t >> np.uint32(16))


def _mul64(a, b):
    ah, al = a
    bh, bl = b
    lo = al * bl
    hi = _mulhi32(al, bl) + al * bh + ah * bl
    return (hi, lo)


def _rotl64(a, r: int):
    ah, al = a
    if r == 0:
        return a
    if r < 32:
        return ((ah << np.uint32(r)) | (al >> np.uint32(32 - r)),
                (al << np.uint32(r)) | (ah >> np.uint32(32 - r)))
    if r == 32:
        return (al, ah)
    r -= 32
    return ((al << np.uint32(r)) | (ah >> np.uint32(32 - r)),
            (ah << np.uint32(r)) | (al >> np.uint32(32 - r)))


def _shr64(a, s: int):
    ah, al = a
    if s == 0:
        return a
    if s < 32:
        return (ah >> np.uint32(s),
                (al >> np.uint32(s)) | (ah << np.uint32(32 - s)))
    if s == 32:
        return (jnp.zeros_like(ah), ah)
    return (jnp.zeros_like(ah), ah >> np.uint32(s - 32))


def _round64(acc, lane):
    acc = _add64(acc, _mul64(lane, _c64(_P64[1])))
    acc = _rotl64(acc, 31)
    return _mul64(acc, _c64(_P64[0]))


def _merge64(h, v):
    zero = (jnp.zeros_like(h[0]), jnp.zeros_like(h[1]))
    h = _xor64(h, _round64(zero, v))
    return _add64(_mul64(h, _c64(_P64[0])), _c64(_P64[3]))


def _broadcast_c64(v: int, B: int):
    hi, lo = _c64(v)
    return (jnp.full((B,), hi, jnp.uint32), jnp.full((B,), lo, jnp.uint32))


@functools.lru_cache(maxsize=64)
def _xxh64_jit(block_len: int, seed: int):
    n_stripes = block_len // 32
    after = n_stripes * 32

    def lane64(blocks, p):
        """8 bytes at static offset p -> (hi, lo) uint32 pair."""
        lanes = _lanes_u32(blocks[:, p:p + 8])
        return (lanes[:, 1], lanes[:, 0])

    def fn(blocks: Array):
        B = blocks.shape[0]
        if n_stripes:
            lanes = _lanes_u32(blocks[:, :after]).reshape(B, n_stripes, 8)

            def body(i, vs):
                out = []
                for j in range(4):
                    lane = (lanes[:, i, 2 * j + 1], lanes[:, i, 2 * j])
                    out.append(_round64(vs[j], lane))
                return tuple(out)

            init = (_broadcast_c64(seed + _P64[0] + _P64[1], B),
                    _broadcast_c64(seed + _P64[1], B),
                    _broadcast_c64(seed, B),
                    _broadcast_c64(seed - _P64[0], B))
            v1, v2, v3, v4 = jax.lax.fori_loop(0, n_stripes, body, init)
            h = _add64(_add64(_rotl64(v1, 1), _rotl64(v2, 7)),
                       _add64(_rotl64(v3, 12), _rotl64(v4, 18)))
            for v in (v1, v2, v3, v4):
                h = _merge64(h, v)
        else:
            h = _broadcast_c64(seed + _P64[4], B)
        h = _add64(h, _broadcast_c64(block_len, B))
        p = after
        while p + 8 <= block_len:
            zero = (jnp.zeros_like(h[0]), jnp.zeros_like(h[1]))
            h = _xor64(h, _round64(zero, lane64(blocks, p)))
            h = _add64(_mul64(_rotl64(h, 27), _c64(_P64[0])), _c64(_P64[3]))
            p += 8
        if p + 4 <= block_len:
            lane = (jnp.zeros((blocks.shape[0],), jnp.uint32),
                    _lanes_u32(blocks[:, p:p + 4])[:, 0])
            h = _xor64(h, _mul64(lane, _c64(_P64[0])))
            h = _add64(_mul64(_rotl64(h, 23), _c64(_P64[1])), _c64(_P64[2]))
            p += 4
        while p < block_len:
            lane = (jnp.zeros((blocks.shape[0],), jnp.uint32),
                    blocks[:, p].astype(jnp.uint32))
            h = _xor64(h, _mul64(lane, _c64(_P64[4])))
            h = _mul64(_rotl64(h, 11), _c64(_P64[0]))
            p += 1
        h = _xor64(h, _shr64(h, 33))
        h = _mul64(h, _c64(_P64[1]))
        h = _xor64(h, _shr64(h, 29))
        h = _mul64(h, _c64(_P64[2]))
        h = _xor64(h, _shr64(h, 32))
        return jnp.stack([h[0], h[1]], axis=-1)  # (B, 2): [hi, lo]

    return jax.jit(fn)


def xxh64_blocks(blocks, seed: int = 0) -> Array:
    """XXH64 of each row of (B, L) uint8; returns (B, 2) uint32 [hi, lo]
    pairs (combine as (hi << 32) | lo)."""
    blocks = jnp.asarray(blocks, dtype=jnp.uint8)
    return _xxh64_jit(int(blocks.shape[1]),
                      seed & ((1 << 64) - 1))(blocks)
