"""Batched checksum kernels for TPU.

The device-side replacement for the reference's CPU checksum hot loops
(ref: src/common/crc32c_intel_fast_asm.s PCLMUL folding,
src/common/crc32c_aarch64.c, bundled src/xxHash) — the bulk path behind
deep-scrub (ref: src/osd/scrubber + ECBackend::be_deep_scrub) and
BlueStore per-block verify (ref: src/os/bluestore/Checksummer.h).

Unit of work: (batch, block_len) uint8 — many equal-sized blocks checked
in one launch (exactly the Checksummer csum_block_size model).

crc32c lowering: CRC is GF(2)-linear in the message, so instead of the
CPU's serial byte loop the whole program is linear maps on uint32 lanes
  1. chunk stage: a row is cut into 8 contiguous byte planes and lane j
     takes one byte of each. A crc table is linear in its index
     (T[b] = XOR_k bit_k(b) * T[1 << k]: b as a register, advanced
     through one zero byte), so a byte's contribution is 8 masked XORs
     of constants, the first 8 columns of a shift matrix — VPU work.
     (The table lookup it
     replaces, jnp.take with ~1 M indices, cost 8.6 ms a plane on a
     v5e: 69 of the 71 ms of a served write's launch.)
  2. combine: crc(A || B) = shift_{|B|}(crc A) ^ crc(B) in any
     grouping, so the first half of the lanes is folded onto the
     second, log2(n) times; each level's "advance the register by h
     zero bytes" is a constant 32x32 GF(2) matrix, 32 masked XORs.
     Halves are contiguous slices of the long minor axis (folding
     neighbours instead de-interleaves it with stride 2 at every
     level: 13x the bytes moved),
  3. the (static) init/xorout contribution is a host constant.
No gather, no transpose and no per-byte dependency chain: wall time
scales with the VPU, not the byte count.

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

from .reference import (apply_shift, inv_shift_matrix, matrix_cols_u32,
                        shift_matrix)

Array = jax.Array


def _apply_bitmatrix32(cols: np.ndarray, x: Array) -> Array:
    """y = M @ x over GF(2): M given as uint32 column constants, one
    for each low bit of the uint32 lanes of x (32 for a register, the
    first 8 for a widened byte)."""
    acc = jnp.zeros_like(x)
    for b, c in enumerate(cols):
        c = int(c)
        if c == 0:
            continue
        mask = jnp.uint32(0) - ((x >> np.uint32(b)) & np.uint32(1))
        acc = acc ^ (mask & np.uint32(c))
    return acc


def _crc32c_linear(blocks: Array) -> Array:
    """Zero-init CRC register over each row of (..., L) uint8, L % 8 == 0.

    A byte is a register with its low 8 bits set (T[b] is b advanced
    through one zero byte), so a row's register is
    XOR_p shift^{L-p}(byte_p) over its byte positions p, in any
    grouping. The grouping here is the one the TPU tiling likes: the
    long axis stays minor from the first op to the last, and is only
    ever cut into contiguous runs. Byte planes are the 8 contiguous
    eighths of a row, (..., n) each — never (..., n, 8), whose minor
    dim of 8 padded every tile 16x (3 GiB of scratch for 5.5 MiB of
    rows at 512 KiB shards), and never a transpose. Lane j of the
    chunk stage holds bytes j, n + j, ..., 7n + j; the combine then
    folds the first half of the lanes onto the second until one is
    left, the lanes one byte apart."""
    lead, n = blocks.shape[:-1], blocks.shape[-1] // 8
    c = jnp.zeros(lead + (n,), dtype=jnp.uint32)
    for i in range(8):
        plane = blocks[..., i * n:(i + 1) * n].astype(jnp.uint32)
        cols = matrix_cols_u32(shift_matrix((7 - i) * n + 1))
        c = c ^ _apply_bitmatrix32(cols[:8], plane)
    # an odd lane count is padded at the FRONT with a zero lane (a zero
    # register stays 0 through a zero prefix, so nothing changes)
    while c.shape[-1] > 1:
        if c.shape[-1] % 2:
            c = jnp.concatenate(
                [jnp.zeros(lead + (1,), dtype=jnp.uint32), c], axis=-1)
        h = c.shape[-1] // 2
        cols = matrix_cols_u32(shift_matrix(h))
        c = _apply_bitmatrix32(cols, c[..., :h]) ^ c[..., h:]
    return c[..., 0]


def _crc32c_zero_seed(blocks: Array) -> Array:
    """Zero-seed CRC register over each row of (..., R, L) uint8, any L.
    A length that is no multiple of 8 is zero-padded at the front (the
    register passes a zero prefix unchanged). Leading dims are kept,
    never merged into R (the relayout of (16, 11, L) to (176, L) alone
    compiled for 55 s), and walked one (R, L) object at a time:
    traced over all objects at once the program asks for more scratch
    than the rows it reads (sized by compiling for a described v5e,
    tests/test_tpu_compile.py)."""
    block_len = blocks.shape[-1]
    if block_len == 0:
        return jnp.zeros(blocks.shape[:-1], dtype=jnp.uint32)
    if block_len % 8:
        blocks = jnp.pad(blocks, [(0, 0)] * (blocks.ndim - 1)
                         + [(-block_len % 8, 0)])
    if blocks.ndim == 2:
        return _crc32c_linear(blocks)
    objects = blocks.reshape((int(np.prod(blocks.shape[:-2])),)
                             + blocks.shape[-2:])
    return jax.lax.map(_crc32c_linear, objects).reshape(blocks.shape[:-1])


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
