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

Two faces of one matrix, and both lowerings serve both:

  device-resident (`make_encoder`, `apply_matrix`) — uint8 (B, k, L) on
      the device in, uint8 (B, m, L) on the device out: what the served
      path composes with its crc programs in one launch
      (`ECBackend._fused_write_fn`, the degraded read's decode,
      recovery, `ops/streaming`).

  host to host (`make_host_encoder`) — host rows in, host rows out:
      `encode_chunks` / `decode_chunks` of the RS, LRC, SHEC and Clay
      coders and `native/server.py`. The bytes cross the link as uint32
      WORDS both ways (a free view on the host), the lowerings run on
      the words themselves (`_apply_bitlinear_words`,
      `_apply_mxu_words`: four bytes a lane, no byte leaves its place),
      and the parity comes back as a flat (N, 128) uint32 array whose
      device layout is byte for byte row-major host memory. A uint8
      result leaves the device tiled, with the batch where m = 3 should
      be, and the runtime's host threads undo that at 0.6 GB/s; bytes
      cast to words on the device cost more than they save (the cast
      wants a minor dimension of 4: 73.5 GB of padding, or a uint8
      relayout at 1.4 GB/s). PERF.md, PR 30, has every form tried.
      A call large enough (`_sub_batch_rows`) goes in as sub-batches
      of the batch axis, each put and launched without a wait between,
      so the copy in, the program and the next copy's layout overlap;
      the parities come home as one array (PERF.md, PR 35).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..gf.tables import bit_powers, matrix_to_bitmatrix
from ..utils.perf_counters import PerfCountersBuilder, g_perf_counters

Array = jax.Array

#: the low bit of each of a word's four bytes
_BYTE_LANES = np.uint32(0x01010101)


def _check(data: Array, k: int) -> None:
    if data.ndim != 3:
        raise ValueError(f"data must be (batch, k, L) uint8, got {data.shape}")
    if data.shape[1] != k:
        raise ValueError(f"data has {data.shape[1]} shards, matrix expects {k}")


def _is_words(data: Array) -> bool:
    """Bytes (uint8, the device-resident callers' form) or the host
    face's words (uint32: four consecutive bytes of a row a lane)."""
    if data.dtype == jnp.uint8:
        return False
    if data.dtype == jnp.uint32:
        return True
    raise ValueError(f"data must be uint8 bytes or uint32 words, got {data.dtype}")


# ---------------------------------------------------------------- bitlinear

def _apply_bitlinear(matrix: np.ndarray, data: Array) -> Array:
    """parity[i] = XOR_j XOR_b bit_b(data[j]) ? (matrix[i,j] * 2^b) : 0."""
    m, k = matrix.shape
    _check(data, k)
    P = bit_powers()[matrix]  # (m, k, 8) uint8 numpy constants
    if _is_words(data):
        return _apply_bitlinear_words(P, data)
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


def _apply_bitlinear_words(P: np.ndarray, words: Array) -> Array:
    """The same terms on four bytes a lane: bit b of each byte is taken
    with one AND, spread to a 0x00/0xFF byte mask with no carry between
    bytes, and ANDed with the constant in all four. One accumulator a
    parity row, stacked at the end: as one (B, m, W) broadcast term,
    the bytes' way, the result takes the batch-second-minor layout and
    the flat form costs a relayout of 8.3 ms a 128 MiB call on the chip
    (16.1 against 5.4 ms of device time, PERF.md, PR 30)."""
    m, k, _ = P.shape
    acc = [None] * m
    for j in range(k):
        dj = words[:, j, :]  # (B, W)
        for b in range(8):
            if not P[:, j, b].any():
                continue
            bits = (dj >> b) & _BYTE_LANES
            mask = (bits << 8) - bits
            for i in range(m):
                if P[i, j, b]:
                    term = mask & (P[i, j, b] * _BYTE_LANES)
                    acc[i] = term if acc[i] is None else acc[i] ^ term
    zero = jnp.zeros_like(words[:, 0, :])
    return jnp.stack([zero if a is None else a for a in acc], axis=1)


# ---------------------------------------------------------------- mxu

def _apply_mxu(matrix: np.ndarray, data: Array) -> Array:
    """Bit-plane int8 matmul on the MXU; sum mod 2 == XOR accumulate."""
    m, k = matrix.shape
    _check(data, k)
    B, _, L = data.shape
    bm = matrix_to_bitmatrix(matrix)  # (m*8, k*8) in {0,1}
    if _is_words(data):
        return _apply_mxu_words(bm, data)
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


def _apply_mxu_words(bm: np.ndarray, words: Array) -> Array:
    """The dense form on words: bit s of byte q of a word is bit 8q+s of
    the lane, so the planes come out of the words by shifts as they do
    out of bytes, a byte position more along the free axis, and the
    parity bits go back in by the same shifts. No byte is moved."""
    B, k, W = words.shape
    m = bm.shape[0] // 8
    # [s, q] = 8q + s: planes contract over (k, s); (q, W) is free
    shifts = (jnp.arange(8, dtype=jnp.uint32)[:, None]
              + 8 * jnp.arange(4, dtype=jnp.uint32)[None, :])
    bits = (words[:, :, None, None, :] >> shifts[None, None, :, :, None]) & 1
    x = bits.reshape(B, k * 8, 4 * W).astype(jnp.int8)
    pbits = jax.lax.dot_general(
        jnp.asarray(bm, dtype=jnp.int8), x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (m*8, B, 4*W)
    pbits = (pbits & 1).astype(jnp.uint32).transpose(1, 0, 2)
    pbits = pbits.reshape(B, m, 8, 4, W) << shifts[None, None, :, :, None]
    return jnp.bitwise_xor.reduce(pbits, axis=(2, 3))


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


#: bytes of one row of the flat transfer form: 128 words
_FLAT_ROW = 512


@functools.lru_cache(maxsize=128)
def _make_jitted_words(matrix_bytes: bytes, m: int, k: int):
    """The host face's program: words in, the parity's words out as
    (N, 128) uint32, whose (8, 128) tiles lie in device memory in
    row-major order, so the copy to the host has nothing to de-tile or
    transpose. (A 1-D result is as linear but compiles in 29 s where
    this takes 2: described-chip compiles, PERF.md, PR 30.)"""
    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k)
    lowering = _lowering(matrix)

    def words_program(words):
        return lowering(matrix, words).reshape(-1, _FLAT_ROW // 4)
    return jax.jit(words_program)


def pow2_bucket(n: int) -> int:
    """Next power of two >= n (>= 1): the shared batch-bucketing rule
    that keeps variable per-PG batch sizes from compiling one XLA
    program per distinct B."""
    return 1 << max(0, int(n - 1).bit_length())


def _pad_rows(arr, rows: int):
    """`arr` on the device with its leading dim zero-padded to `rows`."""
    arr = jnp.asarray(arr)
    if arr.shape[0] != rows:
        arr = jnp.pad(arr, [(0, rows - arr.shape[0])]
                      + [(0, 0)] * (arr.ndim - 1))
    return arr


def pad_to_bucket(arr):
    """`arr` on the device with its leading dim padded to the pow2
    bucket, and the leading dim it had."""
    arr = jnp.asarray(arr)
    B = arr.shape[0]
    return _pad_rows(arr, pow2_bucket(B)), B


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


# A call of the host face whose bucket holds at least two sub-batches of
# this many bytes of operand crosses the link as a pipeline of them
# (`make_host_encoder`); a smaller one is the single launch it always
# was. One v5e, k=8 m=3, rows of 512 KiB, ms a warm call by the operand
# a sub-batch holds (PR 35's chip run; PERF.md has the table):
#
#   call (operand)      one launch   4 MiB    8 MiB   16 MiB   32 MiB
#   B=1    4 MiB           2.47        -        -        -        -
#   B=2    8 MiB           3.27      3.06       -        -        -
#   B=4   16 MiB           4.65      4.25     4.25       -        -
#   B=8   32 MiB           7.62      6.98     6.24     6.68       -
#   B=16  64 MiB          13.79     12.28     9.89    10.72    12.36
#   B=32 128 MiB          39.01     37.03    30.12    31.58    33.82
#
# Under 8 MiB a put costs more than its copy hides (32 puts a call at
# 4 MiB); over it the first sub-batch's way in, which nothing hides,
# grows. The whole gain is the device and the linearize hidden under
# the H2D copy: the way home stays one copy, because a result fetched
# in pieces has to be assembled into a fresh host array and first-touch
# page faults make that pass dearer (53 ms for 48 MiB on one thread,
# 24 on four) than everything it would hide.
_SUB_BATCH_BYTES = 8 << 20


def _sub_batch_rows(rows: int, row_bytes: int) -> int:
    """Rows of a bucket of `rows` that one launch of the host face
    takes: all of them, unless the bucket holds at least two sub-batches
    of `_SUB_BATCH_BYTES` of operand. From the call's shape alone; both
    counts are powers of two, so the sub-batches tile the bucket."""
    sub = pow2_bucket(-(-_SUB_BATCH_BYTES // row_bytes))
    return sub if 2 * sub <= rows else rows


#: how often the host face is called and how often it pipelines
#: (`perf dump` shows the process-wide collection's loggers)
_codec_perf = g_perf_counters.add(
    PerfCountersBuilder("codec")
    .add_u64_counter("host_face_calls",
                     "calls of a matrix's host face (encode_chunks, "
                     "decode_chunks)")
    .add_u64_counter("host_face_pipelined_calls",
                     "of those, calls large enough to go as sub-batches")
    .add_u64_counter("host_face_sub_batches",
                     "sub-batches launched by pipelined calls")
    .add_u64_counter("host_face_bytes_in", "operand bytes handed to the face")
    .create_perf_counters())


def make_host_encoder(matrix: np.ndarray):
    """The host-to-host face of the matrix's program: host uint8
    (B, k, L) in, host uint8 (B, m, L) out, C-contiguous, every byte on
    the host when the call returns (`encode_chunks`, `decode_chunks`).

    Both ways the bytes travel as uint32 words. The rows go in as the
    free view `data.view(uint32)`, (B, k, L/4), the program multiplies on
    the words themselves (four bytes a lane; a byte never changes its
    place in a word, so no byte order is assumed), and the parity comes
    back as ONE flat (N, 128) uint32 array, which the host views as bytes
    again. A uint8 result of this shape leaves the device in layout
    `{2,0,1:T(8,128)(4,1)}` (m = 3 does not fill a tile, so the batch
    takes its place) and the runtime's host threads de-tile and
    transpose it: 84 of a 108 ms call at 32 x 4 MiB, against 12 ms for
    the same bytes as words (PERF.md, PR 30). Turning bytes into words
    on the device costs more than it saves (XLA relayouts uint8 at
    1.4 GB/s), which is why the multiply runs on words and not before a
    cast.

    One algorithm for every shape: a row length that is no multiple of
    512 bytes (128 words, a row of the flat form) is zero-padded here and
    the pad sliced off the view (the one case that copies on the host:
    zero columns give zero parity); the batch is bucketed to a power of
    two as `make_encoder` does, and the bucket's spare rows are sliced
    off the view, not on the device.

    A large call is a pipeline (`_sub_batch_rows`): the bucket goes in
    as equal sub-batches of the batch axis (host views, no host copy),
    each put and launched without waiting for the one before (the same
    program at the sub-batch's shape: one compile, S launches), so the
    H2D copy of sub-batch i+1 runs under the program of sub-batch i;
    the S parities are concatenated on the device into the one flat
    array and come home in one copy. Every operand and parity piece
    stays on the device until the result is on the host. A small call
    is one put and one launch, as before. Device-resident callers keep
    `make_encoder`: its program, shape and dtype are unchanged."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    m, k = matrix.shape
    jitted = _make_jitted_words(matrix.tobytes(), m, k)

    def encode(data) -> np.ndarray:
        data = np.asarray(data, np.uint8)
        _check(data, k)
        B, _, L = data.shape
        tail = -L % _FLAT_ROW
        if tail:
            data = np.pad(data, ((0, 0), (0, 0), (0, tail)))
        host = np.ascontiguousarray(data).view(np.uint32)
        sub = _sub_batch_rows(pow2_bucket(B), k * (L + tail))
        # a bucket's empty sub-batches are not sent; under the line the
        # one sub-batch is the bucket
        launches = max(1, -(-B // sub))
        # both lists live until the parity is home: the device holds the
        # whole call, however many launches it is
        words, parts = [], []
        for i in range(launches):
            words.append(_pad_rows(host[i * sub:(i + 1) * sub], sub))
            parts.append(jitted(words[-1]))
        flat = parts[0] if launches == 1 else jnp.concatenate(parts)
        pipelined = int(launches > 1)
        _codec_perf.inc_many((
            ("host_face_calls", 1),
            ("host_face_pipelined_calls", pipelined),
            ("host_face_sub_batches", launches * pipelined),
            ("host_face_bytes_in", B * k * L)))
        parity = np.asarray(flat).view(np.uint8).reshape(launches * sub, m,
                                                         L + tail)
        return np.ascontiguousarray(parity[:B, :, :L])
    return encode
