"""Chunk-dim tiling — stream stripes bigger than device memory.

The long-context analog of the reference's striping stack (SURVEY.md
§2.7 P7 and §5: scaling "sequence length" here means scaling object/
stripe size — ref: src/libradosstriper/ client-side striping,
ECUtil::stripe_info_t round-robin layout, BlueStore extent/blob
splitting). GF codecs are POSITIONWISE over the byte axis: parity byte
i depends only on data bytes i across shards, so a stripe of any
length streams through a fixed-shape kernel in tiles with bit-exact
results.

Two lowering levels, composable:

* `make_tiled_encoder` — device-side tiling: ONE jit whose lax.map
  walks (T, B, k, tile) so XLA's working set stays one tile regardless
  of chunk length. Use when the full array fits in HBM but a monolithic
  launch would blow VMEM or compile poorly.
* `StreamingCodec` — host-side tiling with async double buffering:
  chunk bytes live on the HOST (bigger than HBM); tile i+1's
  host->device transfer is enqueued while tile i computes (JAX's async
  dispatch overlaps them), and results land in a preallocated host
  buffer one tile behind. Use for > HBM objects — the P5/P7 dataflow.

Both run make_encoder's program for the matrix, and — like
make_encoder — both serve ENCODE and DECODE alike: the "matrix" is
any static GF matrix (coding matrix or inverted decode matrix).
"""

from __future__ import annotations

import functools

import numpy as np

from .rs_kernels import apply_matrix, make_encoder


@functools.lru_cache(maxsize=64)
def _tiled_encoder_cached(matrix_bytes: bytes, m: int, k: int,
                          tile: int):
    import jax
    import jax.numpy as jnp

    matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, k)

    @jax.jit
    def enc(data):
        B, kk, L = data.shape
        if kk != k:
            raise ValueError(f"data has {kk} shards, matrix wants {k}")
        if L % tile:
            raise ValueError(f"chunk len {L} not a multiple of "
                             f"tile {tile}")
        t = L // tile
        # (B, k, T, tile) -> (T, B, k, tile): tiles become the mapped
        # leading axis; lax.map emits ONE tile program + a loop
        tiles = jnp.moveaxis(data.reshape(B, kk, t, tile), 2, 0)
        out = jax.lax.map(functools.partial(apply_matrix, matrix), tiles)
        return jnp.moveaxis(out, 0, 2).reshape(B, m, L)

    return enc


def make_tiled_encoder(matrix: np.ndarray, tile: int = 1 << 20):
    """Jitted (B, k, L) -> (B, m, L) that internally lax.maps over
    L/tile chunk tiles. L must be a multiple of `tile` (the stripe
    layer already pads chunks to alignment). Process-wide cached per
    (matrix, tile)."""
    matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
    m, k = matrix.shape
    return _tiled_encoder_cached(matrix.tobytes(), m, k, int(tile))


class StreamingCodec:
    """Host-resident stripes streamed tile-by-tile through the device.

    encode(data) accepts a HOST (B, k, L) uint8 array of any L and
    returns host (B, m, L) parity without ever materializing more than
    `depth` tiles on device. The per-tile kernel shape is fixed, so one
    compile serves every stripe length (ragged tails are zero-padded —
    padding encodes to padding for any linear code, so the tail slice
    of the output is exact).
    """

    def __init__(self, matrix: np.ndarray, tile: int = 1 << 20,
                 depth: int = 2, perf=None):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        self.m, self.k = matrix.shape
        self.tile = int(tile)
        self.depth = depth  # in-flight tiles (double buffering = 2)
        # make_encoder's program cache is process-wide and keyed by the
        # matrix: every instance with one matrix shares ONE jitted kernel
        self._fn = make_encoder(matrix)
        # optional instrumentation: a PerfCounters with
        # stream_launches / stream_bytes / stream_drain_time declared
        # (the daemon's "ec" logger fits; None = uncounted)
        self.perf = perf
        # reusable ragged-tail staging buffer: allocated once per
        # (B, k, tile) shape instead of a fresh zeroed array per
        # encode call's tail tile
        self._pad: np.ndarray | None = None

    def encode(self, data: np.ndarray, out: np.ndarray | None = None
               ) -> np.ndarray:
        import jax

        data = np.asarray(data)
        if data.ndim != 3 or data.shape[1] != self.k \
                or data.dtype != np.uint8:
            raise ValueError(
                f"want (B, {self.k}, L) uint8, got "
                f"{data.shape} {data.dtype}")
        B, _, L = data.shape
        if out is None:
            out = np.empty((B, self.m, L), dtype=np.uint8)
        elif out.shape != (B, self.m, L) or out.dtype != np.uint8:
            raise ValueError(f"out must be ({B}, {self.m}, {L}) uint8")
        tl = self.tile
        n_tiles = max(1, -(-L // tl))
        inflight: list[tuple[int, int, object]] = []  # (off, len, dev)

        def drain(entry):
            # device_get writes STRAIGHT into the caller's out slice
            # (no intermediate host array + second copy); the D2H for
            # this tile was already started at launch, so by the time
            # the pipeline is `depth` deep this is mostly a wait
            off, ln, dev = entry
            if self.perf is not None:
                with self.perf.time("stream_drain_time"):
                    out[:, :, off:off + ln] = \
                        jax.device_get(dev)[:, :, :ln]
            else:
                out[:, :, off:off + ln] = jax.device_get(dev)[:, :, :ln]

        for ti in range(n_tiles):
            off = ti * tl
            ln = min(tl, L - off)
            src = data[:, :, off:off + tl]
            if ln < tl:  # ragged tail: zero-pad to the fixed shape,
                # reusing ONE preallocated staging buffer per shape
                if self._pad is None or \
                        self._pad.shape != (B, self.k, tl):
                    self._pad = np.zeros((B, self.k, tl),
                                         dtype=np.uint8)
                else:
                    self._pad[:, :, ln:] = 0
                self._pad[:, :, :ln] = src
                src = self._pad
            # enqueue: device_put + launch return immediately (async
            # dispatch); compute of tile i overlaps staging of i+1,
            # and the result's D2H copy starts NOW instead of when
            # drain() blocks on it
            dev = self._fn(jax.device_put(src))
            if self.perf is not None:
                self.perf.inc_many((("stream_launches", 1),
                                    ("stream_bytes", int(src.size))))
            try:
                dev.copy_to_host_async()
            except AttributeError:
                pass   # non-jax array stub
            inflight.append((off, ln, dev))
            if len(inflight) >= self.depth:
                drain(inflight.pop(0))
        while inflight:
            drain(inflight.pop(0))
        return out
