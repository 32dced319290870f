"""Multi-chip sharding: the rebuild's distributed communication backend.

The reference fans EC sub-ops out over OSDs through its AsyncMessenger
(ref: src/msg/async/, ECBackend::handle_sub_write/_reply scatter/gather —
SURVEY.md §2.5, §5 "Distributed communication backend"). TPU-native, that
becomes a device mesh + XLA collectives over ICI:

  axis "dp"    — data parallelism over the object batch (the reference's
                 many-PGs-in-flight axis, P2 in SURVEY.md §2.7);
  axis "shard" — shard placement: the k+m chunks of each stripe live on
                 different devices, like chunks on different OSDs (P1/P3).

Encode scatters parity shards across the "shard" axis (XLA inserts the
scatter from the output sharding); degraded decode gathers surviving
shards over ICI (XLA inserts the all-gather from the survivor indexing).
No hand-written NCCL-style calls — shardings in, collectives out.

Multi-host: the same Meshes span hosts via jax.distributed; ICI carries
the "shard" axis within a pod, DCN carries "dp" across pods.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..gf.numpy_ref import decode_matrix
from ..ops.rs_kernels import apply_matrix


def encode_all_chunks(coder, obj: np.ndarray) -> np.ndarray:
    """(n_chunks, chunk_len) dense stack of every chunk of one object —
    the bridge from a codec's dict-shaped encode() into the sharded
    mesh paths (and their tests)."""
    n = coder.get_chunk_count()
    enc = coder.encode(range(n), obj)
    return np.stack([np.asarray(enc[i]) for i in range(n)])


def default_mesh(devices=None, shard: int = 2) -> Mesh:
    """(dp, shard) mesh over the given (default: all) devices.

    `shard` devices hold disjoint subsets of each stripe's k+m chunks;
    the rest of the devices form the batch-parallel axis. `shard` must
    divide the device count — a silently different topology than the one
    the caller modeled would misplace every shard group.
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    n = devices.size
    if shard < 1 or n % shard:
        raise ValueError(
            f"shard axis {shard} does not divide device count {n}; "
            f"pick a divisor (e.g. {[d for d in (1, 2, 4, 8) if n % d == 0]})")
    return Mesh(devices.reshape(n // shard, shard), ("dp", "shard"))


def chunk_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding of a (batch, n_chunks, L) chunk tensor: batch over dp,
    chunk slots over shard — each device is an 'OSD group' holding its
    slice of every stripe."""
    return NamedSharding(mesh, P("dp", "shard", None))


def data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("dp", None, None))


def padded_slots(n_chunks: int, mesh: Mesh) -> int:
    """Chunk-slot count padded up to a multiple of the shard axis so the
    slot axis divides evenly across devices (empty tail slots are zero —
    the analog of unused placement slots, not of real shards)."""
    s = mesh.devices.shape[mesh.axis_names.index("shard")]
    return -(-n_chunks // s) * s


def make_sharded_encoder(matrix: np.ndarray, mesh: Mesh):
    """Jitted step: (B, k, L) data -> (B, padded_slots(k+m), L) chunks,
    output scattered over the shard axis (the TPU analog of
    MOSDECSubOpWrite fan-out). Slots >= k+m are zero padding."""
    matrix = np.asarray(matrix, dtype=np.uint8)
    n = matrix.shape[0] + matrix.shape[1]
    pad = padded_slots(n, mesh) - n

    def step(data):
        parity = apply_matrix(matrix, data)
        chunks = jnp.concatenate([data, parity], axis=1)
        if pad:
            chunks = jnp.pad(chunks, ((0, 0), (0, pad), (0, 0)))
        return chunks

    return jax.jit(step, in_shardings=data_sharding(mesh),
                   out_shardings=chunk_sharding(mesh))


def make_sharded_gather_apply(D: np.ndarray, slots: tuple[int, ...],
                              mesh: Mesh):
    """Jitted step: sharded (B, n_slots, L) chunks -> (B, rows(D), L).

    Indexing the given shard slots forces an ICI all-gather of exactly
    those chunks (the TPU analog of MOSDECSubOpRead gather), then the
    static GF matrix runs batched on every dp slice. The building block
    for degraded decode, LRC local repair, and any derived linear
    repair (ec.linearize)."""
    D = np.asarray(D, dtype=np.uint8)
    idx = np.asarray(slots, dtype=np.int32)

    def step(chunks):
        return apply_matrix(D, chunks[:, idx, :])

    return jax.jit(step, in_shardings=chunk_sharding(mesh),
                   out_shardings=data_sharding(mesh))


def make_sharded_decoder(matrix: np.ndarray, erasures: tuple[int, ...],
                         survivors: tuple[int, ...], mesh: Mesh):
    """Jitted step: sharded (B, n, L) chunks -> (B, E, L) reconstructed
    (degraded read across the mesh; see make_sharded_gather_apply)."""
    matrix = np.asarray(matrix, dtype=np.uint8)
    k = matrix.shape[1]
    D = decode_matrix(matrix, list(erasures), k, list(survivors))
    return make_sharded_gather_apply(D, tuple(survivors), mesh)


def make_sharded_clay_repair(coder, failed_chunk: int,
                             helper_chunks: tuple[int, ...], mesh: Mesh):
    """Jitted step: sharded (B, n_slots, L) chunks -> (B, L) rebuilt
    Clay chunk, reading ONLY the helpers' repair-plane sub-chunks (the
    MSR bandwidth win, beta = q^(t-1) of q^t sub-chunks per helper)
    before one static matrix-apply on every dp slice."""
    D, rplanes = coder.repair_plan_matrix(failed_chunk, helper_chunks)
    D = np.asarray(D, dtype=np.uint8)
    nsub = coder.get_sub_chunk_count()
    idx = np.asarray(helper_chunks, dtype=np.int32)
    planes = np.asarray(rplanes, dtype=np.int32)
    d, nrp = len(helper_chunks), len(rplanes)

    def step(chunks):
        B, _, L = chunks.shape
        helpers = chunks[:, idx, :]                    # ICI gather of d
        sub = helpers.reshape(B, d, nsub, L // nsub)
        rp = sub[:, :, planes, :]                      # beta sub-chunks
        stacked = rp.reshape(B, d * nrp, L // nsub)
        out = apply_matrix(D, stacked)                 # (B, nsub, L//nsub)
        return out.reshape(B, L)

    return jax.jit(step, in_shardings=chunk_sharding(mesh),
                   out_shardings=NamedSharding(mesh, P("dp", None)))


@functools.lru_cache(maxsize=8)
def _cpu_mesh_devices(n: int):
    devs = jax.devices()
    if len(devs) < n:
        raise RuntimeError(f"need {n} devices, have {len(devs)}")
    return tuple(devs[:n])


def virtual_mesh(n_devices: int, shard: int = 2) -> Mesh:
    """Mesh over the first n devices (virtual CPU devices in tests)."""
    return default_mesh(np.asarray(_cpu_mesh_devices(n_devices)), shard)
