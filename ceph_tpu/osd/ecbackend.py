"""ECBackend — the erasure-coded PG data path, batched for TPU.

Rebuild of the reference's EC read/write/recovery dataflow (ref:
src/osd/ECBackend.{h,cc} + ECCommon.{h,cc} — submit_transaction write
fan-out, RMWPipeline::start_rmw read-modify-write of partial stripes,
objects_read_and_reconstruct degraded read, RecoveryOp/
continue_recovery_op streaming recovery; ECTransaction::
generate_transactions for the per-shard store writes; per-shard HashInfo
bookkeeping ref: src/osd/ECUtil.{h,cc}).

TPU-first reshaping (SURVEY.md §2.7 P1-P4): where the reference fans
one object's sub-ops out over the network and recovers objects under a
semaphore one RecoveryOp at a time, here the unit of work is a BATCH of
objects — writes encode (B, k, chunk) in one device launch, recovery
gathers surviving shards for B objects into (B, k, chunk) device
arrays, runs ONE batched decode, and scatters the rebuilt shards back.
The per-shard stores are MemStore instances standing in for OSDs, so
the whole pipeline runs hermetically (the reference's
many-daemons-one-box trick, in-process).

Stripe geometry is POOL-WIDE and fixed (ref: pool stripe_unit →
ECUtil::stripe_info_t): every object is laid out round-robin in stripes
of k * chunk_size logical bytes, so objects span multiple stripes and a
partial overwrite touches only the stripes covering its byte range.
That makes the reference's read-modify-write pipeline meaningful here:
`write_ranges` reads the pre-image of just the touched stripe window
from the data shards (reconstructing the window from survivors when
shards are down), overlays the new bytes, re-encodes the window in one
batched launch, and emits per-shard sub-range writes.

Object placement: shard slot s of an object lands on the OSD in slot s
of the PG's acting set and carries the coder's chunk id s; the coder's
get_chunk_mapping() names which slots carry data vs parity (identity
for RS, interleaved for LRC). A lost OSD means one lost shard per
object, which is exactly the recovery workload metric #2 in BASELINE.md
measures (objects/s).
"""

from __future__ import annotations

import functools as _functools
import struct as _struct
import threading as _threading
from dataclasses import dataclass, field

import numpy as np

from ..ec.interface import ErasureCode
from ..ec.registry import factory
from ..utils.perf_counters import PerfCountersBuilder
from ..utils.tracing import span
from .memstore import MemStore, Transaction
from .pgbackend import HINFO_KEY, PGBackend, shard_cid  # noqa: F401
from .repairplan import plan_read, plan_repair
from .stripe import HashInfo, StripeInfo, as_flat_u8


def ec_perf_counters():
    """The EC data-path counter schema (logger "ec"). A daemon builds
    ONE instance and shares it across every PG backend it primaries
    (per-PG loggers would explode the metric space); standalone
    harnesses (recovery_bench) read the backend's own default."""
    return (PerfCountersBuilder("ec")
            .add_u64_counter("encode_launches",
                             "generic encode device launches")
            .add_u64_counter("fused_write_launches",
                             "fused encode+crc single launches")
            .add_u64_counter("host_encode_launches",
                             "write-path encodes served by the native "
                             "SSE codec + hardware crc32c (CPU "
                             "backend only — bit-identical to the "
                             "fused device launch)")
            .add_u64_counter("decode_launches",
                             "read-path decode calls, a healthy "
                             "read's pass-through among them")
            .add_u64_counter("degraded_reads",
                             "objects read that rebuilt at least one "
                             "wanted row")
            .add_u64_counter("decode_rows_rebuilt",
                             "wanted rows rebuilt by read-path "
                             "decodes (rows x objects)")
            .add_u64_counter("decode_bytes_rebuilt",
                             "bytes of those rebuilt rows")
            .add_u64_counter("host_decode_launches",
                             "read-path rebuilds computed on the "
                             "host (a codec's impl=ref numpy oracle)")
            .add_u64_counter("recover_launches",
                             "recovery decode launches (fused device "
                             "program, or a codec's generic path)")
            .add_u64_counter("recover_host_launches",
                             "recovery decodes outside the fused "
                             "device program: a codec without a "
                             "static decode plan (`decode_chunks` a "
                             "batch), and the re-decode of objects "
                             "whose helper rows failed hinfo "
                             "(`_recover_fallback`): 0 where the "
                             "fused program rebuilt every object")
            .add_u64_counter("recover_programs_ready",
                             "planned PGs whose recover program was "
                             "built (compiled or loaded for the "
                             "grant's shape) before a reservation was "
                             "asked for")
            .add_u64("recover_programs_pending",
                     "planned PGs whose recover program is still "
                     "being built in the background")
            .add_u64("recover_grant_bytes_max",
                     "most helper bytes one recovery launch staged "
                     "(high-water mark; bounded by "
                     "osd_recovery_max_active x "
                     "osd_recovery_max_chunk on the wire tier)")
            .add_u64_counter("program_cache_hits",
                             "compiled-program cache hits")
            .add_u64_counter("program_cache_misses",
                             "compiled-program cache compiles")
            .add_u64_counter("encode_bytes", "logical bytes encoded")
            .add_u64_counter("decode_bytes", "logical bytes decoded")
            .add_u64_counter("recovered_objects",
                             "objects rebuilt by recovery")
            .add_u64_counter("recovered_bytes",
                             "shard bytes rebuilt by recovery")
            .add_u64_counter("hinfo_failures",
                             "helper chunks failing hinfo verify")
            .add_u64_counter("read_eio",
                             "read-path chunk crc mismatches")
            .add_u64_counter("planner_local_plans",
                             "repairs planned inside one LRC local "
                             "group (repair-locality planner)")
            .add_u64_counter("planner_subchunk_plans",
                             "repairs planned as Clay/MSR sub-chunk "
                             "range reads")
            .add_u64_counter("planner_cost_plans",
                             "cost-ranked helper selections (SHEC "
                             "windows / MDS cheapest-k)")
            .add_u64_counter("planner_full_plans",
                             "plans laddered to a full/multi-loss "
                             "decode (locality broken or multi-loss)")
            .add_u64_counter("recover_wire_bytes",
                             "helper bytes pulled for recovery (the "
                             "repair-bytes-on-wire numerator)")
            .add_u64_counter("recover_helper_reads",
                             "helper shard reads staged into recover "
                             "launches, one a helper an object (fused, "
                             "range and generic paths alike)")
            .add_u64_counter("recover_range_frames_served",
                             "sub-chunk pull frames this daemon served "
                             "as a source (`readv_ranges_host`)")
            .add_u64_counter("recover_range_bytes_served",
                             "range bytes those frames shipped")
            .add_u64_counter("recover_range_verify_bytes",
                             "full-row bytes checksummed at the source "
                             "against their hinfo for those frames")
            .add_time_avg("encode_time", "write-path encode wall time",
                          hist=True)
            .add_time_avg("decode_time", "read-path decode wall time",
                          hist=True)
            .add_u64_counter("gather_rounds",
                             "read-path gather rounds: one a planned "
                             "read, one more for each re-plan round a "
                             "slot that lacks the object")
            .add_u64_counter("gather_frames",
                             "readv frames those rounds sent to "
                             "remote stores (rows and hinfo attrs in "
                             "one answer)")
            .add_u64_counter("verify_launches",
                             "read-path crc verify device launches")
            .add_u64_counter("verify_bytes",
                             "shard bytes crc-verified on read")
            .add_time_avg("verify_time",
                          "read-path crc verify wall time (stage, "
                          "launch, blocking fetch)", hist=True)
            .add_time_avg("recover_stage_time",
                          "recovery's pull: the helper rows read into "
                          "the stage buffer (`recovery.pull`)")
            .add_time_avg("recover_launch_time",
                          "recovery launch enqueue + async D2H start",
                          hist=True)
            .add_time_avg("recover_fetch_time",
                          "blocking remainder of the D2H fetch "
                          "(overlap eats the rest)")
            .add_time_avg("recover_writeback_time",
                          "rebuilt-shard writeback fan-out")
            .add_u64_counter("rmw_ops",
                             "partial-stripe overwrites served by the "
                             "parity-delta fast path")
            .add_u64_counter("rmw_delta_launches",
                             "fused delta-encode launches (device or "
                             "native host)")
            .add_u64_counter("rmw_host_delta_launches",
                             "of those, the ones computed on the "
                             "host (native codec, or a codec's "
                             "generic parity_delta): 0 where the "
                             "fused device program served them all")
            .add_u64_counter("rmw_wire_bytes",
                             "journal + delta payload bytes shipped "
                             "to participating shards (the RMW "
                             "amplification numerator)")
            .add_u64_counter("rmw_preread_bytes",
                             "pre-image bytes read for delta "
                             "construction (zero on the append path)")
            .add_u64_counter("rmw_fetch_waves",
                             "combined RMW prepare-fetch waves (one "
                             "per delta group: hinfo attrs + pre-"
                             "image ranges gathered in a single "
                             "overlapped round trip)")
            .add_u64_counter("rmw_fetch_frames",
                             "prepare-fetch frames issued (one per "
                             "participant shard per wave — the 1+m "
                             "sequential getattrs + per-span reads "
                             "these replaced counted 1 frame each)")
            .add_u64_counter("rmw_shard_ios",
                             "participating shards per RMW op, summed "
                             "(the shard-IO amplification counter: "
                             "1 data + m parity on the fast path)")
            .add_u64_counter("rmw_full_fallbacks",
                             "RMW jobs laddered to the full-stripe "
                             "path (degraded/stale stripe, stripe-"
                             "spanning or overlapping writes)")
            .add_u64_counter("rmw_append_fast",
                             "delta jobs whose pre-image was pure "
                             "padding (appends: no read phase at all)")
            .add_u64_counter("journal_entries",
                             "stripe-journal intents logged")
            .add_u64_counter("meta_rides",
                             "PG metadata records (bounded delta or "
                             "full base) carried on a write's own "
                             "fan-out: a full write's transactions, "
                             "an RMW's apply round")
            .add_u64_counter("meta_persist_rounds",
                             "PG metadata persists sent as a fan-out "
                             "round of their own (the full base to "
                             "every live shard)")
            .add_u64_counter("journal_replay_forward",
                             "journaled RMWs rolled forward on replay")
            .add_u64_counter("journal_replay_rollback",
                             "journaled RMWs rolled back on replay")
            .add_u64_counter("write_wire_bytes",
                             "full-path shard write bytes shipped "
                             "(the full-stripe amplification "
                             "numerator the RMW ratio divides by)")
            .add_u64_counter("stream_launches",
                             "StreamingCodec tile launches")
            .add_u64_counter("stream_bytes",
                             "bytes streamed through tiled encode")
            .add_time_avg("stream_drain_time",
                          "StreamingCodec blocking drain remainder")
            .create_perf_counters())


@dataclass
class ShardSet:
    """The 'cluster': one ObjectStore per OSD id. `store_factory` picks
    the backend — MemStore (default) or a persistent TinStore keyed by
    osd id (the store_test.cc parameterization, applied to the whole
    cluster sim)."""
    stores: dict[int, MemStore] = field(default_factory=dict)
    store_factory: "callable | None" = None

    def osd(self, osd_id: int) -> MemStore:
        if osd_id not in self.stores:
            self.stores[osd_id] = (self.store_factory(osd_id)
                                   if self.store_factory else MemStore())
        return self.stores[osd_id]


class ECBackend(PGBackend):
    """One PG's EC backend over a set of per-OSD stores."""

    def __init__(self, profile: dict | str, pg: str, acting: list[int],
                 cluster: ShardSet | None = None,
                 chunk_size: int | None = None,
                 perf=None, ensure_collections: bool = True):
        # data-path counters: the owning daemon passes its shared "ec"
        # logger; a bare backend (benches, unit tests) gets its own
        self.perf = perf if perf is not None else ec_perf_counters()
        self.coder: ErasureCode = factory(profile)
        self.k = self.coder.get_data_chunk_count()
        self.m = self.coder.get_coding_chunk_count()
        self.min_live = self.k  # EC pool min_size gate
        if len(acting) != self.k + self.m:
            raise ValueError(
                f"acting set size {len(acting)} != k+m={self.k + self.m}")
        # chunk mapping (ref: ErasureCodeInterface::get_chunk_mapping):
        # shard slot s holds the coder's chunk id s, and mapping[j]
        # names the slot carrying DENSE row j (encode_chunks' k data
        # rows then m parity rows). Identity for RS; LRC interleaves
        # data and local/global parity positions.
        self.chunk_mapping = [int(p) for p in
                              self.coder.get_chunk_mapping()]
        if sorted(self.chunk_mapping) != list(range(self.k + self.m)):
            raise ValueError(
                f"chunk mapping {self.chunk_mapping} is not a "
                f"permutation of 0..{self.k + self.m - 1}")
        self.data_slots = self.chunk_mapping[:self.k]
        self._perm = np.asarray(self.chunk_mapping)
        self._row_of_slot = np.argsort(self._perm)
        self._identity_mapping = \
            self.chunk_mapping == list(range(self.k + self.m))
        # pool-wide stripe geometry; round the requested chunk size up
        # through the coder's own alignment rule (clay needs sub-chunk
        # multiples, everything needs CHUNK_ALIGNMENT)
        requested = chunk_size or self.coder.get_chunk_size(0) or 4096
        cs = self.coder.get_chunk_size(requested * self.k)
        self.sinfo = StripeInfo(self.k, cs)
        self._init_common(pg, acting, cluster or ShardSet(),
                          ensure_collections=ensure_collections)
        self._fused_cache: dict = {}
        self._read_plans: dict = {}      # survivors -> (rows, family)
        # partial-stripe RMW state: per-PG stripe-journal sequence
        # (replay re-anchors it past every seq seen on disk) and the
        # crash hook the phase-boundary tests drive (None in prod)
        self._rmw_seq = 0
        self._rmw_crash_hook = None
        # read-path EIO accounting (verify-on-read mismatches + the
        # in-place rewrites they triggered)
        self.eio_stats = {"read_eio": 0, "repaired": 0}

    # -- helpers ------------------------------------------------------------

    def _shard_len(self, object_size: int) -> int:
        return self.sinfo.object_size_to_shard_size(object_size)

    def _slots_from_dense(self, dense: np.ndarray) -> np.ndarray:
        """(B, n, L) dense rows (k data then m parity, encode order)
        -> per-slot rows: slot chunk_mapping[j] carries dense row j."""
        if self._identity_mapping:
            return dense
        out = np.empty_like(dense)
        out[:, self._perm] = dense
        return out

    _expected_shard_len = _shard_len  # shallow-scrub size rule

    # hinfo CRCs use the shared batched-launch helper
    _batched_hinfo_crcs = staticmethod(PGBackend._batched_crcs)

    @staticmethod
    @_functools.lru_cache(maxsize=256)
    def _fused_write_fn(matrix_bytes: bytes, m: int, k: int, sl: int,
                        bucket: int, planes: int = 1):
        """Process-wide cache (like rs_kernels._make_jitted): every
        PG backend with the same coder geometry shares ONE compiled
        program per (shard len, batch bucket) — a per-backend cache
        would recompile the identical HLO once per PG per daemon.
        `planes` > 1 is a vector code's encode (`vector_encode_matrix`):
        the (m*planes, k*planes) matrix over the data rows viewed as
        (k*planes, sl/planes) sub-chunks, the parity viewed back as m
        rows."""
        import jax
        import jax.numpy as jnp

        from ..csum.kernels import crc32c_blocks
        from ..ops.rs_kernels import make_encoder
        matrix = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(
            m * planes, k * planes)
        enc = make_encoder(matrix, bucket_batch=False)

        def fused(d):                # (bucket, k, sl) u8
            if planes == 1:
                parity = enc(d)      # (bucket, m, sl)
            else:
                parity = enc(d.reshape(bucket, k * planes, sl // planes)
                             ).reshape(bucket, m, sl)
            rows = jnp.concatenate([d, parity], axis=1)
            crcs = crc32c_blocks(rows, init=0xFFFFFFFF, xorout=0)
            return parity, crcs      # (bucket, n) u32
        return jax.jit(fused)

    @_functools.cached_property
    def _write_matrix(self) -> tuple[bytes, int] | None:
        """(matrix bytes, sub-chunks) of the one-launch write: a vector
        code's encode over its sub-chunks, or a matrix code's (m, k)
        matrix over whole rows (`encode_matrix`: RS's coding matrix,
        LRC's layers composed); None for a coder with neither."""
        got = self.coder.vector_encode_matrix()
        if got is not None:
            return (np.ascontiguousarray(got[0], np.uint8).tobytes(),
                    int(got[1]))
        mat = self.coder.encode_matrix()
        if mat is None:
            return None
        return np.ascontiguousarray(mat, np.uint8).tobytes(), 1

    def _fused_write_program(self, sl: int, bucket: int):
        """The one-launch write program for (shard len, bucket), or None
        where the coder has no static encode at this length. Counts the
        launch and the program cache's hit or miss."""
        if self._write_matrix is None or sl % self._write_matrix[1]:
            return None
        mat, planes = self._write_matrix
        # RS leaves `planes` out: its cache key is the one it always had
        args = (mat, self.m, self.k, sl, bucket) + (
            (planes,) if planes > 1 else ())
        cache = self._fused_write_fn
        ci0 = cache.cache_info()
        fn = cache(*args)
        ci1 = cache.cache_info()
        self.perf.inc_many(
            (("fused_write_launches", 1),
             ("program_cache_hits", ci1.hits - ci0.hits),
             ("program_cache_misses", ci1.misses - ci0.misses)))
        return fn

    def _encode_shards_with_crcs(self, data_shards: np.ndarray,
                                 sl: int) -> tuple[np.ndarray,
                                                   np.ndarray]:
        """(B, k, sl) data rows -> (slot-ordered (B, n, sl) shards,
        slot-ordered (B, n) hinfo CRCs). For static-matrix coders (RS,
        LRC's composed layers, and a vector code's sub-chunk encode:
        `_fused_write_program`) the encode AND both CRC sets run as ONE
        fused, B-bucketed device launch with a single host fetch — the
        write path's r01 shape dispatched encode + CRC as separate
        launches with host round-trips between (the wire tier pays that
        per client op). There a mapping that is not the identity hands
        back `_SlotRows`, views of the rows in slot order. Other coders
        take the generic two-launch path."""
        from ..ec.rs import ReedSolomon
        B = data_shards.shape[0]
        if isinstance(self.coder, ReedSolomon) \
                and _host_crc_available():
            # host-encode mode (the r10 host-integrity precedent, on
            # the WRITE path): on the CPU backend the native SSE RS
            # codec + hardware crc32c beat the XLA launch ~4x at wire
            # batch sizes, and the bytes are BIT-IDENTICAL (same
            # coding matrix, ec_create_with_matrix; parity pinned by
            # tests/test_sharded_osd.py). On a real accelerator the
            # device encode is nearly free and this path stays off.
            mat = np.ascontiguousarray(self.coder.matrix,
                                       dtype=np.uint8)
            handle = _host_encoder_handle(mat.tobytes(), self.k,
                                          self.m)
            if handle is not None:
                from .. import native as _native
                import ctypes as _ctypes
                self.perf.inc_many(
                    (("host_encode_launches", 1),
                     ("encode_bytes", int(data_shards.size))))
                with span("ecbackend.write.encode",
                          counters=self.perf, key="encode_time"):
                    data_c = np.ascontiguousarray(data_shards)
                    parity = np.zeros((B, self.m, sl), np.uint8)
                    rc = _native.lib().ec_encode(
                        handle,
                        data_c.ctypes.data_as(_ctypes.c_char_p),
                        parity.ctypes.data_as(_ctypes.c_char_p),
                        sl, B)
                    if rc == 0:
                        dense = np.concatenate([data_shards, parity],
                                               axis=1)
                        dense_crcs = _native.native_crc32c_rows(
                            0xFFFFFFFF,
                            np.ascontiguousarray(dense).reshape(
                                B * self.n, sl)).reshape(B, self.n)
                        shards = self._slots_from_dense(dense)
                        if self._identity_mapping:
                            return shards, dense_crcs
                        crcs = np.empty_like(dense_crcs)
                        crcs[:, self._perm] = dense_crcs
                        return shards, crcs
                # rc != 0: fall through to the fused device launch
        from ..ops.rs_kernels import pow2_bucket
        bucket = pow2_bucket(B)
        fn = self._fused_write_program(sl, bucket)
        if fn is not None:
            import jax
            self.perf.inc("encode_bytes", int(data_shards.size))
            # stage + launch is the host's own work; fetch is the
            # wait for the device (and for the ops queued before it)
            with span("ecbackend.write.encode", counters=self.perf,
                      key="encode_time"):
                with span("ecbackend.write.stage"):
                    padded = data_shards
                    if bucket != B:
                        padded = np.zeros(
                            (bucket,) + data_shards.shape[1:],
                            dtype=np.uint8)
                        padded[:B] = data_shards
                with span("ecbackend.write.launch"):
                    parity_d, crcs_d = fn(padded)
                with span("ecbackend.write.fetch"):
                    parity, dense_crcs = jax.device_get(
                        (parity_d, crcs_d))
            parity = np.asarray(parity)[:B]
            dense_crcs = np.asarray(dense_crcs)[:B]
            if not self._identity_mapping:
                # the fan-out takes a view of each slot's row: no copy
                # of the rows into dense, then into slot order
                with span("ecbackend.write.slots"):
                    crcs = np.empty_like(dense_crcs)
                    crcs[:, self._perm] = dense_crcs
                    return (_SlotRows(data_shards, parity,
                                      self._row_of_slot), crcs)
            return np.concatenate([data_shards, parity], axis=1), dense_crcs
        self.perf.inc_many((("encode_launches", 1),
                            ("encode_bytes", int(data_shards.size))))
        with span("ecbackend.write.encode", counters=self.perf,
                  key="encode_time"):
            parity = np.asarray(self.coder.encode_chunks(data_shards))
        shards = self._slots_from_dense(
            np.concatenate([data_shards, parity], axis=1))
        crcs = self._batched_hinfo_crcs(
            shards.reshape(-1, sl)).reshape(B, self.n)
        return shards, crcs

    def _write_empty(self, name: str, live: list[int] | None = None) -> None:
        hinfo = HashInfo(1, 0, [0xFFFFFFFF])
        self.object_sizes[name] = 0
        live = live if live is not None else list(range(self.n))
        for shard in live:
            t = (Transaction()
                 .write(shard_cid(self.pg, shard), name, 0, b"")
                 .truncate(shard_cid(self.pg, shard), name, 0)
                 .setattr(shard_cid(self.pg, shard), name,
                          HINFO_KEY, hinfo.to_bytes()))
            self._store(shard).queue_transaction(t)
        self._log_write(name, live)

    # -- write path (submit_transaction, full-object) ------------------------

    def write_objects(self, objects: dict[str, bytes | np.ndarray],
                      dead_osds: set[int] | None = None,
                      shard_txn_extra=None) -> None:
        """Full-object writes, batched: encode every equal-length group
        in one device launch, then scatter per-shard store transactions
        (the role of ECTransaction::generate_transactions). Shards on
        dead OSDs are skipped and fall behind in the PG log.

        shard_txn_extra: optional factory, called once per fan-out
        wave with the wave's object names, AFTER the PG log reflects
        the wave's writes; returns fn(shard, txn) that appends extra
        ops to each shard's transaction. The wire tier rides the PG metadata persist on it
        (the pg-log-entries-inside-the-transaction discipline, ref:
        ECTransaction carrying log entries to every shard) so a client
        write costs ONE fan-out instead of two. With the hook in use
        the log append happens before the fan-out; a failed wave then
        leaves log entries no shard applied, which the caller's
        degraded retry simply supersedes (cursors only advance on the
        entries the retry wave ships)."""
        live = self._live_slots(dead_osds)
        self._check_min_size(live)
        by_len: dict[int, list[tuple[str, np.ndarray]]] = {}
        for name, data in objects.items():
            arr = as_flat_u8(data)
            by_len.setdefault(len(arr), []).append((name, arr))
        for olen, group in by_len.items():
            if olen == 0:
                for name, _ in group:
                    self._write_empty(name, live)
                if shard_txn_extra is not None:
                    add = shard_txn_extra([n for n, _ in group])
                    txns = []
                    for shard in live:
                        t = Transaction()
                        add(shard, t)
                        txns.append((shard, t))
                    self._fanout_txns(txns)
                continue
            sl = self._shard_len(olen)
            with span("ecbackend.write.stripe"):
                batch = np.stack([a for _, a in group])
                data_shards = self.sinfo.object_to_shards(batch)  # (B, k, sl)
            shards, crcs = self._encode_shards_with_crcs(data_shards,
                                                         sl)
            for name, _ in group:
                self.object_sizes[name] = olen
            # ONE combined transaction per shard for the whole batch
            # (the sub-op fan-out unit; on the wire tier this is one
            # MStoreOp frame per shard instead of one per object —
            # the batched analog of MOSDECSubOpWrite carrying the
            # whole RMW plan), fanned out pipelined: all shards'
            # frames hit the wire before any ack is awaited
            txns = []
            with span("ecbackend.write.txns"):
                add = None
                if shard_txn_extra is not None:
                    # log FIRST so the extra ops (the metadata
                    # persist) see the post-write history; see the
                    # docstring for why a failed wave cannot wedge
                    # the cursors
                    for name, _ in group:
                        self._log_write(name, live)
                    add = shard_txn_extra([n for n, _ in group])
                for shard in live:
                    cid = shard_cid(self.pg, shard)
                    t = Transaction()
                    for bi, (name, arr) in enumerate(group):
                        hinfo = HashInfo(1, sl, [int(crcs[bi, shard])])
                        # truncate clears any stale tail from a
                        # previous, larger version of the object
                        t.write(cid, name, 0, shards[bi, shard, :]) \
                         .truncate(cid, name, sl) \
                         .setattr(cid, name, HINFO_KEY,
                                  hinfo.to_bytes())
                    if add is not None:
                        add(shard, t)
                    txns.append((shard, t))
            self.perf.inc("write_wire_bytes", len(group) * len(live) * sl)
            # submit to every shard, then the wait for the slowest ack
            with span("ecbackend.write.fanout"):
                self._fanout_txns(txns)
            if shard_txn_extra is None:
                for name, _ in group:
                    self._log_write(name, live)

    # -- write path (RMW partial-stripe) -------------------------------------

    # write_at (the single-range RMW entry; ref: ECCommon::RMWPipeline::
    # start_rmw) is inherited from PGBackend and lands in write_ranges

    def _read_data_window(self, names: list[str], c0: int, clen: int,
                          dead: set[int],
                          old_slens: list[int]) -> np.ndarray:
        """Pre-image data-shard window (B, k, clen) for the RMW read
        phase, reconstructing down data shards from survivors (the
        degraded-write case). Reads past a shard's end zero-fill, which
        matches the zero-padding layout rule.

        old_slens: each object's current shard length — vector codes
        (clay) must decode at the OLD length because their sub-chunk
        geometry depends on chunk length; zero-extended chunks would
        decode to garbage."""
        B = len(names)
        avail = self._fresh_for(
            names, [s for s in range(self.n) if self.acting[s] not in dead])
        lost_data = [s for s in self.data_slots if s not in avail]

        def read_window(s: int, nm: str, off: int, ln: int) -> np.ndarray:
            buf = np.zeros(ln, dtype=np.uint8)
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            if st.exists(cid, nm):
                got = st.read(cid, nm, off, ln)
                buf[:len(got)] = got
            return buf

        # window rows are DENSE data order (row j <-> slot
        # data_slots[j]) so shards_to_object can consume it directly
        dense_of = {s: j for j, s in enumerate(self.data_slots)}
        window = np.zeros((B, self.k, clen), dtype=np.uint8)
        for j, s in enumerate(self.data_slots):
            if s in lost_data:
                continue
            for bi, nm in enumerate(names):
                window[bi, j] = read_window(s, nm, c0, clen)
        if not lost_data:
            return window
        helpers = sorted(self.coder.minimum_to_decode(lost_data, avail))
        if getattr(self.coder, "positionwise", True):
            # surviving data helpers are already in `window`; only read
            # parity helpers from the stores
            stacks = {s: window[:, dense_of[s]] if s in dense_of else
                      np.stack([read_window(s, nm, c0, clen)
                                for nm in names])
                      for s in helpers}
            rec = self.coder.decode_chunks(lost_data, stacks)
            for s in lost_data:
                window[:, dense_of[s]] = np.asarray(rec[s])
        else:
            # decode whole chunks at each object's OLD shard length
            # (the non-positionwise path always uses c0 == 0 windows)
            by_old: dict[int, list[int]] = {}
            for bi, sl in enumerate(old_slens):
                if sl:
                    by_old.setdefault(sl, []).append(bi)
            for sl, idxs in by_old.items():
                stacks = {s: np.stack([read_window(s, names[bi], 0, sl)
                                       for bi in idxs])
                          for s in helpers}
                rec = self.coder.decode_chunks(lost_data, stacks)
                ln = min(sl, clen)
                for s in lost_data:
                    window[idxs, dense_of[s], :ln] = \
                        np.asarray(rec[s])[:, :ln]
        return window

    def write_ranges(self, ops: list[tuple[str, int, bytes | np.ndarray]],
                     dead_osds: set[int] | None = None,
                     shard_txn_extra=None) -> list[int]:
        """Batched RMW dispatcher: every (name, offset, bytes) op goes
        to the PARITY-DELTA fast path when the stripe is clean (all
        shards live + caught up, write within one stripe, touched data
        columns < k) — only the touched data shard(s) plus the m
        parity shards move on the wire, crash-consistent through the
        per-PG stripe journal — and ladders to the full-stripe RMW
        (`_write_ranges_full`, the pre-r16 path) otherwise: degraded
        or stale stripes, object creation, stripe-spanning or
        overlapping writes, vector-code geometry changes.

        shard_txn_extra: write_objects' factory. The delta path calls
        it once a wave and puts its ops on every shard's transaction
        of the APPLY round (see _delta_commit); the full path does not
        use it, so a caller that rides its metadata on the hook still
        has to persist what a full-path op logged. Returns the slots
        that hold no byte of the wave and did not acknowledge their
        extra-ops-only transaction (the caller's to suspect; a
        participant's failure raises)."""
        dead = dead_osds or set()
        delta_jobs, full_ops = self._partition_rmw(ops, dead)
        unacked: list[int] = []
        if delta_jobs:
            unacked = self._write_ranges_delta(delta_jobs,
                                               shard_txn_extra)
        if full_ops:
            self.perf.inc("rmw_full_fallbacks",
                          len({n for n, _o, _d in full_ops}))
            with span("ecbackend.rmw.full"):
                self._write_ranges_full(full_ops, dead_osds)
        return unacked

    def _write_ranges_full(self,
                           ops: list[tuple[str, int, bytes | np.ndarray]],
                           dead_osds: set[int] | None = None) -> None:
        """Full-stripe RMW: read the touched stripe window, overlay,
        re-encode, and emit per-shard sub-range writes + hinfo
        updates. Encode launches are batched across objects whose
        windows have equal chunk length. Handles every case the delta
        path refuses (degraded pre-image reconstruction included)."""
        dead = dead_osds or set()
        k, si = self.k, self.sinfo
        live = [s for s in range(self.n) if self.acting[s] not in dead]
        self._check_min_size(live)

        # merge ops per object into one covering window
        per_obj: dict[str, list[tuple[int, np.ndarray]]] = {}
        for name, offset, data in ops:
            if offset < 0:
                raise ValueError(f"negative offset {offset}")
            per_obj.setdefault(name, []).append(
                (int(offset), as_flat_u8(data)))

        jobs = []  # (name, writes, old_slen, new_size, s0, clen)
        for name, writes in per_obj.items():
            old_size = self.object_sizes.get(name, 0)
            writes = [(off, a) for off, a in writes if len(a)]
            if not writes:
                # zero-length writes don't extend; just ensure existence
                if name not in self.object_sizes:
                    self._write_empty(name, live)
                continue
            hi = max(off + len(a) for off, a in writes)
            new_size = max(old_size, hi)
            lo = min(off for off, a in writes)
            if not getattr(self.coder, "positionwise", True):
                # vector codes (clay) couple bytes across the whole
                # chunk: windows are not independently encodable, so
                # fall back to a whole-object RMW
                lo, hi = 0, new_size
            s0, slen = si.offset_len_to_stripe_bounds(lo, hi - lo)
            jobs.append((name, writes, self._shard_len(old_size),
                         new_size, s0, slen // k))

        by_clen: dict[int, list[tuple]] = {}
        for job in jobs:
            by_clen.setdefault(job[-1], []).append(job)

        for clen, group in by_clen.items():
            names = [j[0] for j in group]
            old_slens = [j[2] for j in group]
            c0s = {j[4] // k for j in group}
            if len(c0s) == 1:
                window = self._read_data_window(names, c0s.pop(), clen,
                                                dead, old_slens)
            else:
                # mixed chunk offsets in one length group: read per job
                window = np.stack([
                    self._read_data_window([j[0]], j[4] // k, clen, dead,
                                           [j[2]])[0]
                    for j in group])
            # overlay new bytes in logical space
            logical = si.shards_to_object(window)  # (B, slen)
            for bi, (name, writes, _, _, s0, _) in enumerate(group):
                for off, arr in writes:
                    logical[bi, off - s0:off - s0 + len(arr)] = arr
            dshards = si.object_to_shards(logical)       # (B, k, clen)
            parity = np.asarray(self.coder.encode_chunks(dshards))
            shards = self._slots_from_dense(
                np.concatenate([dshards, parity], axis=1))  # (B, n, clen)

            # apply sub-range writes + recompute full-shard hinfo on the
            # LIVE shards only (down shards are rebuilt by recovery;
            # touching their stores would resurrect destroyed OSD ids).
            # Cumulative-CRC hinfo is append-only in the reference; an
            # overwrite invalidates it, so the RMW path recomputes the
            # full-shard CRC — batched per equal shard length.
            new_full: dict[int, list[np.ndarray]] = {}  # nsl -> full bytes
            slots: dict[int, list[tuple[int, int]]] = {}  # nsl -> (bi, s)
            for bi, (name, writes, _, new_size, s0, _) in enumerate(group):
                nsl = self._shard_len(new_size)
                c0 = s0 // k
                for s in live:
                    st = self._store(s)
                    cid = shard_cid(self.pg, s)
                    old = st.read(cid, name) if st.exists(cid, name) \
                        else np.zeros(0, dtype=np.uint8)
                    full = np.zeros(nsl, dtype=np.uint8)
                    full[:min(len(old), nsl)] = old[:nsl]
                    full[c0:c0 + clen] = shards[bi, s]
                    new_full.setdefault(nsl, []).append(full)
                    slots.setdefault(nsl, []).append((bi, s))
            crc_of: dict[tuple[int, int], int] = {}
            for nsl, fulls in new_full.items():
                crcs = self._batched_hinfo_crcs(np.stack(fulls))
                for (bi, s), c in zip(slots[nsl], crcs):
                    crc_of[(bi, s)] = int(c)
            # one combined txn per live shard for the whole group,
            # fanned out pipelined (matches the full-write path)
            shard_txns = {s: Transaction() for s in live}
            for bi, (name, writes, _, new_size, s0, _) in enumerate(group):
                nsl = self._shard_len(new_size)
                c0 = s0 // k
                for s in live:
                    hinfo = HashInfo(1, nsl, [crc_of[(bi, s)]])
                    shard_txns[s].write(shard_cid(self.pg, s), name, c0,
                                        shards[bi, s]) \
                        .setattr(shard_cid(self.pg, s), name,
                                 HINFO_KEY, hinfo.to_bytes())
            self.perf.inc("write_wire_bytes",
                          len(group) * len(live) * clen)
            self._fanout_txns(list(shard_txns.items()))
            for bi, (name, writes, _, new_size, s0, _) in enumerate(group):
                self.object_sizes[name] = new_size
                self._log_write(name, live)

    # -- write path (parity-delta fast path + stripe journal) ----------------
    #
    # The small-overwrite/append data path (ROADMAP item 3; the
    # online-EC measurement arxiv 1709.05365 shows write amplification
    # dominating this workload): delta_j = G[j,i] (x) (new_i ^ old_i)
    # folded into each parity shard, so only the touched data shard(s)
    # plus m parity shards move — not k+m. Crash consistency comes
    # from a per-PG stripe journal (intent logged durably on every
    # participating shard BEFORE any in-place XOR; an applied shard
    # atomically bumps its watermark and drops the entry), replayed by
    # stripe_journal_replay: SIGKILL anywhere leaves the stripe
    # bit-exact with either the old or the new bytes, never torn.

    JOURNAL_OBJ = "__stripe_journal__"
    _J_APPLIED = b"applied"

    @staticmethod
    def _jkey(seq: int) -> bytes:
        return b"e%016x" % seq

    @staticmethod
    def _encode_jentry(seq: int, name: str, slot: int,
                       participants, new_size: int, osl: int, nsl: int,
                       a: int, delta: bytes, new_crc: int,
                       version: int) -> bytes:
        from ..utils.encoding import Encoder
        e = Encoder()
        e.u32(1)                        # entry codec version
        e.u64(seq).string(name).u32(slot)
        e.list([int(p) for p in participants], Encoder.u32)
        e.u64(new_size).u64(osl).u64(nsl)
        e.u64(a).blob(delta)
        e.u32(new_crc)
        e.u64(version)                  # the lowest version of `name`
        #                                 that supersedes this intent
        #                                 (_delta_commit): replay drops
        #                                 the entry once the name has
        #                                 reached it
        return e.bytes()

    @staticmethod
    def _decode_jentry(raw: bytes) -> dict:
        from ..utils.encoding import Decoder
        d = Decoder(raw)
        v = d.u32()
        if v != 1:
            raise ValueError(f"stripe-journal entry version {v}")
        return {"seq": d.u64(), "name": d.string(), "slot": d.u32(),
                "participants": d.list(Decoder.u32),
                "new_size": d.u64(), "osl": d.u64(), "nsl": d.u64(),
                "a": d.u64(), "delta": d.blob(), "new_crc": d.u32(),
                "version": d.u64()}

    def _partition_rmw(self, ops, dead: set[int]):
        """Split a write_ranges op list into delta-eligible jobs and
        the ops the full path must carry. One job per object (ops
        merged); a job is delta-eligible when the stripe is CLEAN
        (every slot live and caught up — a delta against a stale or
        reconstructed pre-image would fold garbage into parity, so
        degraded stripes refuse and ladder down), the object exists,
        the merged writes don't overlap or span a full stripe, fewer
        than k data columns are touched, and (vector codes) the shard
        length doesn't change under the sub-chunk geometry."""
        k, si = self.k, self.sinfo
        per_obj: dict[str, list[tuple[int, np.ndarray]]] = {}
        order: list[str] = []
        raw: dict[str, list[tuple]] = {}
        for name, offset, data in ops:
            if offset < 0:
                raise ValueError(f"negative offset {offset}")
            if name not in per_obj:
                order.append(name)
            per_obj.setdefault(name, []).append(
                (int(offset), as_flat_u8(data)))
            raw.setdefault(name, []).append((name, offset, data))
        all_live = len(self._live_slots(dead)) == self.n
        jobs, full_ops = [], []
        for name in order:
            writes = [(o, a) for o, a in per_obj[name] if len(a)]
            old_size = self.object_sizes.get(name, 0)
            job = None
            if writes and all_live and old_size > 0:
                job = self._delta_job(name, writes, old_size)
            if job is not None \
                    and len(self._fresh_for([name],
                                            list(range(self.n)))) \
                    == self.n:
                jobs.append(job)
            else:
                full_ops.extend(raw[name])
        return jobs, full_ops

    def _delta_job(self, name: str, writes, old_size: int):
        """Geometry of one delta-eligible overwrite, or None. A job is
        (name, writes, old_size, new_size, osl, nsl, touched, spans,
        a, b): `spans` are per-write (col, chunk_off, len, log_off)
        chunk sub-ranges, (a, b) the common shard-offset window the
        delta rows are positioned in."""
        si, k = self.sinfo, self.k
        sw = si.stripe_width
        lo = min(o for o, _a in writes)
        hi = max(o + len(a) for o, a in writes)
        if hi - lo >= sw or lo >= old_size + sw:
            return None     # stripe-spanning, or a hole of untouched
        #                     stripes past the tail: full path
        # overlap check: delta composition is XOR — overlapping writes
        # in one wave would double-fold
        ivs = sorted((o, o + len(a)) for o, a in writes)
        for (s1, e1), (s2, _e2) in zip(ivs, ivs[1:]):
            if s2 < e1:
                return None
        new_size = max(old_size, hi)
        osl = self._shard_len(old_size)
        nsl = self._shard_len(new_size)
        spans = []
        touched: set[int] = set()
        for off, arr in writes:
            at = off
            end = off + len(arr)
            while at < end:
                stripe, rem = divmod(at, sw)
                col = rem // si.chunk_size
                in_chunk = rem % si.chunk_size
                ln = min(end - at, si.chunk_size - in_chunk)
                spans.append((col, stripe * si.chunk_size + in_chunk,
                              ln, at))
                touched.add(col)
                at += ln
        if len(touched) >= k:
            return None     # every data shard moves anyway
        if not getattr(self.coder, "positionwise", True):
            if nsl != osl:
                return None     # sub-chunk geometry changes with
            #                     length: ladder to full re-encode
            a, b = 0, osl       # byte positions couple: the delta
            #                     window is the whole chunk
        else:
            a = min(c0 for _col, c0, _ln, _lo in spans)
            b = max(c0 + ln for _col, c0, ln, _lo in spans)
        return (name, writes, old_size, new_size, osl, nsl,
                tuple(sorted(touched)), spans, a, b)

    @staticmethod
    @_functools.lru_cache(maxsize=256)
    def _fused_delta_fn(matrix_bytes: bytes, m: int, t: int, wl: int,
                        bucket: int):
        """Process-wide fused delta-encode program (the r10 recovery-
        program sharing rule): every PG backend whose coder exposes
        the same delta_program_key shares ONE compiled program per
        (window len, batch bucket). delta rows (bucket, t, wl) ->
        (parity deltas (bucket, m, wl), zero-seed CRCs of all t+m
        rows) in a single launch — the CRCs feed the incremental
        hinfo update."""
        import jax
        import jax.numpy as jnp

        from ..csum.kernels import crc32c_blocks
        from ..ops.rs_kernels import make_encoder
        D = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(m, t)
        enc = make_encoder(D, bucket_batch=False)

        def fused(d):                   # (bucket, t, wl) u8
            parity = enc(d)             # (bucket, m, wl)
            rows = jnp.concatenate([d, parity], axis=1)
            crcs = crc32c_blocks(rows, init=0, xorout=0)
            return parity, crcs         # (bucket, t + m) u32
        return jax.jit(fused)

    def _delta_parity_crcs(self, touched: tuple, deltas: np.ndarray
                           ) -> tuple[np.ndarray, np.ndarray]:
        """(B, t, wl) data deltas -> ((B, m, wl) parity deltas,
        (B, t+m) zero-seed CRCs of data+parity delta rows). Static-
        matrix coders take the native host codec (CPU backend, the
        r13 host-encode mode) or the fused device program; the rest
        (bitmatrix, clay) go through parity_delta's generic
        XOR-linear encode."""
        B, t, wl = deltas.shape
        D = self.coder.delta_matrix(touched)
        if D is not None and _host_crc_available():
            handle = _host_encoder_handle(
                np.ascontiguousarray(D, np.uint8).tobytes(), t, self.m)
            if handle is not None:
                from .. import native as _native
                import ctypes as _ctypes
                data_c = np.ascontiguousarray(deltas)
                parity = np.zeros((B, self.m, wl), np.uint8)
                rc = _native.lib().ec_encode(
                    handle,
                    data_c.ctypes.data_as(_ctypes.c_char_p),
                    parity.ctypes.data_as(_ctypes.c_char_p), wl, B)
                if rc == 0:
                    self.perf.inc_many((("rmw_delta_launches", 1),
                                        ("rmw_host_delta_launches", 1)))
                    rows = np.concatenate([deltas, parity], axis=1)
                    crcs = _native.native_crc32c_rows(
                        0, np.ascontiguousarray(rows).reshape(
                            B * (t + self.m), wl)).reshape(
                                B, t + self.m)
                    return parity, crcs
        if D is not None:
            import jax

            from ..ops.rs_kernels import pow2_bucket
            bucket = pow2_bucket(B)
            ci0 = self._fused_delta_fn.cache_info()
            fn = self._fused_delta_fn(
                np.ascontiguousarray(D, np.uint8).tobytes(), self.m,
                t, wl, bucket)
            ci1 = self._fused_delta_fn.cache_info()
            self.perf.inc_many(
                (("rmw_delta_launches", 1),
                 ("program_cache_hits", ci1.hits - ci0.hits),
                 ("program_cache_misses", ci1.misses - ci0.misses)))
            # launch is the host's own work (the dispatch with its
            # H2D); fetch is the wait for the device
            with span("ecbackend.rmw.delta.stage"):
                padded = deltas
                if bucket != B:
                    padded = np.zeros((bucket, t, wl), np.uint8)
                    padded[:B] = deltas
            with span("ecbackend.rmw.delta.launch"):
                parity_d, crcs_d = fn(padded)
            with span("ecbackend.rmw.delta.fetch"):
                parity, crcs = jax.device_get((parity_d, crcs_d))
            return (np.asarray(parity)[:B], np.asarray(crcs)[:B])
        self.perf.inc_many((("rmw_delta_launches", 1),
                            ("rmw_host_delta_launches", 1)))
        parity = self.coder.parity_delta(touched, deltas)
        rows = np.concatenate([deltas, parity], axis=1)
        crcs = _rows_crc0(rows.reshape(B * (t + self.m), wl)).reshape(
            B, t + self.m)
        return parity, crcs

    def _shard_old_crcs(self, name: str, slots) -> dict[int, int] | None:
        """Current hinfo CRC per slot, or None when any slot's stored
        hinfo is absent/odd (the delta path then refuses the job —
        an incremental update against a wrong base would stamp a
        corrupt CRC that verifies forever)."""
        osl = self._shard_len(self.object_sizes[name])
        out: dict[int, int] = {}
        for s in slots:
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            try:
                hinfo = HashInfo.from_bytes(st.getattr(cid, name,
                                                       HINFO_KEY))
            except KeyError:
                return None
            if hinfo.total_chunk_size != osl:
                return None
            out[s] = hinfo.get_chunk_hash(0)
        return out

    def _rmw_participants(self, touched: tuple, osl: int,
                          nsl: int) -> list[int]:
        """Participant shard slots of one delta job: the touched data
        columns + every parity slot; growth (nsl != osl) adds the
        rest with payload-free entries (zero-extension + hinfo
        shift). ONE derivation shared by the prepare fetch and the
        commit fan-out so the two cannot drift."""
        parts = ([self.data_slots[c] for c in touched]
                 + [self.chunk_mapping[self.k + j]
                    for j in range(self.m)])
        if nsl != osl:
            parts = parts + [s for s in range(self.n)
                             if s not in set(parts)]
        return parts

    def _rmw_prefetch(self, touched: tuple, group):
        """One pipelined wave of combined (hinfo attr + pre-image
        sub-range) fetches for a whole delta group — r16's prepare
        phase paid 1+m tiny sequential getattrs plus one read RTT per
        touched span per job; this pays ONE overlapped frame per
        participant shard for the group (RemoteStore.rmw_fetch_submit;
        in-process stores take the direct path, same accounting).

        Returns (old_crcs, prereads) aligned with `group`:
        old_crcs[i] = {slot: stored hinfo crc} or None when any
        participant's hinfo refuses the incremental base (the job
        then reroutes through the full path, exactly like
        _shard_old_crcs); prereads[i] = {(slot, off, len): bytes}
        for every touched sub-range below the old tail."""
        per_slot: dict[int, list[tuple[str, list]]] = {}
        parts_of: list[list[int]] = []
        ranges_of: list[dict[int, list]] = []
        for job in group:
            name, _w, old_size, _ns, osl, nsl, _t, spans, _a, _b = job
            parts = self._rmw_participants(touched, osl, nsl)
            parts_of.append(parts)
            need: dict[int, list] = {}
            for col, c0, ln, lo in spans:
                if lo < old_size:
                    need.setdefault(self.data_slots[col],
                                    []).append((c0, ln))
            ranges_of.append(need)
            for s in parts:
                per_slot.setdefault(s, []).append(
                    (name, need.get(s, [])))
        handles: list[tuple[int, object]] = []
        results: dict[int, list] = {}
        for s, items in sorted(per_slot.items()):
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            sub = getattr(st, "rmw_fetch_submit", None)
            if sub is not None:
                handles.append((s, sub(cid, HINFO_KEY, items)))
                continue
            out = []
            for name, ranges in items:
                try:
                    attr, ok = st.getattr(cid, name, HINFO_KEY), True
                except KeyError:
                    attr, ok = b"", False
                out.append((ok, attr,
                            [np.asarray(st.read(cid, name, off, ln),
                                        np.uint8).tobytes()
                             for off, ln in ranges]))
            results[s] = out
        for s, h in handles:
            results[s] = h.result()
        self.perf.inc_many((("rmw_fetch_waves", 1),
                            ("rmw_fetch_frames", len(per_slot))))
        cursor = {s: 0 for s in per_slot}
        old_crcs: list[dict | None] = []
        prereads: list[dict] = []
        for ji, job in enumerate(group):
            osl = job[4]
            crcs: dict[int, int] | None = {}
            pre: dict = {}
            for s in parts_of[ji]:
                ok, attr, rows = results[s][cursor[s]]
                cursor[s] += 1
                if crcs is not None:
                    hinfo = None
                    if ok:
                        try:
                            hinfo = HashInfo.from_bytes(attr)
                        except Exception:   # noqa: BLE001 — odd
                            hinfo = None    # stored attr: refuse
                    if hinfo is None or hinfo.total_chunk_size != osl:
                        crcs = None
                    else:
                        crcs[s] = hinfo.get_chunk_hash(0)
                for (off, ln), blob in zip(ranges_of[ji].get(s, []),
                                           rows):
                    pre[(s, off, ln)] = np.frombuffer(blob, np.uint8)
            old_crcs.append(crcs)
            prereads.append(pre)
        return old_crcs, prereads

    def _write_ranges_delta(self, jobs,
                            shard_txn_extra=None) -> list[int]:
        """Execute delta-eligible RMW jobs: build the delta rows
        (reading only the touched sub-ranges' pre-image — none at all
        for appends into padding), one fused delta-encode launch per
        (touched-columns, window) group, then the journaled two-phase
        shard update. Jobs whose stored hinfo refuses the incremental
        update reroute through the full path. Returns write_ranges'
        unacknowledged slots."""
        by_shape: dict[tuple, list] = {}
        for job in jobs:
            _n, _w, _os, _ns, _osl, _nsl, touched, _sp, a, b = job
            by_shape.setdefault((touched, b - a), []).append(job)
        unacked: list[int] = []
        for (touched, wl), group in by_shape.items():
            with span("ecbackend.rmw"):
                unacked += self._delta_group(touched, wl, group,
                                             shard_txn_extra)
        return unacked

    def _delta_group(self, touched: tuple, wl: int, group,
                     shard_txn_extra=None) -> list[int]:
        t = len(touched)
        col_of = {c: i for i, c in enumerate(touched)}
        parity_slots = [self.chunk_mapping[self.k + j]
                        for j in range(self.m)]
        B = len(group)
        deltas = np.zeros((B, t, wl), np.uint8)
        append_fast = 0
        preread = 0
        # r17: ONE overlapped prepare-fetch wave for the whole group —
        # hinfo attrs (the incremental-update base _delta_commit
        # verifies) and pre-image sub-ranges arrive together, one
        # frame per participant shard instead of 1+m sequential
        # getattrs + a read RTT per span per job
        with span("ecbackend.rmw.prefetch"):
            old_crcs, prereads = self._rmw_prefetch(touched, group)
        with span("ecbackend.rmw.delta.stage"):
            for bi, job in enumerate(group):
                name, writes, old_size, _ns, osl, _nsl, _t, spans, a, _b \
                    = job
                pure_append = all(lo >= old_size
                                  for _c, _c0, _ln, lo in spans)
                for col, c0, ln, lo in spans:
                    off, arr = next((o, w) for o, w in writes
                                    if o <= lo and lo + ln <= o + len(w))
                    newb = arr[lo - off:lo - off + ln]
                    row = deltas[bi, col_of[col]]
                    if lo >= old_size:
                        # append into padding: the pre-image is zeros
                        # by the layout rule — no read phase
                        row[c0 - a:c0 - a + ln] = newb
                        continue
                    got = prereads[bi][(self.data_slots[col], c0, ln)]
                    oldb = np.zeros(ln, np.uint8)
                    oldb[:len(got)] = got
                    preread += ln
                    row[c0 - a:c0 - a + ln] = np.asarray(newb) ^ oldb
                if pure_append:
                    append_fast += 1
        parity, crcs = self._delta_parity_crcs(touched, deltas)
        self.perf.inc_many((("rmw_preread_bytes", preread),
                            ("rmw_append_fast", append_fast)))
        return self._delta_commit(touched, wl, group, deltas, parity,
                                  crcs, parity_slots, old_crcs=old_crcs,
                                  shard_txn_extra=shard_txn_extra)

    def _delta_commit(self, touched: tuple, wl: int, group,
                      deltas, parity, crcs, parity_slots,
                      old_crcs: list | None = None,
                      shard_txn_extra=None) -> list[int]:
        """The journaled two-phase shard update of one delta batch:
        intent entries (delta payload + new hinfo) durably on every
        participating shard, then the atomic per-shard apply (XOR +
        hinfo + watermark bump + entry drop in ONE transaction).
        `old_crcs` carries the prefetched per-job hinfo bases from
        _rmw_prefetch (None entries reroute through the full path);
        absent, the per-job sync getattr loop serves (bare-backend
        callers).

        With `shard_txn_extra` (write_objects' factory) the wave is
        logged BEFORE the rounds, the factory is called once for the
        wave's names, and its ops go on every shard's transaction of
        the apply round: atomic with the XOR on a participant, alone
        on the other shards (the ref's MOSDECSubOpWrite carries the
        log entries to the shards that get no bytes too). The intents
        are durable on every participant before any shard holds a
        record that names the new version, so a crash in the apply
        round replays forward; a wave that fails leaves log entries no
        shard applied, and the caller's retry logs past them. Returns
        the non-participants whose transaction was not acknowledged
        (never a participant: that raises)."""
        t = len(touched)
        hook = self._rmw_crash_hook
        rides = shard_txn_extra is not None
        # per job: rows per slot, new crcs per slot, participants
        waves = []       # (job, seq, {slot: (row|None, new_crc)})
        wire = 0
        shard_prep: dict[int, Transaction] = {}
        shard_apply: dict[int, Transaction] = {}
        max_seq_of: dict[int, int] = {}
        keys_of: dict[int, list[bytes]] = {}
        for bi, job in enumerate(group):
            name, _w, _os, new_size, osl, nsl, _t, _sp, a, b = job
            # growth touches every shard (zero-extension + hinfo
            # shift) — the others ride payload-free entries
            parts = self._rmw_participants(touched, osl, nsl)
            old = old_crcs[bi] if old_crcs is not None \
                else self._shard_old_crcs(name, parts)
            if old is None:
                # stored hinfo refuses the incremental base: reroute
                # this job through the full path (rare — e.g. a
                # legacy object written before hinfo discipline)
                self.perf.inc("rmw_full_fallbacks")
                with span("ecbackend.rmw.full"):
                    self._write_ranges_full(
                        [(name, o, w) for o, w in job[1]], None)
                continue
            self._rmw_seq += 1
            seq = self._rmw_seq
            # the PG-log version this job will create (jobs log in
            # wave order: after the apply fan-out, or before the
            # rounds where the extra ops ride) and, from it, the
            # lowest version of the name that supersedes the intent.
            # Logged after the apply, a failed wave's version is the
            # retry's too, so the name AT that version supersedes;
            # logged first it is this wave's alone, a shard's
            # metadata may name it while its neighbour still holds
            # the intent, and only a later one supersedes.
            pred_version = self.pg_log.head + len(waves) + 1
            superseded_at = pred_version + (1 if rides else 0)
            plan: dict[int, tuple] = {}
            for ti, c in enumerate(touched):
                s = self.data_slots[c]
                crc0 = int(crcs[bi, ti])
                plan[s] = (deltas[bi, ti], crc0)
            for j, s in enumerate(parity_slots):
                plan[s] = (parity[bi, j], int(crcs[bi, t + j]))
            for s in parts:
                row, crc0 = plan.get(s, (None, None))
                if crc0 is None:
                    new_crc = _crc_shift(old[s], nsl - osl)
                else:
                    new_crc = (_crc_shift(old[s], nsl - osl)
                               ^ _crc_shift(crc0, nsl - b))
                delta_b = b"" if row is None else row.tobytes()
                entry = self._encode_jentry(
                    seq, name, s, parts, new_size, osl, nsl, a,
                    delta_b, new_crc, superseded_at)
                cid = shard_cid(self.pg, s)
                shard_prep.setdefault(s, Transaction()).omap_set(
                    cid, self.JOURNAL_OBJ,
                    {self._jkey(seq): entry})
                at = shard_apply.setdefault(s, Transaction())
                if row is not None:
                    at.xor(cid, name, a, row)
                if nsl != osl:
                    at.truncate(cid, name, nsl)
                at.setattr(cid, name, HINFO_KEY,
                           HashInfo(1, nsl, [new_crc]).to_bytes())
                max_seq_of[s] = max(max_seq_of.get(s, 0), seq)
                keys_of.setdefault(s, []).append(self._jkey(seq))
                wire += len(entry) + len(delta_b)
            waves.append((job, seq, plan, parts))
        if not waves:
            return []
        for s, at in shard_apply.items():
            cid = shard_cid(self.pg, s)
            at.omap_set(cid, self.JOURNAL_OBJ,
                        {self._J_APPLIED:
                         _struct.pack("<Q", max_seq_of[s])})
            at.omap_rmkeys(cid, self.JOURNAL_OBJ, keys_of[s])
        live = list(range(self.n))

        def log_wave():
            for job, _seq, _plan, _parts in waves:
                self.object_sizes[job[0]] = job[3]
                self._log_write(job[0], live)
        extra_only: list[int] = []
        if rides:
            log_wave()
            add = shard_txn_extra([job[0] for job, *_ in waves])
            extra_only = [s for s in live if s not in shard_apply]
            for s in live:
                add(s, shard_apply.setdefault(s, Transaction()))
        unacked: list[int] = []
        try:
            if hook is not None:
                hook("before_prepare")
                # sequential fan-outs under the hook so the crash
                # matrix can land BETWEEN shards (a pipelined wave
                # has no observable mid-point)
                for idx, (s, pt) in enumerate(
                        sorted(shard_prep.items())):
                    self._store(s).queue_transaction(pt)
                    if idx == 0:
                        hook("mid_prepare")
            else:
                with span("ecbackend.rmw.journal"):
                    self._fanout_txns(list(shard_prep.items()))
            self.perf.inc("journal_entries",
                          sum(len(v) for v in keys_of.values()))
            if hook is not None:
                hook("after_prepare")
                for idx, (s, at) in enumerate(
                        sorted(shard_apply.items())):
                    self._store(s).queue_transaction(at)
                    if idx == 0:
                        hook("mid_apply")
            else:
                with span("ecbackend.rmw.apply"):
                    unacked = self._fanout_txns(
                        list(shard_apply.items()), optional=extra_only)
            if hook is not None:
                hook("after_apply")
        except (ConnectionError, OSError):
            # a participant died mid-wave: best-effort drop of the
            # wave's intents on every reachable shard (an applied
            # shard holds none — rmkeys no-ops). The caller's
            # degraded retry then rewrites the window through the
            # full path, and the superseded-version guard makes any
            # entry this cleanup missed a replay no-op.
            for s, keys in keys_of.items():
                try:
                    self._store(s).queue_transaction(
                        Transaction().omap_rmkeys(
                            shard_cid(self.pg, s),
                            self.JOURNAL_OBJ, keys))
                except (ConnectionError, OSError, KeyError):
                    pass
            if rides:
                # logged first: the entries stay (the retry logs past
                # them), the sizes are the stores' again
                for job, _seq, _plan, _parts in waves:
                    self.object_sizes[job[0]] = job[2]
            raise
        if not rides:
            log_wave()
        # a shard io moves object bytes: the participants', not a
        # transaction that holds the extra ops alone
        ios = sum(len(parts) for _job, _seq, _plan, parts in waves)
        self.perf.inc_many((("rmw_ops", len(waves)),
                            ("rmw_shard_ios", ios),
                            ("rmw_wire_bytes", wire)))
        return unacked

    def stripe_journal_replay(self, dead_osds: set[int] | None = None
                              ) -> dict:
        """Replay the per-PG stripe journal after a crash/remount
        (ref: the PGLog-driven divergent-entry resolution, applied to
        RMW intents). Decision per pending seq: roll FORWARD when any
        live participant already applied it (its watermark proves the
        prepare phase completed everywhere) or when every live
        participant still holds the intent (prepare complete, crash
        before any apply — forward and backward are both consistent;
        forward matches the ack the client may have seen); roll BACK
        otherwise (prepare incomplete: applying would tear the
        stripe). Apply is idempotent — an applied shard holds no
        entry and is never re-XORed. Returns {forward, rolled_back,
        entries}."""
        dead = dead_osds or set()
        live = self._live_slots(dead)
        live_set = set(live)
        pending: dict[int, dict[int, dict]] = {}
        watermark: dict[int, int] = {}
        # the existence probe fans out PIPELINED (one overlapped round
        # trip, not n sequential ones — restores run this on every
        # reconcile and most PGs have no journal at all)
        probes: list[tuple[int, object]] = []
        sync_exists: dict[int, bool] = {}
        for s in list(live):
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            sub = getattr(st, "exists_submit", None)
            try:
                if sub is not None:
                    probes.append((s, sub(cid, self.JOURNAL_OBJ)))
                else:
                    sync_exists[s] = st.exists(cid, self.JOURNAL_OBJ)
            except (ConnectionError, OSError, KeyError):
                live_set.discard(s)
        for s, h in probes:
            try:
                sync_exists[s] = bool(h.result()[0])
            except (ConnectionError, OSError, KeyError):
                # an unreachable-but-not-yet-marked shard: scan
                # around it like a dead one (its intents settle on
                # the next restore's replay)
                live_set.discard(s)
        for s in list(live):
            if not sync_exists.get(s, False):
                continue
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            try:
                page = st.omap_iter(cid, self.JOURNAL_OBJ)
            except (ConnectionError, OSError, KeyError):
                live_set.discard(s)
                continue
            for key, val in page:
                if key == self._J_APPLIED:
                    watermark[s] = _struct.unpack("<Q", val)[0]
                elif key.startswith(b"e"):
                    ent = self._decode_jentry(val)
                    pending.setdefault(ent["seq"], {})[s] = ent
        forward = rolled_back = 0
        for seq in sorted(pending):
            holders = pending[seq]
            ent0 = next(iter(holders.values()))
            parts = [p for p in ent0["participants"] if p in live_set]
            applied_any = any(watermark.get(p, -1) >= seq
                              for p in parts)
            all_logged = all(p in holders for p in parts)
            name = ent0["name"]
            # superseded entries (a later write — e.g. the degraded
            # full-path retry of this very RMW — already bumped the
            # object's version) must never re-fold their delta; the
            # entry states the version that supersedes it, so metadata
            # that rode this RMW's own apply round and names its
            # version does not
            roll = (applied_any or all_logged) \
                and name in self.object_sizes \
                and ent0["version"] > self.object_versions.get(name, 0)
            for s, ent in holders.items():
                st = self._store(s)
                cid = shard_cid(self.pg, s)
                txn = Transaction()
                if roll:
                    if ent["delta"]:
                        txn.xor(cid, name, ent["a"], np.frombuffer(
                            ent["delta"], np.uint8))
                    if ent["nsl"] != ent["osl"]:
                        txn.truncate(cid, name, ent["nsl"])
                    txn.setattr(cid, name, HINFO_KEY, HashInfo(
                        1, ent["nsl"], [ent["new_crc"]]).to_bytes())
                    txn.omap_set(cid, self.JOURNAL_OBJ,
                                 {self._J_APPLIED:
                                  _struct.pack("<Q", max(
                                      watermark.get(s, 0), seq))})
                    watermark[s] = max(watermark.get(s, 0), seq)
                txn.omap_rmkeys(cid, self.JOURNAL_OBJ,
                                [self._jkey(seq)])
                st.queue_transaction(txn)
            if roll:
                forward += 1
                self.object_sizes[name] = max(
                    self.object_sizes.get(name, 0), ent0["new_size"])
            else:
                rolled_back += 1
        self._rmw_seq = max([self._rmw_seq] + list(pending)
                            + list(watermark.values()))
        self.perf.inc_many((("journal_replay_forward", forward),
                            ("journal_replay_rollback", rolled_back)))
        return {"forward": forward, "rolled_back": rolled_back,
                "entries": sum(len(h) for h in pending.values())}

    # -- read path -----------------------------------------------------------

    # read_object is inherited; read_objects is the batched
    # objects_read_and_reconstruct analog

    def read_objects(self, names: list[str],
                     dead_osds: set[int] | None = None,
                     verify: bool = True,
                     repair: bool = True,
                     helper_costs: dict[int, int] | None = None
                     ) -> dict[str, np.ndarray]:
        """Batched reads with BlueStore-style verify-on-read: every
        chunk consumed is CRC-checked against its stored hinfo in one
        batched launch (ref: BlueStore::_verify_csum on every read);
        a mismatch is the EIO path — the read transparently re-decodes
        from other shards AND repairs the rotten chunk in place (ref:
        the read-error recovery qa/standalone/erasure-code/
        test-erasure-eio.sh exercises). repair=False keeps the
        re-decode but skips the writeback — the read-only contract of
        a degraded-read view served by a non-primary.

        The rows of a plan are gathered in one overlapped round
        (`_gather`): one `readv` frame a remote slot, each answer
        carrying its rows' hinfo attrs, all on the wire before any is
        awaited; the slots the primary holds itself are read in place
        meanwhile. A slot whose store lacks an object (a re-pointed
        slot whose rebuild has not landed) is dropped and the read
        planned again without it.

        Degraded reads gather through the repair-locality planner
        (plan_read): an LRC single-shard loss pulls its local group
        instead of any-k, and `helper_costs` (slot -> cost) biases
        which survivors serve (the daemon's complaint/latency
        memory) at the first read a set of survivors serves: the
        rows then stay the same (`_read_plan`), so a degraded PG meets
        one decode program, not one for every order of the costs."""
        dead = dead_osds or set()
        alive = [s for s in range(self.n)
                 if self.acting[s] not in dead]
        want = list(self.data_slots)
        out: dict[str, np.ndarray] = {}
        # batched like recovery: stack equal-shard-length groups and
        # decode each group in ONE launch
        by_len: dict[int, list[str]] = {}
        for name in names:
            if self.object_sizes[name] == 0:
                out[name] = np.zeros(0, dtype=np.uint8)
                continue
            by_len.setdefault(self._shard_len(self.object_sizes[name]),
                              []).append(name)
        for sl, group in by_len.items():
            # a shard that missed any of this group's writes is stale
            # for it and must not serve (it replays on rejoin)
            avail = self._fresh_for(group, alive)
            while True:
                # the planner raises when the survivors can't cover
                # `want` — the caller's retry boundary
                need_set, family = self._read_plan(avail, helper_costs)
                if family != "direct":
                    self._count_plan(family)
                need = sorted(need_set)
                with span("ecbackend.read.gather"):
                    stacks, hinfos, missing = self._gather(
                        need, group, sl, verify)
                if not missing:
                    break
                # cursor says fresh but the store lacks the object: a
                # repointed slot whose rebuild has not landed this
                # object yet (recovery in flight) — plan around it
                # like a stale shard
                avail = [s for s in avail if s not in missing]
            bad: dict[str, set[int]] = {}
            if verify:
                # the read path's device launch: one crc program over
                # every row consumed
                with span("ecbackend.read.verify", counters=self.perf,
                          key="verify_time"):
                    rows = np.concatenate([stacks[s] for s in need])
                    crcs = self._batched_crcs(
                        rows, "ecbackend.read.verify").reshape(
                            len(need), len(group))
                self.perf.inc_many((("verify_launches", 1),
                                    ("verify_bytes", int(rows.size))))
                for si, s in enumerate(need):
                    for bi, nm in enumerate(group):
                        stored = HashInfo.from_bytes(hinfos[s][bi])
                        if int(crcs[si, bi]) != stored.get_chunk_hash(0):
                            bad.setdefault(nm, set()).add(s)
            clean_group = [n for n in group if n not in bad]
            if clean_group:
                idx = [group.index(n) for n in clean_group]
                sub = {s: stacks[s][idx] for s in need}
                self.perf.inc_many(
                    (("decode_launches", 1),
                     ("decode_bytes",
                      len(clean_group) * len(need) * sl)))
                with span("ecbackend.read.decode", counters=self.perf,
                          key="decode_time"):
                    rec = self._decode_rows(want, sub, sl)
                with span("ecbackend.read.unstripe"):
                    shards = np.stack([rec[s] for s in self.data_slots],
                                      axis=1)
                    objs = self.sinfo.shards_to_object(shards)
                for oi, name in enumerate(clean_group):
                    out[name] = objs[oi, :self.object_sizes[name]]
            for name, bad_set in bad.items():
                self.eio_stats["read_eio"] += len(bad_set)
                self.perf.inc("read_eio", len(bad_set))
                out[name] = self._read_eio(name, sl, avail, bad_set,
                                           repair=repair)
        return out

    def _read_plan(self, avail: list[int],
                   costs: dict[int, int] | None) -> tuple[set[int], str]:
        """`plan_read` for the data rows, decided once for each set of
        survivors. A decode program is compiled for each (lost rows,
        helper rows) pattern, and the costs are latencies that change
        from read to read: they choose among equal helpers when the
        plan is made, and a plan that is made stands until the
        survivors change."""
        key = tuple(avail)
        plan = self._read_plans.get(key)
        if plan is None:
            if len(self._read_plans) >= 64:      # survivors came and
                self._read_plans.clear()         # went: start again
            plan = self._read_plans[key] = plan_read(
                self.coder, self.data_slots, avail, costs=costs)
        return plan

    def _gather(self, need: list[int], group: list[str], sl: int,
                verify: bool) -> tuple[dict, dict, set[int]]:
        """One round of a read's gather: slot -> (len(group), sl) rows,
        slot -> the rows' hinfo attrs (with `verify`), and the slots
        whose store lacks one of the objects. Every remote slot's
        `readv` frames (rows and attrs in one answer; a group larger
        than RECOVERY_FETCH_BYTES split, so no frame grows with the
        group) are on the wire before any answer is awaited or the
        primary's own slots are read in place, so the round costs the
        slowest answer, not their sum. A failure other than a missing
        object surfaces from the first handle that meets it; no handle
        is left in flight."""
        stacks: dict[int, np.ndarray] = {}
        hinfos: dict[int, list[bytes]] = {}
        missing: set[int] = set()
        attr_key = HINFO_KEY if verify else None
        per = max(1, RECOVERY_FETCH_BYTES // sl)
        parts: dict[int, list[np.ndarray]] = {}
        handles: list[tuple] = []
        local: list[int] = []
        try:
            for s in need:
                submit = getattr(self._store(s), "readv_submit", None)
                if submit is None:
                    local.append(s)
                    continue
                cid = shard_cid(self.pg, s)
                for c0 in range(0, len(group), per):
                    names = group[c0:c0 + per]
                    handles.append((s, len(names),
                                    submit(cid, names, sl, attr_key)))
            self.perf.inc_many((("gather_rounds", 1),
                                ("gather_frames", len(handles))))
            for s in local:
                st, cid = self._store(s), shard_cid(self.pg, s)
                try:
                    read_batch = getattr(st, "read_batch", None)
                    stacks[s] = read_batch(cid, group, sl) \
                        if read_batch is not None else np.stack(
                            [st.read(cid, n) for n in group])
                    if verify:
                        hinfos[s] = [st.getattr(cid, n, HINFO_KEY)
                                     for n in group]
                except KeyError:
                    missing.add(s)
            for s, nb, handle in handles:
                try:
                    data, attrs = handle.result()
                except KeyError:
                    missing.add(s)
                    continue
                rows = np.frombuffer(data, np.uint8)
                if rows.size != nb * sl:
                    raise ValueError(
                        f"readv: got {rows.size} bytes, "
                        f"expected {nb * sl}")
                parts.setdefault(s, []).append(rows.reshape(nb, sl))
                if verify:
                    hinfos.setdefault(s, []).extend(attrs)
        except BaseException:
            # what this round still has on the wire is nobody's now
            for _s, _nb, handle in handles:
                handle.cancel()
            raise
        for s, got in parts.items():
            stacks[s] = got[0] if len(got) == 1 else np.concatenate(got)
        return stacks, hinfos, missing

    def _decode_rows(self, want: list[int], rows: dict[int, np.ndarray],
                     sl: int) -> dict[int, np.ndarray]:
        """The `want` rows of a (B, sl) stack a slot: those gathered
        pass through, the rest are rebuilt from the gathered rows, on
        the device where the codec has a program for the pattern
        (stage: the helper rows stacked; launch: the dispatch with its
        copy to the device; fetch: the wait for the rebuilt rows)."""
        from ..ec.rs import ReedSolomon
        lost = [s for s in want if s not in rows]
        if not lost:
            return rows
        # a static decode matrix takes the first k rows there are
        helpers = sorted(rows)[:self.k]
        n_obj = len(rows[helpers[0]])
        fn = self.coder.batch_decoder(lost, helpers) \
            if isinstance(self.coder, ReedSolomon) else None
        if fn is None:
            # the codec's own decode (LRC's layers, Clay's coupled
            # planes, SHEC's windows): device programs of its making,
            # but for impl=ref, the numpy oracle
            if self.coder.ref_oracle:
                self.perf.inc("host_decode_launches")
            rebuilt = self.coder.decode_chunks(lost, rows)
        else:
            with span("ecbackend.read.decode.stage"):
                stack = np.stack([rows[s] for s in helpers], axis=1)
            with span("ecbackend.read.decode.launch"):
                out_d = fn(stack)
            with span("ecbackend.read.decode.fetch"):
                out = np.asarray(out_d)
            rebuilt = {s: out[:, i] for i, s in enumerate(lost)}
        self.perf.inc_many(
            (("degraded_reads", n_obj),
             ("decode_rows_rebuilt", len(lost) * n_obj),
             ("decode_bytes_rebuilt", len(lost) * n_obj * sl)))
        return {**rows, **rebuilt}

    def _read_eio(self, name: str, sl: int, avail: list[int],
                  bad: set[int], repair: bool = True) -> np.ndarray:
        """One object's EIO path: decode around the rotten shards,
        return the bytes, and repair the rot in place.

        Substitute shards are CRC-VERIFIED before they feed the decode:
        an unverified substitute with its own rot would hand the client
        corrupt bytes and then durably launder them — the repair would
        rewrite the flagged shard from corrupt data under a freshly
        matching CRC that no future scrub could catch."""
        want = list(self.data_slots)
        bad = set(bad)
        while True:
            ok_shards = [s for s in avail if s not in bad]
            need = sorted(plan_read(self.coder, want, ok_shards)[0])
            stacks = {}
            newly_bad = False
            for s in need:
                st = self._store(s)
                cid = shard_cid(self.pg, s)
                try:
                    chunk = st.read(cid, name)
                    hinfo = HashInfo.from_bytes(st.getattr(cid, name,
                                                           HINFO_KEY))
                except KeyError:
                    # repointed slot mid-rebuild (no bytes/hinfo yet):
                    # plan around it, exactly like rot
                    bad.add(s)
                    newly_bad = True
                    break
                crc = int(self._batched_crcs(chunk[None, :])[0])
                if crc != hinfo.get_chunk_hash(0):
                    self.eio_stats["read_eio"] += 1
                    bad.add(s)
                    newly_bad = True
                    break
                stacks[s] = chunk[None, :]
            if newly_bad:
                continue  # re-plan without the newly found rot
            rec = self.coder.decode(want, stacks)
            shards = np.stack([rec[s] for s in self.data_slots], axis=1)
            obj = self.sinfo.shards_to_object(shards)[0]
            if repair:
                self._repair_shards(name, obj, sorted(bad), sl)
            return obj[:self.object_sizes[name]]

    def _repair_shards(self, name: str, logical: np.ndarray,
                       slots: list[int], sl: int) -> None:
        """Rewrite specific shards of one object from its logical bytes
        (the read-error / `ceph pg repair` writeback)."""
        dshards = self.sinfo.object_to_shards(logical[None, :])
        parity = np.asarray(self.coder.encode_chunks(dshards))
        full = self._slots_from_dense(
            np.concatenate([dshards, parity], axis=1))[0]  # (n, sl)
        crcs = self._batched_hinfo_crcs(full[slots])
        for ci, s in enumerate(slots):
            hinfo = HashInfo(1, sl, [int(crcs[ci])])
            t = (Transaction()
                 .write(shard_cid(self.pg, s), name, 0, full[s])
                 .truncate(shard_cid(self.pg, s), name, sl)
                 .setattr(shard_cid(self.pg, s), name,
                          HINFO_KEY, hinfo.to_bytes()))
            self._store(s).queue_transaction(t)
            self.eio_stats["repaired"] += 1

    def repair_pg(self, dead_osds: set[int] | None = None) -> dict:
        """`ceph pg repair` analog: deep-scrub, then rewrite every
        inconsistent shard from the surviving majority (ref:
        PrimaryLogPG repair path driven by the scrubber's
        authoritative-copy decision)."""
        dead = dead_osds or set()
        rep = self.deep_scrub(dead_osds=dead)
        alive = [s for s in range(self.n)
                 if self.acting[s] not in dead]
        alive_set = set(alive)
        by_name: dict[str, list[int]] = {}
        skipped = 0
        for name, slot in rep["inconsistent"]:
            # never write to a dead slot (repairing it would resurrect
            # a destroyed OSD's store; recovery rebuilds it instead),
            # and a deleted object's leftover is delete-replay's job
            if slot not in alive_set or name not in self.object_sizes:
                skipped += 1
                continue
            by_name.setdefault(name, []).append(slot)
        repaired = 0
        for name, slots in sorted(by_name.items()):
            sl = self._shard_len(self.object_sizes[name])
            obj = self._read_eio(name, sl,
                                 self._fresh_for([name], alive),
                                 set(slots))
            del obj  # _read_eio already repaired in place
            repaired += len(slots)
        return {"checked": rep["checked"], "repaired": repaired,
                "objects": len(by_name), "skipped": skipped,
                "strays_removed": self._remove_strays(dead)}

    # -- recovery (the objects/s metric) -------------------------------------

    def _count_plan(self, family: str) -> None:
        """Fold a planner decision into the declared counters."""
        key = {"lrc_local": "planner_local_plans",
               "clay_planes": "planner_subchunk_plans",
               "shec_cost": "planner_cost_plans",
               "mds": "planner_cost_plans"}.get(family,
                                                "planner_full_plans")
        self.perf.inc(key)

    def plan_recovery(self, lost_shards: list[int],
                      replacement_osds: dict[int, int] | None = None,
                      verify_hinfo: bool = True,
                      names: list[str] | None = None,
                      helper_exclude: set[int] | None = None,
                      helper_costs: dict[int, int] | None = None
                      ) -> "_RecoveryPlan":
        """Open one PG's recovery intent: validate the plan, point the
        lost slots at their replacement OSDs, replay deletes and empty
        objects immediately, and return the rebuild work (names grouped
        by shard length) for a RecoveryRunner to execute — possibly
        FUSED with other PGs' plans into shared decode launches (the
        cross-PG batch formation the per-PG reconcile round lacked).
        Raises ValueError before any mutation when the plan is
        impossible (insufficient live helpers), exactly like the old
        monolithic recover_shards.

        Helper selection goes through the repair-locality planner
        (repairplan.plan_repair): LRC single-loss reads one local
        group, Clay single-loss reads only the repair planes (the
        runner ships sub-chunk ranges), SHEC/RS rank by the optional
        per-helper `helper_costs` (slot -> cost; the daemon feeds its
        complaint memory + peer-latency EWMAs).

        A call to a replacement OSD can fail (a timeout on a busy
        host) after the slots were re-pointed: they are put back, or
        the caller's next look would find acting as the map has it,
        nothing lost, and a PG that reads clean with a shard that was
        never rebuilt."""
        was = list(self.acting)
        try:
            return self._plan_recovery(
                lost_shards, replacement_osds, verify_hinfo, names,
                helper_exclude, helper_costs)
        except BaseException:
            self.acting[:] = was
            raise

    def _plan_recovery(self, lost_shards, replacement_osds, verify_hinfo,
                       names, helper_exclude, helper_costs
                       ) -> "_RecoveryPlan":
        lost = sorted(set(lost_shards))
        if len(lost) > self.m:
            raise ValueError(f"{len(lost)} lost shards exceeds m={self.m}")
        excluded = helper_exclude or set()
        full_plan = names is None
        names = sorted(self.object_sizes) if names is None \
            else sorted(set(names))
        provided = set(names)
        # helpers must be caught up for everything being REBUILT — a
        # stale survivor would decode old bytes into the new shard.
        # Validate the plan BEFORE mutating acting, so an impossible
        # recovery (insufficient live helpers) leaves no partial state.
        # A deletes-only replay needs no helper data at all.
        rebuild = [n for n in names if n in self.object_sizes]
        survivors: list[int] = []
        helper: list[int] = []
        repair = None
        if rebuild:
            survivors = self._fresh_for(
                rebuild, [s for s in range(self.n)
                          if s not in lost and s not in excluded])
            repair = plan_repair(self.coder, lost, survivors,
                                 costs=helper_costs)
            helper = sorted(repair.helpers)
            self._count_plan(repair.family)
        repl = replacement_osds or {}
        for s in lost:
            new_osd = repl.get(s, self.acting[s])
            self.acting[s] = new_osd
            t = Transaction().create_collection(shard_cid(self.pg, s))
            self.cluster.osd(new_osd).queue_transaction(t)
        plan = _RecoveryPlan(self, lost, helper, survivors,
                             verify_hinfo, full_plan, provided)
        plan.repair = repair
        # names whose last log entry was a DELETE replay as removals
        names = self._replay_deletes(lost, names)

        for name in names:
            if self.object_sizes[name] == 0:
                hinfo = HashInfo(1, 0, [0xFFFFFFFF])
                for s in lost:
                    # truncate clears a stale pre-failure chunk (the
                    # object may have shrunk to empty while this shard
                    # was down)
                    t = (Transaction()
                         .write(shard_cid(self.pg, s), name, 0, b"")
                         .truncate(shard_cid(self.pg, s), name, 0)
                         .setattr(shard_cid(self.pg, s), name,
                                  HINFO_KEY, hinfo.to_bytes()))
                    self._store(s).queue_transaction(t)
                plan.counters["objects"] += 1
                continue
            plan.names_by_len.setdefault(
                self._shard_len(self.object_sizes[name]),
                []).append(name)
        plan.remaining = {n for g in plan.names_by_len.values()
                          for n in g}
        if plan.names_by_len:
            if repair is not None and repair.planes is not None:
                # sub-chunk wire reads: stage only the repair planes
                # and decode through the range program — the helper
                # bytes on the wire drop to wire_fraction of a full
                # pull (beta/q^t for Clay)
                fn = self.coder.range_batch_decoder(lost, helper)
                if fn is not None:
                    plan.dec_fn = fn
                    plan.group_key = self.coder. \
                        range_decode_program_key(lost, helper)
                    plan.range_planes = repair.planes
                    plan.sub_count = repair.sub_chunk_count
            if plan.dec_fn is None:
                plan.dec_fn = self.coder.batch_decoder(lost, helper)
                if plan.dec_fn is not None:
                    key = self.coder.decode_program_key(lost, helper)
                    # id()-keyed fallbacks stay in the BACKEND's cache
                    # (a process-wide id key could alias a dead object)
                    plan.group_key = key if key is not None else None
        return plan

    def recover_shards(self, lost_shards: list[int],
                       replacement_osds: dict[int, int] | None = None,
                       batch: int = 128,
                       verify_hinfo: bool = True,
                       names: list[str] | None = None,
                       helper_exclude: set[int] | None = None,
                       helper_costs: dict[int, int] | None = None) -> dict:
        """Rebuild every object's lost shard(s): the RecoveryOp loop,
        batched AND pipelined. Returns counters {objects, bytes,
        hinfo_failures}. One-plan convenience over plan_recovery +
        RecoveryRunner — the cross-PG reconcile pass feeds MANY plans
        to one runner instead.

        Dataflow (ref: ECBackend::continue_recovery_op streaming, P5):
        for codecs with a static decode matrix (batch_decoder), each
        sub-batch is ONE fused device launch (decode + helper XOR-fold;
        integrity rides the fold — see RecoveryRunner); launches are
        enqueued asynchronously with copy_to_host_async, so results
        stream back one batch behind (double buffering). Codecs
        without a static matrix take the generic decode_chunks path,
        still batched per launch.

        lost_shards: shard slots whose OSD died.
        replacement_osds: slot -> new OSD id (defaults to reusing the
        slot's OSD id, i.e. re-created store after replacement).
        names: restrict recovery to these objects — the PG-log
        delta-replay path (a revived shard rebuilds only what it
        missed; ref: PGLog-driven recovery vs backfill).
        helper_exclude: shard slots that must not serve helper reads
        (other still-down OSDs during a partial rejoin).
        """
        plan = self.plan_recovery(lost_shards, replacement_osds,
                                  verify_hinfo, names, helper_exclude,
                                  helper_costs=helper_costs)
        RecoveryRunner([plan], batch=batch, perf=self.perf).run()
        return plan.counters

    def _recover_fallback(self, lost: list[int], survivors: list[int],
                          bad_pairs: dict[str, set[int]],
                          subgroup: list[str], rebuilt_all: np.ndarray,
                          counters: dict) -> None:
        """Re-decode objects whose helper reads failed hinfo, batched by
        identical bad-shard set (one decode launch per distinct set
        instead of the r01 per-object loop)."""
        by_bad: dict[tuple[int, ...], list[str]] = {}
        for name, bad in bad_pairs.items():
            by_bad.setdefault(tuple(sorted(bad)), []).append(name)
        for bad, names_ in by_bad.items():
            alt = [s for s in survivors if s not in bad]
            alt_need = sorted(self.coder.minimum_to_decode(lost, alt))
            stacks = {s: np.stack([self._store(s).read(
                shard_cid(self.pg, s), n) for n in names_])
                for s in alt_need}
            self.perf.inc("recover_host_launches")
            alt_rec = self.coder.decode_chunks(lost, stacks)
            for li, s in enumerate(lost):
                rec_s = np.asarray(alt_rec[s])
                for ni, name in enumerate(names_):
                    rebuilt_all[subgroup.index(name), li] = rec_s[ni]

    def _writeback_rebuilt(self, lost: list[int], subgroup: list[str],
                           rebuilt_all: np.ndarray, crcs: np.ndarray,
                           sl: int, counters: dict,
                           window: "RecoveryRunner | None" = None) -> None:
        # ONE combined txn per replacement shard for the whole batch
        # (the write-path fan-out unit), pipelined across shards — at
        # the wire tier this is len(lost) overlapped MStoreOp frames
        # per batch instead of len(lost) * B sequential ones. With a
        # `window`, the push rides the runner's byte-budgeted in-flight
        # window instead: frames of LATER batches go out before these
        # acks return (acks are collected as the budget fills and at
        # finish()), the recovery analog of the client op window.
        txns = []
        for li, s in enumerate(lost):
            cid = shard_cid(self.pg, s)
            t = Transaction()
            for bi, name in enumerate(subgroup):
                chunk = rebuilt_all[bi, li]
                hinfo = HashInfo(1, sl, [int(crcs[bi, li])])
                t.write(cid, name, 0, chunk) \
                 .truncate(cid, name, sl) \
                 .setattr(cid, name, HINFO_KEY, hinfo.to_bytes())
                counters["bytes"] += int(chunk.size)
            txns.append((s, t))
        if window is None:
            self._fanout_txns(txns)
        else:
            window.push_txns(self, txns, len(subgroup) * sl)
        counters["objects"] += len(subgroup)

    def _count_recovery(self, counters: dict) -> None:
        self.perf.inc_many(
            (("recovered_objects", counters["objects"]),
             ("recovered_bytes", counters["bytes"]),
             ("hinfo_failures", counters["hinfo_failures"])))

    # -- deep scrub ----------------------------------------------------------

    def _scrub_journal(self, live_slots: list[int]) -> dict:
        """Journal-aware deep scrub (r17): audit pending
        __stripe_journal__ intents instead of skipping the collection
        with the other "__" internals. Per live slot, every entry must
        decode (codec version 1), agree with its omap key, name this
        slot as a participant, fit its own geometry (the delta payload
        inside the new shard length), and sit ABOVE the slot's applied
        watermark (an entry at-or-below the watermark was applied but
        never dropped — the apply txn is atomic, so that's store
        corruption, not lag). Intents a later write superseded are
        counted stale — inert by the replay's version guard, not
        corrupt. Findings stay OUT of the `inconsistent` list: a
        pending intent is crash-recovery state, and auto_repair's
        decode-rebuild must never chew on the journal object."""
        pending = stale = 0
        bad: list[tuple[int, str]] = []      # (slot, why)
        for s in live_slots:
            store = self._store(s)
            cid = shard_cid(self.pg, s)
            try:
                if not store.exists(cid, self.JOURNAL_OBJ):
                    continue
                page = store.omap_iter(cid, self.JOURNAL_OBJ)
            except (ConnectionError, OSError, KeyError):
                continue                      # unreachable: lag excuse
            watermark = None
            entries: list[tuple[bytes, bytes]] = []
            for key, val in page:
                if key == self._J_APPLIED:
                    if len(val) == 8:
                        watermark = _struct.unpack("<Q", val)[0]
                    else:
                        bad.append((s, "watermark not 8 bytes"))
                elif key.startswith(b"e"):
                    entries.append((key, val))
                else:
                    bad.append((s, f"unknown journal key {key!r}"))
            for key, val in entries:
                try:
                    ent = self._decode_jentry(val)
                except Exception as e:   # noqa: BLE001 — ANY decode
                    # failure is the corruption this audit exists for
                    bad.append((s, f"undecodable intent {key!r}: "
                                   f"{type(e).__name__}"))
                    continue
                if self._jkey(ent["seq"]) != key:
                    bad.append((s, f"intent seq {ent['seq']} "
                                   f"disagrees with key {key!r}"))
                    continue
                if s not in ent["participants"]:
                    bad.append((s, f"intent seq {ent['seq']} does "
                                   f"not name slot {s} a participant"))
                    continue
                if ent["delta"] and \
                        ent["a"] + len(ent["delta"]) > ent["nsl"]:
                    bad.append((s, f"intent seq {ent['seq']} delta "
                                   f"overruns shard length "
                                   f"{ent['nsl']}"))
                    continue
                if watermark is not None and ent["seq"] <= watermark:
                    bad.append((s, f"intent seq {ent['seq']} at or "
                                   f"below applied watermark "
                                   f"{watermark} (apply is atomic "
                                   f"with the entry drop)"))
                    continue
                if ent["version"] <= self.object_versions.get(
                        ent["name"], 0):
                    stale += 1                # superseded: inert
                else:
                    pending += 1              # legitimate in-flight
        return {"journal_pending": pending, "journal_stale": stale,
                "journal_bad": bad}

    def deep_scrub(self, dead_osds: set[int] | None = None) -> dict:
        """Read every LIVE shard of every object, verify stored hinfo
        CRCs (the be_deep_scrub bulk-checksum audit), batched per
        shard. Dead slots are skipped — even touching their stores
        would resurrect destroyed OSD ids. The per-PG stripe journal
        is audited too (see _scrub_journal) instead of skipped."""
        from ..csum.kernels import crc32c_blocks
        dead = dead_osds or set()
        bad: list[tuple[str, int]] = []
        checked = 0
        for s in range(self.n):
            if self.acting[s] in dead:
                continue
            store = self._store(s)
            cid = shard_cid(self.pg, s)
            # a shard behind on an object's last write (or holding a
            # not-yet-replayed delete's leftover) is lagging, not
            # corrupt — same staleness excuse the replicated scrub and
            # shallow scrub apply
            # "__"-prefixed objects are PG-internal bookkeeping (e.g.
            # the standalone tier's __pg_meta__ omap blob): no hinfo,
            # not client data — the scrub audits client objects only
            names = [n for n in store.list_objects(cid)
                     if not n.startswith("__")
                     and n in self.object_sizes
                     and self.shard_applied[s]
                     >= self.object_versions.get(n, 0)]
            by_len: dict[int, list[str]] = {}
            for n in names:
                by_len.setdefault(store.stat(cid, n), []).append(n)
            for ln, group in by_len.items():
                blocks = np.stack([store.read(cid, n) for n in group])
                crcs = np.asarray(crc32c_blocks(blocks, init=0xFFFFFFFF,
                                                xorout=0))
                for bi, n in enumerate(group):
                    hinfo = HashInfo.from_bytes(store.getattr(cid, n,
                                                              HINFO_KEY))
                    checked += 1
                    if hinfo.get_chunk_hash(0) != int(crcs[bi]):
                        bad.append((n, s))
        rep = {"checked": checked, "inconsistent": bad}
        rep.update(self._scrub_journal(
            [s for s in range(self.n) if self.acting[s] not in dead]))
        return rep


# -- cross-PG recovery engine -------------------------------------------------

_RECOVER_PROGRAMS: dict = {}
_RECOVER_PROGRAMS_LOCK = _threading.Lock()

#: one shard-fetch frame's byte budget (readv chunks larger batches so
#: a single source OSD never serializes a multi-MiB frame per pull)
RECOVERY_FETCH_BYTES = 8 << 20


#: the CEILING on the helper bytes one fused recovery launch stages
#: (pow2 objects, one at least). What sizes a launch is the runner's
#: byte budget where it has one: on the wire tier
#: osd_recovery_max_active x osd_recovery_max_chunk (24 MiB by default:
#: 4 objects of 4 MiB at k=8), the budget of the push window; this
#: constant bounds larger settings and the runners without a window
#: (`recover_shards`, tools). The launch's device scratch is ~13x what it stages at 512 KiB
#: rows (compiled for a described v5e: 1.7 GiB at 128 MiB staged,
#: 6.2 GiB at the 512 MiB that osd_recovery_batch=128 objects of 4 MiB
#: would stage), two launches are in flight per runner, and every
#: daemon of a process shares the one chip's 16 GiB
RECOVERY_STAGE_BYTES = 128 << 20


class _SlotRows:
    """(B, n, L) rows of a write in slot order, over its data and parity
    rows and without a copy of either: `rows[b, s]` is a view of the
    dense row slot s carries (`row_of_slot`, the inverse of the chunk
    mapping). What the fan-out takes of a mapping that is not the
    identity; `np.asarray(rows)` makes the copy."""

    __slots__ = ("data", "parity", "row_of_slot")

    def __init__(self, data: np.ndarray, parity: np.ndarray,
                 row_of_slot: np.ndarray):
        self.data, self.parity, self.row_of_slot = data, parity, row_of_slot

    def __getitem__(self, key):
        b, s, *rest = key
        j, k = int(self.row_of_slot[s]), self.data.shape[1]
        row = self.data[b, j] if j < k else self.parity[b, j - k]
        return row[tuple(rest)]

    def __array__(self, dtype=None, copy=None):
        dense = np.concatenate([self.data, self.parity], axis=1)
        return dense[:, self.row_of_slot].astype(dtype or np.uint8,
                                                 copy=False)


def _fetch_frames(nb: int, rl: int) -> list[tuple[int, int]]:
    """(first row, rows) of each readv frame that pulls `nb` objects'
    rows of `rl` bytes from one helper: chunks of RECOVERY_FETCH_BYTES,
    so that one source never serializes a giant frame."""
    per = max(1, RECOVERY_FETCH_BYTES // max(1, rl))
    return [(c0, min(per, nb - c0)) for c0 in range(0, nb, per)]


@_functools.lru_cache(maxsize=64)
def _host_encoder_handle(matrix_bytes: bytes, k: int, m: int):
    """Process-wide native RS encoder per coding matrix (the same
    sharing rule as the fused-program cache). Handles live for the
    process — ec_destroy never runs, matching the program caches."""
    try:
        from .. import native
        h = native.lib().ec_create_with_matrix(k, m, matrix_bytes)
        return h or None
    except Exception:   # noqa: BLE001 — no native lib: device path
        return None


@_functools.lru_cache(maxsize=1)
def _host_crc_available() -> bool:
    """Host-integrity mode: on the CPU backend with the native SSE4.2
    crc32c built, checksums run faster as host instructions than as
    XLA programs (~20x against the table-gather program; the bit-linear
    one is not measured there) — the device then runs DECODE ONLY
    (plus the helper XOR-fold) and integrity moves off the launch.
    On a real accelerator the device checksum is nearly free and the
    host would serialize, so this stays device-side there."""
    import jax
    if jax.default_backend() != "cpu":
        return False
    try:
        from .. import native
        return native.ready() and native.crc32c_hw()
    except Exception:   # noqa: BLE001 — any native trouble = no mode
        return False


@_functools.lru_cache(maxsize=4096)
def _shift_cols(nbytes: int) -> tuple:
    """Packed GF(2) column constants of the CRC32C shift-by-nbytes
    matrix (cached: the RMW path shifts through the same tail
    distances over and over)."""
    from ..csum.reference import matrix_cols_u32, shift_matrix
    return tuple(int(c) for c in matrix_cols_u32(shift_matrix(nbytes)))


def _crc_shift(reg: int, nbytes: int) -> int:
    """Advance a raw CRC32C register through nbytes zero bytes — the
    O(1) building block of the incremental hinfo update (CRC32C is
    GF(2)-linear in the message AND the seed, so
    crc(new_row) = shift^{tail}(crc(old_row)) ^ shift^{tail'}(crc0(delta)))."""
    if nbytes == 0 or reg == 0:
        return int(reg)
    cols = _shift_cols(int(nbytes))
    out = 0
    for b in range(32):
        if (reg >> b) & 1:
            out ^= cols[b]
    return out


def _rows_crc0(rows: np.ndarray) -> np.ndarray:
    """(N, L) byte rows -> (N,) ZERO-seed crc32c (the delta-row
    convention: a zero seed composes under XOR and position shifts);
    native SSE4.2 when built, batched device launch otherwise."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    if _host_crc_available():
        from .. import native
        return np.asarray(native.native_crc32c_rows(0, rows),
                          dtype=np.uint32)
    from ..csum.kernels import crc32c_blocks
    from ..ops.rs_kernels import run_bucketed
    return np.asarray(run_bucketed(
        lambda b: crc32c_blocks(b, init=0, xorout=0), rows),
        dtype=np.uint32)


def _rows_crc32c(rows: np.ndarray) -> np.ndarray:
    """(B, L) byte rows -> (B,) raw crc32c (seed -1, the HashInfo
    convention); native SSE4.2 when built, batched device launch
    otherwise."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    if _host_crc_available():
        from .. import native
        return native.native_crc32c_rows(0xFFFFFFFF, rows)
    return np.asarray(PGBackend._batched_crcs(rows), dtype=np.uint32)


def readv_ranges_host(store, cid: str, names: list[str], length: int,
                      ranges, attr_key: str | None, perf=None
                      ) -> tuple[np.ndarray, np.ndarray | None,
                                 list[int]]:
    """Serve a ranged shard pull from a LOCAL store — the source half
    of the sub-chunk wire read (ref: ErasureCodeClay's
    minimum_to_decode sub-chunk ranges riding the ECSubRead).

    Per object: verify the FULL stored row against its hinfo when
    `attr_key` is given (rot detection stays at the source — the
    receiver never sees the whole row, so the r10 whole-row fold can't
    cover it), slice the planned `ranges`, and crc32c the shipped
    bytes (range-level integrity the receiver's fold verify consumes;
    CRC32C is GF(2)-linear at any row length, so H range rows still
    verify with ONE fold CRC).

    Returns (rows (B, rl) uint8, range CRCs (B,) uint32 | None,
    indices of rows whose FULL shard failed its hinfo — their range
    bytes ship anyway and the receiver plans around them).

    Spans: `recovery.serve_ranges` (nbytes: the range bytes shipped)
    round the whole, with the parts beside it: `.read` (the store
    reads), `.verify` (the full rows' crcs against hinfo) and `.slice`
    (the ranges copied out and their crcs). `perf`, the serving
    daemon's `ec` logger, counts the frame, the bytes it ships and the
    bytes it checksums whole."""
    ranges = [(int(o), int(ln)) for o, ln in ranges]
    rl = sum(ln for _o, ln in ranges)
    B = len(names)
    with span("recovery.serve_ranges", nbytes=B * rl):
        served = _serve_ranges(store, cid, names, length, ranges, rl,
                               attr_key)
    if perf is not None:
        perf.inc_many((("recover_range_frames_served", 1),
                       ("recover_range_bytes_served", B * rl),
                       ("recover_range_verify_bytes",
                        B * length if attr_key is not None else 0)))
    return served


def _serve_ranges(store, cid: str, names: list[str], length: int,
                  ranges: list, rl: int, attr_key: str | None):
    B = len(names)
    rows = np.empty((B, rl), dtype=np.uint8)
    bad: list[int] = []
    if attr_key is not None:
        full = np.empty((B, length), dtype=np.uint8)
        with span("recovery.serve_ranges.read", detail=True):
            for i, name in enumerate(names):
                arr = store.read(cid, name)
                if len(arr) != length:
                    # a stale/partial shard must fail LOUDLY — zero-
                    # filling would hand the decoder garbage (the readv
                    # contract)
                    raise ValueError(
                        f"readv_ranges: {name!r} is {len(arr)} bytes, "
                        f"expected {length}")
                full[i] = arr
        with span("recovery.serve_ranges.verify", detail=True):
            crcs = _rows_crc32c(full)
            for i, name in enumerate(names):
                hinfo = HashInfo.from_bytes(
                    store.getattr(cid, name, attr_key))
                if int(crcs[i]) != hinfo.get_chunk_hash(0):
                    bad.append(i)
        with span("recovery.serve_ranges.slice", detail=True):
            at = 0
            for off, ln in ranges:
                rows[:, at:at + ln] = full[:, off:off + ln]
                at += ln
            range_crcs = _rows_crc32c(rows)
        return rows, range_crcs, bad
    with span("recovery.serve_ranges.read", detail=True):
        for i, name in enumerate(names):
            at = 0
            for off, ln in ranges:
                got = store.read(cid, name, off, ln)
                if len(got) != ln:
                    raise ValueError(
                        f"readv_ranges: {name!r} range ({off},{ln}) "
                        f"returned {len(got)} bytes")
                rows[i, at:at + ln] = got
                at += ln
    return rows, None, bad


@_functools.lru_cache(maxsize=256)
def _fold_seed_const(sl: int) -> int:
    """shift^{sl}(0xFFFFFFFF): the seed contribution inside a raw
    hinfo CRC of an sl-byte row (crc_{-1}(m) = crc_0(m) ^ K)."""
    from ..csum.reference import apply_shift
    return int(apply_shift(0xFFFFFFFF, sl))


def _expected_fold_crcs(exp: np.ndarray, sl: int) -> np.ndarray:
    """Expected raw CRC of the XOR-fold of H helper rows, from their
    expected per-row hinfo CRCs. CRC32C is GF(2)-linear in the
    message: crc_0(r0 ^ .. ^ rH) = XOR_i crc_0(r_i), and the -1 seed
    adds the constant K = shift^{sl}(-1) per row — so H rows verify
    with ONE data-pass checksum instead of H (arxiv 2108.02692's
    aggregation idea applied to the verify pass; a corruption pair
    that XOR-cancels would need a 2^-32 collision AND two rotten
    helpers in one object)."""
    K = np.uint32(_fold_seed_const(sl))
    folded = np.bitwise_xor.reduce(exp.astype(np.uint32) ^ K, axis=1)
    return folded ^ K


def _build_recover_program(dec_fn, verify: bool, host_crc: bool):
    """ONE jitted device program per (decode program, verify, mode) —
    process-wide when the coder exposes a decode_program_key, so every
    PG backend with the same geometry shares ONE compiled program (the
    r09 tree compiled it once per PG per daemon).

    host_crc mode: fn(stack) -> (rebuilt[, helper-fold]); checksums run
    on the host (native SSE4.2). Device mode: fn(stack, expfold) ->
    (rebuilt, rebuilt-CRCs, fold-ok) all device-resident."""
    import jax
    import jax.numpy as jnp

    if host_crc:
        def fused(stack):              # (B, H, sl) u8
            rebuilt = dec_fn(stack)    # (B, E, sl)
            if verify:
                fold = jnp.bitwise_xor.reduce(stack, axis=1)
                return rebuilt, fold
            return (rebuilt,)
        return jax.jit(fused)

    from ..csum.kernels import crc32c_blocks

    def fused(stack, expfold):         # (B, H, rl) u8, (B,) u32
        # rebuilt (B, E, sl): sl may exceed the staged rl (range plans
        # ship sub-chunks, rebuild whole rows)
        rebuilt = dec_fn(stack)
        rcrc = crc32c_blocks(rebuilt, init=0xFFFFFFFF, xorout=0)
        if verify:
            fold = jnp.bitwise_xor.reduce(stack, axis=1)
            fcrc = crc32c_blocks(fold, init=0xFFFFFFFF, xorout=0)
            ok = fcrc == expfold
        else:
            ok = jnp.ones(stack.shape[:1], dtype=bool)
        return rebuilt, rcrc, ok
    return jax.jit(fused)


class _RecoveryPlan:
    """One PG's recovery intent (opened by ECBackend.plan_recovery):
    the rebuild name groups plus everything a RecoveryRunner needs to
    stage, verify, write back, and finally mark the slots caught up.
    `remaining` shrinks as batches land — a wire-tier round that dies
    mid-way re-plans exactly the leftover names."""

    __slots__ = ("be", "lost", "helper", "survivors", "verify",
                 "full_plan", "provided", "counters", "names_by_len",
                 "dec_fn", "group_key", "remaining", "done",
                 "repair", "range_planes", "sub_count", "_counted")

    def __init__(self, be, lost, helper, survivors, verify, full_plan,
                 provided):
        self.be = be
        self.lost = list(lost)
        self.helper = list(helper)
        self.survivors = list(survivors)
        self.verify = verify
        self.full_plan = full_plan
        self.provided = provided
        self.counters = {"objects": 0, "bytes": 0, "hinfo_failures": 0}
        self._counted = dict(self.counters)   # what the perf counters have
        self.names_by_len: dict[int, list[str]] = {}
        self.dec_fn = None
        self.group_key = None
        self.remaining: set[str] = set()
        self.done = False
        # repair-locality planner outputs: the RepairPlan that chose
        # the helpers, plus the sub-chunk range shape when the wire
        # ships less than full rows (range_planes None = full rows)
        self.repair = None
        self.range_planes: tuple[int, ...] | None = None
        self.sub_count = 1

    def row_ranges(self, sl: int):
        """(row bytes shipped per helper, coalesced (off, len) ranges
        or None) at shard length `sl` — the wire shape of one staged
        helper row."""
        if self.range_planes is None:
            return sl, None
        from .repairplan import coalesce_ranges
        s = sl // self.sub_count
        return (len(self.range_planes) * s,
                coalesce_ranges((z * s, s) for z in self.range_planes))

    def count(self) -> None:
        """Fold what was rebuilt since the last call into the perf
        counters: once a batch, so that `recovered_objects` rises while
        a long round runs and not in one step at its end."""
        new = {k: v - self._counted[k] for k, v in self.counters.items()}
        self._counted = dict(self.counters)
        self.be._count_recovery(new)

    def finish(self) -> None:
        """Count the work done; advance applied cursors only when every
        planned name landed (a partial round must not defeat the
        staleness gate — the retry covers the rest)."""
        if self.done:
            return
        self.done = True
        if not self.remaining:
            self.be._mark_caught_up(self.lost, self.full_plan,
                                    self.provided)
        self.count()


class RecoveryRunner:
    """Cross-PG fused recovery: executes MANY plans as one pipeline of
    fused decode batches (ref: ECBackend::continue_recovery_op, but the
    unit of admission is a BATCH drawn from every primaried PG, not one
    RecoveryOp of one PG).

    Batch formation: fused plans group by (decode-program key, shard
    length) — PGs sharing a geometry and loss pattern FILL shared
    batches, so the round costs one launch per batch instead of one
    per PG; mixed-geometry plans (different k/m, different loss slots)
    ride the same pipeline side by side with their own programs. The
    batch dim is pow2-bucketed like the write path (ragged tails would
    compile one program per size).

    What sizes a batch: as many objects (a power of two, one at least)
    as stage `push_window_bytes` of helper rows — the one byte budget of
    a grant, which on the wire tier is osd_recovery_max_active x
    osd_recovery_max_chunk and also bounds the push window — under the
    ceilings `batch` (osd_recovery_batch) and RECOVERY_STAGE_BYTES; a
    runner without a budget (`recover_shards`, tools) takes the
    ceilings. So a grant of the wire tier holds its primary's worker,
    the daemon lock and the PG locks for a few objects' worth of pull,
    launch and push, whatever the backlog.

    Pipelining: launches dispatch async with copy_to_host_async, one
    batch ahead (results stream back under the next batch's staging);
    shard fetches submit per (PG, helper shard) and overlap across
    source OSDs (windowed PULL); writeback acks collect behind a byte
    budget (windowed PUSH). step() advances one batch at a time so the
    wire tier's mClock worker can interleave client ops between grants.

    Consistency under interleaved client ops (wire tier): the lost
    slots were repointed at plan time, so every client mutation after
    that reaches the recovering store directly; staging skips names
    whose size-class changed, and writeback skips names whose version
    moved since their stage — a skipped name needs nothing from us and
    a write of the OLD decode would resurrect overwritten (or deleted)
    bytes under a fresh CRC."""

    def __init__(self, plans, batch: int = 128, perf=None,
                 push_window_ops: int = 0, push_window_bytes: int = 0,
                 host_crc: bool | None = None):
        self.plans = [p for p in plans if p is not None]
        self.perf = perf if perf is not None else (
            self.plans[0].be.perf if self.plans else ec_perf_counters())
        self.batch = max(1, int(batch))
        self._host_crc = (_host_crc_available() if host_crc is None
                          else bool(host_crc))
        self._push_ops_cap = int(push_window_ops)
        self._push_bytes_cap = int(push_window_bytes)
        self._push: list = []        # (handle, nbytes) in-flight acks
        self._push_bytes = 0
        self.stats = {"batches": 0, "fused_batches": 0,
                      "generic_batches": 0, "cross_pg_batches": 0,
                      "range_batches": 0, "helper_bytes_on_wire": 0,
                      "push_stalls": 0, "push_max_inflight_bytes": 0,
                      "skipped_stale": 0,
                      "host_crc": self._host_crc}
        from ..ops.rs_kernels import pow2_bucket
        self._batches: list = []
        self._buckets: dict[int, int] = {}   # batch index -> launch rows
        groups: dict = {}
        order: list = []
        for plan in self.plans:
            for sl, names in sorted(plan.names_by_len.items()):
                if plan.dec_fn is None:
                    for i in range(0, len(names), self.batch):
                        self._batches.append(
                            ("generic", plan, sl,
                             names[i:i + self.batch]))
                    continue
                key = (plan.group_key
                       if plan.group_key is not None
                       else ("inst", id(plan.be), tuple(plan.lost),
                             tuple(plan.helper)),
                       sl, plan.verify)
                if key not in groups:
                    groups[key] = []
                    order.append(key)
                groups[key].extend((plan, n) for n in names)
        for key in order:
            pairs = groups[key]
            proto = pairs[0][0]
            rl, _ranges = proto.row_ranges(key[1])
            budget = min(RECOVERY_STAGE_BYTES,
                         self._push_bytes_cap or RECOVERY_STAGE_BYTES)
            fit = budget // max(1, len(proto.helper) * rl)
            per = min(self.batch, 1 << max(0, fit.bit_length() - 1))
            # one launch shape a group: a group of several batches
            # launches its short tail padded to the whole batch (zeros
            # cost a launch milliseconds; a program of its own for the
            # tail's bucket costs a compile)
            bucket = pow2_bucket(min(per, len(pairs)))
            for i in range(0, len(pairs), per):
                sub = pairs[i:i + per]
                self._buckets[len(self._batches)] = bucket
                self._batches.append(("fused", proto, key[1], sub))
        self._bi = 0
        self._pending: list = []
        self._stage_bufs: dict = {}

    # -- pacing hooks (the mClock worker's inputs) -------------------------

    def pending(self) -> int:
        return (len(self._batches) - self._bi) + len(self._pending)

    def next_cost(self) -> int:
        """Bytes the next step will move — the mClock cost input
        (range plans cost their PLANNED wire bytes, not full rows)."""
        if self._bi < len(self._batches):
            kind, plan, sl, payload = self._batches[self._bi]
            rl, _ranges = plan.row_ranges(sl)
            return max(1, len(plan.helper)) * rl * len(payload)
        if self._pending:
            sl, pairs = self._pending[0][0], self._pending[0][2]
            return sl * len(pairs)
        return 1

    def next_stage_bytes(self) -> int:
        """Helper bytes the next step is to stage: 0 where it only
        completes a launch already made."""
        return self.next_cost() if self._bi < len(self._batches) else 0

    def next_helper_osds(self) -> list[int]:
        """Distinct source OSD ids the NEXT batch pulls helper rows
        from — the key set the r17 per-failure-domain repair budgets
        bucket next_cost()'s bytes by. Empty when the pipeline is
        drained or the next step is a completion (no new reads)."""
        if self._bi >= len(self._batches):
            return []
        kind, plan, _sl, payload = self._batches[self._bi]
        plans = [plan] if kind == "generic" else \
            list({id(p): p for p, _n in payload}.values())
        out: set[int] = set()
        for p in plans:
            for h in p.helper:
                out.add(int(p.be.acting[h]))
        return sorted(out)

    # -- pipeline ----------------------------------------------------------

    def step(self) -> bool:
        """One pipeline advance: launch the next batch (completing the
        oldest first when the pipeline is full) or drain one pending
        completion. Returns True while work remains."""
        if self._bi < len(self._batches):
            kind, plan, sl, payload = self._batches[self._bi]
            self._bi += 1
            if kind == "generic":
                self._run_generic(plan, sl, payload)
            else:
                self._launch(sl, payload, self._buckets[self._bi - 1])
                if len(self._pending) >= 2:
                    self._complete(self._pending.pop(0))
        elif self._pending:
            self._complete(self._pending.pop(0))
        else:
            return False
        return self._bi < len(self._batches) or bool(self._pending)

    def prepare(self) -> None:
        """Build every program the round will launch, at the shape it
        will launch it, before the first `step()`: one pass of zeros
        through each (program, planned batch shape), so that jax holds
        the compiled program and no launch compiles. The caller holds no
        lock (a wire-tier step runs under the daemon lock and every
        member PG's lock, and a program takes seconds to compile)."""
        import jax
        seen = set()
        for bi, (kind, proto, sl, pairs) in enumerate(self._batches):
            if kind != "fused":
                continue
            rl, _ranges = proto.row_ranges(sl)
            if proto.range_planes is not None:
                self._prepare_range_sources(pairs, sl, rl, seen)
            shape = (self._buckets[bi], len(proto.helper), rl)
            program = self._program(proto)
            if (id(program), shape) in seen:
                continue
            seen.add((id(program), shape))
            stack = self._stage_buffer(*shape)
            jax.block_until_ready(
                program(stack) if self._host_crc
                else program(stack, np.zeros(shape[0], np.uint32)))

    def _prepare_range_sources(self, pairs, sl: int, rl: int,
                               seen: set) -> None:
        """The sources of a range plan checksum each frame's full rows
        and its shipped ranges on the device (`readv_ranges_host`), at
        the frame's row count: the frames `_stage` sends for each
        plan's run of the batch (`_segments`, `_fetch_frames`). Those
        crc programs are built here too, where every daemon of an
        in-process cluster finds them, so that no frame of a short tail
        compiles."""
        if _host_crc_available():
            return
        from ..ops.rs_kernels import pow2_bucket
        for _plan, _r0, names in self._segments(pairs):
            for _c0, rows in _fetch_frames(len(names), rl):
                for width in (sl, rl):
                    key = ("range-source", pow2_bucket(rows), width)
                    if key not in seen:
                        seen.add(key)
                        _rows_crc32c(np.zeros((rows, width), np.uint8))

    def run(self) -> None:
        while self.step():
            pass
        self.finish()

    def finish(self) -> None:
        """Drain the pipeline and the push window, then settle every
        plan (cursor advance + counter fold)."""
        while self._pending:
            self._complete(self._pending.pop(0))
        self._drain_push(0, 0)
        for plan in self.plans:
            plan.finish()

    # -- windowed push ------------------------------------------------------

    def push_txns(self, be, txns, nbytes: int) -> None:
        """Submit writeback transactions into the in-flight window:
        transmit now, collect acks only when the byte/op budget fills
        (and at finish) — later batches' frames overlap these acks."""
        for shard, t in txns:
            st = be._store(shard)
            submit = getattr(st, "queue_transaction_async", None)
            if submit is None:
                st.queue_transaction(t)
                continue
            if self._push_ops_cap or self._push_bytes_cap:
                stalled = self._drain_push(
                    (self._push_ops_cap - 1) if self._push_ops_cap
                    else None,
                    (self._push_bytes_cap - nbytes)
                    if self._push_bytes_cap else None)
                if stalled:
                    self.stats["push_stalls"] += stalled
            self._push.append((submit(t), nbytes))
            self._push_bytes += nbytes
            self.stats["push_max_inflight_bytes"] = max(
                self.stats["push_max_inflight_bytes"], self._push_bytes)
        if not (self._push_ops_cap or self._push_bytes_cap):
            # no window configured: keep the synchronous durability
            # point (every shard acked before the next batch) — the
            # frames still all hit the wire before any ack is awaited
            self._drain_push(0, 0)

    def _drain_push(self, max_ops: int | None,
                    max_bytes: int | None) -> int:
        drained = 0
        while self._push and (
                (max_ops is not None and len(self._push) > max_ops)
                or (max_bytes is not None
                    and self._push_bytes > max(0, max_bytes))):
            h, nb = self._push.pop(0)
            self._push_bytes -= nb
            drained += 1
            h.result()
        return drained

    # -- fused path ---------------------------------------------------------

    def _program(self, plan):
        key = plan.group_key
        if key is None:
            # no shareable identity: cache on the owning backend (the
            # pre-r10 behavior, minus the per-(sl) duplication)
            ckey = ("r10", id(plan.dec_fn), plan.verify, self._host_crc)
            fn = plan.be._fused_cache.get(ckey)
            if fn is None:
                self.perf.inc("program_cache_misses")
                fn = _build_recover_program(plan.dec_fn, plan.verify,
                                            self._host_crc)
                plan.be._fused_cache[ckey] = fn
            else:
                self.perf.inc("program_cache_hits")
            return fn
        ckey = (key, plan.verify, self._host_crc)
        with _RECOVER_PROGRAMS_LOCK:
            fn = _RECOVER_PROGRAMS.get(ckey)
            if fn is None:
                self.perf.inc("program_cache_misses")
                fn = _build_recover_program(plan.dec_fn, plan.verify,
                                            self._host_crc)
                _RECOVER_PROGRAMS[ckey] = fn
            else:
                self.perf.inc("program_cache_hits")
        return fn

    def _stage_buffer(self, bucket: int, H: int, sl: int) -> np.ndarray:
        # ring of 2 reusable buffers per shape: with a depth-2 pipeline
        # the transfer of batch i completed at dispatch, so buffer
        # i % 2 is free by the time batch i+2 stages (a fresh 100+ MiB
        # np.empty per batch pays page-fault cost every launch)
        key = (bucket, H, sl, self.stats["batches"] % 2)
        buf = self._stage_bufs.get(key)
        if buf is None:
            buf = np.zeros((bucket, H, sl), dtype=np.uint8)
            self._stage_bufs[key] = buf
        return buf

    def _launch(self, sl: int, pairs, bucket: int) -> None:
        """Stage and dispatch one fused batch, `bucket` rows wide: the
        planned shape that `prepare()` built, whatever names were
        skipped since."""
        import jax
        proto = pairs[0][0]
        helper = proto.helper
        H = len(helper)
        # the group key pins every plan in the batch to one program,
        # hence one (H, range shape) — rl is the staged row width
        # (full shard, or the planned sub-chunk ranges only)
        rl, _ranges = proto.row_ranges(sl)
        # stage-time revalidation (see class docstring)
        live: list[tuple] = []   # (plan, name, version-at-stage)
        for plan, name in pairs:
            size = plan.be.object_sizes.get(name)
            if size is None or plan.be._shard_len(size) != sl:
                plan.remaining.discard(name)
                self.stats["skipped_stale"] += 1
                continue
            live.append((plan, name,
                         plan.be.object_versions.get(name, 0)))
        if not live:
            return
        B = len(live)
        stack = self._stage_buffer(bucket, H, rl)
        exp = np.zeros((B, H), dtype=np.uint32)
        wire = B * H * rl
        # helper rows in: one readv frame a (PG, helper shard), all on
        # the wire before any answer is taken
        with span("recovery.pull", counters=self.perf,
                  key="recover_stage_time", nbytes=wire):
            pre_bad = self._stage(live, sl, rl, stack, exp,
                                  proto.verify)
        self.stats["helper_bytes_on_wire"] += wire
        self.perf.inc_many((("recover_wire_bytes", wire),
                            ("recover_helper_reads", B * H)))
        if wire > self.perf.get("recover_grant_bytes_max"):
            self.perf.set("recover_grant_bytes_max", wire)
        with span("recovery.stage"):
            if bucket != B:
                stack[B:] = 0
            program = self._program(proto)
            expfold = None
            if not self._host_crc:
                expfold = np.zeros(bucket, dtype=np.uint32)
                if proto.verify:
                    expfold[:B] = _expected_fold_crcs(exp, rl)
                    # a padded all-zero row folds to zero bytes, whose
                    # raw CRC is just the seed shifted through rl zero
                    # bytes — match it so padding never "fails"
                    expfold[B:] = _fold_seed_const(rl)
        self.perf.inc("recover_launches")
        # nbytes: the helper rows this launch decodes, B objects of H;
        # the tags carry the objects and the helper reads to the log
        with span("recovery.launch", counters=self.perf,
                  key="recover_launch_time", nbytes=wire,
                  tags={"objects": B, "recover_helper_reads": B * H}):
            handles = program(stack) if self._host_crc \
                else program(stack, expfold)
            for h in handles:
                try:
                    h.copy_to_host_async()
                except AttributeError:
                    break   # non-jax handle (test stub)
        self._pending.append((sl, rl, live, handles, exp, pre_bad))
        self.stats["batches"] += 1
        self.stats["fused_batches"] += 1
        if proto.range_planes is not None:
            self.stats["range_batches"] += 1
        if len({id(p) for p, _, _ in live}) > 1:
            self.stats["cross_pg_batches"] += 1

    @staticmethod
    def _segments(live) -> list[tuple]:
        """Contiguous per-plan runs of a batch's (plan, name, ...)
        entries: (plan, row0, names)."""
        segs: list[tuple] = []
        for ri, (plan, name, *_rest) in enumerate(live):
            if not segs or segs[-1][0] is not plan:
                segs.append((plan, ri, []))
            segs[-1][2].append(name)
        return segs

    def _stage(self, live, sl: int, rl: int, stack: np.ndarray,
               exp: np.ndarray, verify: bool) -> dict[int, set[int]]:
        """Fill (B, H, rl) helper rows + expected fold inputs. Remote
        stores submit ONE readv frame per (PG, helper shard) — data
        AND integrity in the frame — all frames on the wire before any
        reply is collected (the windowed PULL: fetches from different
        source OSDs overlap instead of serializing per object).

        Full-row plans ship whole shards and `exp` carries the stored
        hinfo CRCs (the r10 whole-row fold). Range plans ship only the
        planned sub-chunk ranges; the SOURCE verifies each full shard
        against its hinfo (rot detection moves to the helper), `exp`
        carries the shipped ranges' CRCs, and rows whose full shard
        failed at the source come back in the returned
        {batch row: {helper slot}} map — the decode proceeds but those
        objects re-decode through the full-row fallback."""
        waits: list[tuple] = []
        pre_bad: dict[int, set[int]] = {}
        for plan, r0, names in self._segments(live):
            nb = len(names)
            _rl, ranges = plan.row_ranges(sl)
            for hi, s in enumerate(plan.helper):
                st = plan.be._store(s)
                cid = shard_cid(plan.be.pg, s)
                frames = _fetch_frames(nb, rl)
                if ranges is not None:
                    subr = getattr(st, "readv_ranges_submit", None)
                    for c0, rows_n in frames:
                        cnames = names[c0:c0 + rows_n]
                        if subr is not None:
                            waits.append(
                                (subr(cid, cnames, sl, ranges,
                                      HINFO_KEY if verify else None),
                                 r0 + c0, hi, len(cnames), s))
                            continue
                        rows, crcs, bad = readv_ranges_host(
                            st, cid, cnames, sl, ranges,
                            HINFO_KEY if verify else None,
                            perf=self.perf)
                        stack[r0 + c0:r0 + c0 + len(cnames), hi, :] \
                            = rows
                        if crcs is not None:
                            exp[r0 + c0:r0 + c0 + len(cnames), hi] \
                                = crcs
                        for b in bad:
                            pre_bad.setdefault(r0 + c0 + b,
                                               set()).add(s)
                    continue
                subv = getattr(st, "readv_submit", None)
                if subv is not None:
                    for c0, rows_n in frames:
                        cnames = names[c0:c0 + rows_n]
                        waits.append(
                            (subv(cid, cnames, sl,
                                  HINFO_KEY if verify else None),
                             r0 + c0, hi, len(cnames), None))
                    continue
                out = stack[r0:r0 + nb, hi, :]
                rb = getattr(st, "read_batch", None)
                if rb is not None:
                    rb(cid, names, sl, out=out)
                else:
                    for bi, name in enumerate(names):
                        out[bi] = st.read(cid, name)
                if verify:
                    for bi, name in enumerate(names):
                        hb = st.getattr(cid, name, HINFO_KEY)
                        exp[r0 + bi, hi] = HashInfo.from_bytes(
                            hb).get_chunk_hash(0)
        for handle, r0, hi, nb, range_slot in waits:
            if range_slot is not None:
                data, crcs, bad = handle.result()
                rows = np.frombuffer(data, np.uint8)
                if rows.size != nb * rl:
                    raise ValueError(
                        f"readv_ranges: got {rows.size} bytes, "
                        f"expected {nb * rl}")
                stack[r0:r0 + nb, hi, :] = rows.reshape(nb, rl)
                if crcs is not None:
                    exp[r0:r0 + nb, hi] = crcs
                for b in bad:
                    pre_bad.setdefault(r0 + int(b),
                                       set()).add(range_slot)
                continue
            data, attrs = handle.result()
            rows = np.frombuffer(data, np.uint8)
            if rows.size != nb * sl:
                raise ValueError(
                    f"readv: got {rows.size} bytes, expected {nb * sl}")
            stack[r0:r0 + nb, hi, :] = rows.reshape(nb, sl)
            if attrs is not None:
                for bi, hb in enumerate(attrs):
                    exp[r0 + bi, hi] = HashInfo.from_bytes(
                        hb).get_chunk_hash(0)
        return pre_bad

    def _locate_bad_helpers(self, plan, name: str, bi: int,
                            exp: np.ndarray) -> set[int]:
        """Fold CRC mismatched for one object: re-read its helper rows
        and checksum each to find the rotten shard(s) — the rare path
        pays the per-row pass the common path no longer does. For
        range plans `exp` holds the SHIPPED ranges' CRCs (not hinfo),
        so the re-read compares full rows against the stored hinfo
        instead — same verdict, different oracle."""
        bad: set[int] = set()
        for hi, s in enumerate(plan.helper):
            st = plan.be._store(s)
            cid = shard_cid(plan.be.pg, s)
            chunk = st.read(cid, name)
            if self._host_crc:
                from .. import native
                crc = int(native.native_crc32c(0xFFFFFFFF, chunk))
            else:
                crc = int(PGBackend._batched_crcs(chunk[None, :])[0])
            if plan.range_planes is not None:
                want = HashInfo.from_bytes(
                    st.getattr(cid, name, HINFO_KEY)).get_chunk_hash(0)
            else:
                want = int(exp[bi, hi])
            if crc != want:
                bad.add(s)
        return bad

    def _complete(self, entry) -> None:
        import jax
        sl, rl, live, handles, exp, pre_bad = entry
        B = len(live)
        proto = live[0][0]
        with span("recovery.fetch", counters=self.perf,
                  key="recover_fetch_time"):
            got = jax.device_get(handles)
        if self._host_crc:
            rebuilt = np.asarray(got[0])[:B]
            E = rebuilt.shape[1]
            from .. import native
            rcrc = native.native_crc32c_rows(
                0xFFFFFFFF, rebuilt.reshape(B * E, sl)).reshape(B, E)
            if proto.verify:
                fold = np.asarray(got[1])[:B]
                ok = (native.native_crc32c_rows(0xFFFFFFFF, fold)
                      == _expected_fold_crcs(exp, rl))
            else:
                ok = np.ones(B, dtype=bool)
        else:
            rebuilt = np.asarray(got[0])[:B]
            rcrc = np.asarray(got[1])[:B]
            ok = np.asarray(got[2])[:B]
        # rebuilt may be a read-only device_get view; the fallback and
        # the bucket slice both want a private copy
        rebuilt = np.array(rebuilt)
        rcrc = np.array(rcrc)
        bad_by_plan: dict[int, dict[str, set[int]]] = {}
        # source-flagged rot (range plans: the helper's full shard
        # failed its hinfo before slicing — the fold can't see it
        # because the range CRC covers the rotten bytes as shipped)
        for bi, bads in (pre_bad or {}).items():
            plan, name, _v = live[bi]
            plan.counters["hinfo_failures"] += len(bads)
            bad_by_plan.setdefault(id(plan), {})[name] = set(bads)
        if proto.verify and not ok.all():
            for bi in np.nonzero(~ok)[0]:
                plan, name, _v = live[bi]
                if name in bad_by_plan.get(id(plan), {}):
                    continue    # already flagged at the source
                bad = self._locate_bad_helpers(plan, name, int(bi), exp)
                if bad:
                    plan.counters["hinfo_failures"] += len(bad)
                    bad_by_plan.setdefault(id(plan), {})[name] = bad
        with span("recovery.push", counters=self.perf,
                  key="recover_writeback_time"):
            for plan, r0, names in self._segments(live):
                nb = len(names)
                seg_rebuilt = rebuilt[r0:r0 + nb]
                seg_crcs = rcrc[r0:r0 + nb]
                bad_pairs = bad_by_plan.get(id(plan), {})
                if bad_pairs:
                    plan.be._recover_fallback(
                        plan.lost, plan.survivors, bad_pairs, names,
                        seg_rebuilt, plan.counters)
                    idxs = sorted(names.index(n) for n in bad_pairs)
                    fix = plan.be._batched_hinfo_crcs(
                        seg_rebuilt[idxs].reshape(-1, sl)).reshape(
                            len(idxs), len(plan.lost))
                    seg_crcs[idxs] = fix
                # writeback-time revalidation: a name whose version
                # moved since its stage already holds fresher bytes on
                # the recovering slot — writing the stale decode would
                # resurrect them under a matching CRC
                keep = [i for i in range(nb)
                        if plan.be.object_versions.get(names[i], 0)
                        == live[r0 + i][2]
                        and names[i] in plan.be.object_sizes]
                if len(keep) != nb:
                    self.stats["skipped_stale"] += nb - len(keep)
                if keep:
                    plan.be._writeback_rebuilt(
                        plan.lost, [names[i] for i in keep],
                        seg_rebuilt[keep], seg_crcs[keep], sl,
                        plan.counters, window=self)
                plan.remaining.difference_update(names)
                plan.count()

    # -- generic path (codecs without a static decode plan) ----------------

    def _run_generic(self, plan, sl: int, names: list[str]) -> None:
        be = plan.be
        live = [n for n in names
                if be.object_sizes.get(n) is not None
                and be._shard_len(be.object_sizes[n]) == sl]
        if len(live) != len(names):
            # stale-skipped names need nothing from us (their mutation
            # already reached the repointed slot) but must still leave
            # the remaining set or the plan never settles
            self.stats["skipped_stale"] += len(names) - len(live)
            plan.remaining.difference_update(
                set(names) - set(live))
        names = live
        if not names:
            return
        self.perf.inc_many((("recover_launches", 1),
                            ("recover_host_launches", 1)))
        self.stats["batches"] += 1
        self.stats["generic_batches"] += 1
        wire = len(plan.helper) * sl * len(names)
        self.stats["helper_bytes_on_wire"] += wire
        self.perf.inc_many((("recover_wire_bytes", wire),
                            ("recover_helper_reads",
                             len(plan.helper) * len(names))))
        stacks = {s: np.stack([be._store(s).read(
            shard_cid(be.pg, s), n) for n in names])
            for s in plan.helper}
        bad_pairs: dict[str, set[int]] = {}
        if plan.verify:
            for s in plan.helper:
                crcs_s = be._batched_hinfo_crcs(stacks[s])
                for bi, name in enumerate(names):
                    hb = be._store(s).getattr(
                        shard_cid(be.pg, s), name, HINFO_KEY)
                    if HashInfo.from_bytes(hb).get_chunk_hash(0) \
                            != int(crcs_s[bi]):
                        plan.counters["hinfo_failures"] += 1
                        bad_pairs.setdefault(name, set()).add(s)
        rec = be.coder.decode_chunks(plan.lost, stacks)
        rebuilt_all = np.stack(
            [np.asarray(rec[s]) for s in plan.lost], axis=1)
        if bad_pairs:
            be._recover_fallback(plan.lost, plan.survivors, bad_pairs,
                                 names, rebuilt_all, plan.counters)
        crcs = be._batched_hinfo_crcs(
            rebuilt_all.reshape(-1, sl)).reshape(len(names),
                                                 len(plan.lost))
        be._writeback_rebuilt(plan.lost, names, rebuilt_all, crcs, sl,
                              plan.counters, window=self)
        plan.remaining.difference_update(names)
        plan.count()
