"""PGBackend — the replicated-vs-erasure backend abstraction.

Rebuild of the reference's per-PG backend split (ref: src/osd/PGBackend.h
— PGBackend with submit_transaction / objects_read_async /
recover_object / be_deep_scrub, subclassed by ReplicatedBackend
(src/osd/ReplicatedBackend.{h,cc}) and ECBackend (src/osd/ECBackend.cc)).

The shared machinery both backends need — per-slot store plumbing, the
PG mutation log with per-shard applied cursors (staleness gating), the
min-size write gate — lives here; ECBackend (osd/ecbackend.py) and
ReplicatedBackend (below) differ only in how bytes are laid out across
the acting set:

* ReplicatedBackend: slot i holds a FULL copy of every object; writes
  fan the same bytes out, reads come from any caught-up live replica,
  recovery is a verified copy (push) from a surviving replica.
* ECBackend: slot i holds shard i of the stripe; writes encode, reads/
  recovery decode.

TPU-first shaping: the replicated path has no GF math, but its integrity
surface is the same batched checksum workload — full-object crc32c
digests (the role of object_info_t's data_digest) computed in one
device launch per equal-length group, both on write and on deep scrub.

Both backends expose the same surface, so SimCluster (osd/cluster.py)
drives either pool type through one code path — exactly how the
reference's PrimaryLogPG calls through the PGBackend interface without
knowing which backend it has.
"""

from __future__ import annotations

import numpy as np

from ..utils.tracing import span
from .memstore import MemStore, Transaction
from .pglog import PGLog
from .stripe import HashInfo, as_flat_u8

HINFO_KEY = "hinfo_key"  # same xattr name role as the reference


def shard_cid(pg: str, shard: int) -> str:
    """Collection name of one PG shard (role of spg_t's shard id)."""
    return f"{pg}s{shard}"


class PGBackend:
    """Common base: store plumbing + PG-log bookkeeping (ref:
    src/osd/PGBackend.h contract; log semantics ref: src/osd/PGLog.h)."""

    #: live slots a write needs before it may proceed (the pool
    #: min_size gate); subclasses set it in __init__
    min_live: int = 1

    def _init_common(self, pg: str, acting: list[int], cluster,
                     ensure_collections: bool = True) -> None:
        self.pg = pg
        self.acting = list(acting)
        self.n = len(acting)
        self.cluster = cluster
        if ensure_collections:
            # ensure_collections=False builds a READ-ONLY view (the
            # degraded-read fast path): no store mutation, and no txn
            # to an acting member that may be dead-but-not-yet-marked
            # (the collections already exist on every real member)
            for shard, osd in enumerate(self.acting):
                t = Transaction().create_collection(shard_cid(pg, shard))
                self.cluster.osd(osd).queue_transaction(t)
        self.object_sizes: dict[str, int] = {}  # authoritative size info
        # mutation log + per-shard applied cursor (ref: PGLog /
        # peering's last_update per shard): a shard that missed writes
        # replays just the delta on rejoin
        self.pg_log = PGLog()
        self.shard_applied = [0] * self.n
        self.object_versions: dict[str, int] = {}  # name -> last version

    # -- shared helpers ------------------------------------------------------

    def _store(self, shard: int) -> MemStore:
        return self.cluster.osd(self.acting[shard])

    def _live_slots(self, dead_osds: set[int] | None) -> list[int]:
        dead = dead_osds or set()
        return [s for s in range(self.n) if self.acting[s] not in dead]

    def _log_write(self, name: str, live: list[int]) -> None:
        """Append to the PG log and advance the applied cursor of every
        shard that received this write (down shards stay behind and
        replay the delta on rejoin).

        The cursor only advances CONTIGUOUSLY: a live-but-behind shard
        (revived, replay still pending) receives the new bytes but
        keeps its old cursor, else its gap would silently close and
        reads could select it as fresh for objects it missed (the
        reference keeps last_update + an explicit missing set; our
        conservative cursor re-replays a little instead)."""
        v = self.pg_log.append(name)
        self.object_versions[name] = v
        for s in live:
            if self.shard_applied[s] == v - 1:
                self.shard_applied[s] = v

    def _fresh_for(self, names: list[str], shards: list[int]) -> list[int]:
        """Shards (from `shards`) whose applied cursor covers the last
        write of every object in `names` — a shard that was down across
        a write holds STALE bytes for it and must not serve reads or
        helper gathers until it replays (ref: peering's missing-set)."""
        need = max((self.object_versions.get(n, 0) for n in names),
                   default=0)
        return [s for s in shards if self.shard_applied[s] >= need]

    def _fanout_txns(self, items, optional=()) -> list[int]:
        """Apply [(shard, Transaction)] across the acting set,
        PIPELINED where the store supports it (RemoteStore at the wire
        tier): every txn is transmitted before any ack is awaited, so
        the fan-out costs one overlapped round trip instead of
        len(items) sequential ones (the reference dispatches its
        MOSDECSubOpWrite sub-ops in parallel too). Durability point
        unchanged — this returns only after EVERY shard acked, and a
        shard failure raises exactly like the sequential loop did,
        except on a shard in `optional` (one whose transaction moves
        no object byte): those that failed are returned instead.
        In-process stores (MemStore/TinStore) take the sync path."""
        waits: list = []
        errs: list[tuple[int, BaseException]] = []
        for shard, t in items:
            st = self._store(shard)
            submit = getattr(st, "queue_transaction_async", None)
            try:
                if submit is not None:
                    waits.append((shard, submit(t)))
                else:
                    st.queue_transaction(t)
            except (ConnectionError, OSError) as e:
                errs.append((shard, e))
        for shard, h in waits:
            try:
                h.result()
            except (ConnectionError, OSError) as e:
                errs.append((shard, e))
        for shard, e in errs:
            if shard not in optional:
                raise e
        return [shard for shard, _e in errs]

    def _check_min_size(self, live: list[int]) -> None:
        """Writes need >= min_live receiving slots or the PG goes
        inactive and blocks I/O (the pool min_size gate). Counts
        DISTINCT OSDs, not slots: mid-backfill an OSD can temporarily
        hold two slots, and two copies on one disk are one failure
        domain, not two."""
        distinct = len({self.acting[s] for s in live})
        if distinct < self.min_live:
            raise ValueError(
                f"PG below min_size: {distinct} live shards < "
                f"min_size={self.min_live}; write refused (pg inactive)")

    @staticmethod
    def _batched_crcs(blocks: np.ndarray,
                      stages: str = "pgbackend.crcs") -> np.ndarray:
        """One device launch for a (B, L) stack of byte rows -> (B,)
        uint32 CRCs (raw register, seed -1 — the HashInfo convention).
        The row count is bucketed to a power of two: per-PG batches
        vary freely and each distinct B would otherwise compile its
        own program. `stages` names the caller's path: its `.stage`
        (to the device, padded), `.launch` (dispatch returns) and
        `.fetch` (the wait for the device) spans."""
        from ..csum.kernels import crc32c_blocks
        from ..ops.rs_kernels import pad_to_bucket
        with span(stages + ".stage"):
            padded, B = pad_to_bucket(np.asarray(blocks, dtype=np.uint8))
        with span(stages + ".launch"):
            crcs = crc32c_blocks(padded, init=0xFFFFFFFF, xorout=0)[:B]
        with span(stages + ".fetch"):
            return np.asarray(crcs)

    def _remove_strays(self, dead: set[int]) -> int:
        """Remove per-slot leftover objects the PG's metadata no
        longer knows: divergent dead-interval writes kept by a member
        that rejoined as a NON-primary (only the restoring primary
        runs the divergent-log rewind), or delete leftovers a trimmed
        log can never replay. Ref: PrimaryLogPG's stray/unexpected
        object handling on scrub repair."""
        removed = 0
        for s in range(self.n):
            if self.acting[s] in dead:
                continue
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            strays = [n for n in st.list_objects(cid)
                      if not n.startswith("__")
                      and n not in self.object_sizes]
            if not strays:
                continue
            t = Transaction()   # one combined txn (one wire frame)
            for name in strays:
                t.remove(cid, name)
            st.queue_transaction(t)
            removed += len(strays)
        return removed

    # -- contract (ref: PGBackend.h pure virtuals) ---------------------------

    def write_objects(self, objects, dead_osds=None) -> None:
        raise NotImplementedError

    def write_ranges(self, ops, dead_osds=None) -> None:
        raise NotImplementedError

    def write_at(self, name: str, offset: int, data,
                 dead_osds: set[int] | None = None, **kw):
        """One range; `kw` and the result are write_ranges' (an
        ECBackend's takes write_objects' `shard_txn_extra`)."""
        return self.write_ranges([(name, offset, data)], dead_osds, **kw)

    def append_objects(self, appends, dead_osds=None, **kw):
        """Append streams: each name's bytes land at its current tail
        (creating absent objects at offset 0). On an EC pool a tail
        landing inside the padded stripe is the RMW append fast path:
        the pre-image is zeros by the layout rule, so no read phase
        and only the tail data shard + m parity shards move. `kw` and
        the result are write_ranges'."""
        return self.write_ranges(
            [(name, self.object_sizes.get(name, 0), data)
             for name, data in appends.items()], dead_osds, **kw)

    def read_objects(self, names, dead_osds=None) -> dict[str, np.ndarray]:
        raise NotImplementedError

    def read_object(self, name: str,
                    dead_osds: set[int] | None = None) -> np.ndarray:
        return self.read_objects([name], dead_osds)[name]

    def remove_objects(self, names, dead_osds=None) -> None:
        """Delete objects from every live slot. A remove is a LOGGED
        mutation (ref: pg_log_entry_t DELETE): a shard that was down
        across it replays the delete on rejoin instead of resurrecting
        a stale copy."""
        live = self._live_slots(dead_osds)
        self._check_min_size(live)
        names = list(names)
        # validate the whole batch before mutating anything (the
        # recover_shards convention): a bad name mid-batch must not
        # leave a half-applied, half-logged delete
        for name in names:
            if name not in self.object_sizes:
                raise KeyError(f"no object {name!r}")
        # ONE combined txn per shard for the whole batch (the window's
        # store-apply unit — the per-name loop cost B*n transactions
        # and B*n wire frames where n now suffice; ROADMAP item 2b's
        # `store.apply` wall), fanned out pipelined
        seen: set[str] = set()
        doomed: list[str] = []
        for name in names:
            if name not in seen:
                seen.add(name)
                doomed.append(name)
        txns = []
        for s in live:
            t = Transaction()
            for name in doomed:
                t.remove(shard_cid(self.pg, s), name)
            txns.append((s, t))
        self._fanout_txns(txns)
        for name in doomed:
            del self.object_sizes[name]
            self._log_write(name, live)

    def stat_object(self, name: str) -> int:
        """Logical object size (the rados_stat role)."""
        return self.object_sizes[name]

    def list_pg_objects(self) -> list[str]:
        return sorted(self.object_sizes)

    def split_to(self, child: "PGBackend", names) -> int:
        """PG split, the data half (ref: src/osd/PG.cc split machinery;
        on-disk it is a LOCAL collection split — no bytes cross OSDs):
        move `names`' shards store-locally from this PG's collections
        into `child`'s, carrying the hinfo xattrs, and log the transfer
        on both sides (child: create entries; parent: delete entries)
        so later delta-rejoins replay exactly. The child must start on
        the parent's acting set — relocation to its own CRUSH targets
        is the cluster layer's pg_temp-protected backfill, afterwards.

        Caller contract: every shard caught up (a clean PG) — enforced
        here because a behind shard would silently split a stale copy.
        """
        if child.acting != self.acting:
            raise ValueError("split child must start on the parent's "
                             "acting set")
        for s in range(self.n):
            if self.shard_applied[s] < self.pg_log.head:
                raise ValueError(
                    f"shard {s} is behind (applied "
                    f"{self.shard_applied[s]} < head {self.pg_log.head}); "
                    f"split requires a clean PG")
        moved = [n for n in names if n in self.object_sizes]
        for s in range(self.n):
            st = self._store(s)
            src = shard_cid(self.pg, s)
            dst = shard_cid(child.pg, s)
            t = Transaction()
            for name in moved:
                if not st.exists(src, name):
                    # clean PG + absent store entry = the zero-length
                    # convention; mirror it on the child WITH an empty
                    # hinfo — deep scrub reads the xattr unguarded
                    t.touch(dst, name).truncate(dst, name, 0)
                    t.setattr(dst, name, HINFO_KEY,
                              HashInfo(1, 0, [0xFFFFFFFF]).to_bytes())
                    continue
                data = st.read(src, name)
                t.write(dst, name, 0, data).truncate(dst, name, len(data))
                try:
                    t.setattr(dst, name, HINFO_KEY,
                              st.getattr(src, name, HINFO_KEY))
                except KeyError:
                    pass    # zero-length objects may carry no hinfo
                t.remove(src, name)
            st.queue_transaction(t)
        live = list(range(self.n))
        for name in moved:
            child.object_sizes[name] = self.object_sizes.pop(name)
            child._log_write(name, live)
            self.object_versions.pop(name, None)
            self._log_write(name, live)   # the parent-side DELETE entry
        return len(moved)

    def _replay_deletes(self, lost: list[int], names) -> list[str]:
        """Split a recovery name list: apply deletes for names the PG
        no longer knows (their last log entry was a remove) to the
        recovering slots, and return the names still to rebuild.

        Batched per slot: ONE listing + ONE combined remove txn
        instead of a per-name exists+remove pair — at the wire tier
        the per-name form cost 2B round trips per recovering slot."""
        keep = [n for n in names if n in self.object_sizes]
        dels = [n for n in names if n not in self.object_sizes]
        if dels:
            for s in lost:
                cid = shard_cid(self.pg, s)
                present = set(self._store(s).list_objects(cid))
                doomed = [n for n in dels if n in present]
                if not doomed:
                    continue
                t = Transaction()
                for name in doomed:
                    t.remove(cid, name)
                self._store(s).queue_transaction(t)
        return keep

    def recover_shards(self, lost_shards, replacement_osds=None,
                       batch: int = 128, verify_hinfo: bool = True,
                       names=None, helper_exclude=None) -> dict:
        raise NotImplementedError

    def _mark_caught_up(self, lost: list[int], full_plan: bool,
                        provided: set) -> None:
        """Advance recovered slots' applied cursors to the log head —
        but only when the recovered names cover the slot's whole
        missing set. A narrower caller-supplied subset must not mark
        objects it never touched as fresh (that would defeat
        _fresh_for's staleness gate). Shared by both backends so the
        gate can't silently diverge."""
        for s in lost:
            missing = self.pg_log.missing_since(self.shard_applied[s])
            if missing is None:           # log trimmed: backfill must
                missing = self.object_sizes   # have covered everything
            if full_plan or set(missing) <= provided:
                self.shard_applied[s] = self.pg_log.head

    def deep_scrub(self) -> dict:
        raise NotImplementedError

    # -- shallow scrub (shared) ----------------------------------------------

    def _expected_shard_len(self, object_size: int) -> int:
        """Bytes slot s should hold for an object of `object_size`
        logical bytes (replicated: the full object; EC: the shard)."""
        raise NotImplementedError

    def shallow_scrub(self, skip_slots: set[int] | None = None) -> dict:
        """Metadata-only audit — no data reads (ref: the scrubber's
        shallow pass compares object set, sizes, and attrs across
        shards; src/osd/scrubber/pg_scrubber.cc). Checks every slot
        against the authoritative object map: presence, stored length,
        hinfo attr presence + its recorded length, and flags stray
        objects the PG doesn't know about."""
        skip = skip_slots or set()
        errors: list[tuple[str, int, str]] = []  # (name, slot, what)
        checked = 0
        for s in range(self.n):
            if s in skip:
                continue
            store = self._store(s)
            cid = shard_cid(self.pg, s)
            on_disk = set(store.list_objects(cid))
            for name, osize in self.object_sizes.items():
                checked += 1
                # a shard that missed this object's last write is
                # legitimately behind, not inconsistent
                if self.shard_applied[s] < self.object_versions.get(
                        name, 0):
                    continue
                if name not in on_disk:
                    errors.append((name, s, "missing"))
                    continue
                want = self._expected_shard_len(osize)
                have = store.stat(cid, name)
                if have != want:
                    errors.append((name, s, f"size {have} != {want}"))
                try:
                    hb = store.getattr(cid, name, HINFO_KEY)
                except KeyError:
                    errors.append((name, s, "no hinfo attr"))
                    continue
                hinfo = HashInfo.from_bytes(hb)
                if hinfo.total_chunk_size != want:
                    errors.append(
                        (name, s, f"hinfo len {hinfo.total_chunk_size} "
                                  f"!= {want}"))
            for stray in on_disk - set(self.object_sizes):
                # "__"-prefixed names are PG-internal bookkeeping
                # (stripe journal, standalone __pg_meta__): never
                # client data, never stray
                if stray.startswith("__"):
                    continue
                # a behind shard may hold an object whose delete it
                # hasn't replayed yet — lag, not corruption (same
                # excuse the missing/size checks apply above)
                if self.shard_applied[s] < self.object_versions.get(
                        stray, 0):
                    continue
                errors.append((stray, s, "stray object"))
        return {"checked": checked, "errors": errors}


class ReplicatedBackend(PGBackend):
    """Full-copy replication across the acting set (ref:
    src/osd/ReplicatedBackend.{h,cc} — submit_transaction fans the same
    transaction out to every replica; recovery pushes whole objects from
    a surviving replica; be_deep_scrub compares replica digests).

    Every slot stores the complete object plus a HashInfo xattr whose
    single CRC covers the full byte stream (the data_digest role). The
    xattr layout matches ECBackend's, so SimCluster's backfill copy loop
    works unchanged for either pool type.
    """

    def __init__(self, size: int, pg: str, acting: list[int],
                 cluster=None, min_size: int | None = None,
                 ensure_collections: bool = True):
        if len(acting) != size:
            raise ValueError(f"acting set size {len(acting)} != size={size}")
        from .ecbackend import ShardSet
        self.size = size
        # the reference default: size - size/2, i.e. ceil(size/2)
        # (osd_pool_default_min_size=0 behavior) — 2 for size 3 AND 4
        self.min_live = min_size if min_size is not None \
            else size - size // 2
        if not (1 <= self.min_live <= size):
            raise ValueError(f"min_size {self.min_live} not in [1, {size}]")
        self._init_common(pg, acting, cluster or ShardSet(),
                          ensure_collections=ensure_collections)
        self.eio_stats = {"read_eio": 0, "repaired": 0}

    def _expected_shard_len(self, object_size: int) -> int:
        return object_size  # every replica holds the whole object

    # -- write path ----------------------------------------------------------

    def _put_full(self, name: str, arr: np.ndarray, crc: int,
                  live: list[int]) -> None:
        self._put_group([(name, arr, crc)], live)

    def _put_group(self, items, live: list[int]) -> None:
        """Fan a group of (name, bytes, crc) puts out as ONE combined
        transaction per replica (the window's store-apply unit;
        ROADMAP item 2b — the per-object fan-out cost B*n store
        transactions and B*n `store.apply` passes where n suffice)."""
        txns = []
        for s in live:
            cid = shard_cid(self.pg, s)
            t = Transaction()
            for name, arr, crc in items:
                hinfo = HashInfo(1, len(arr), [crc])
                t.write(cid, name, 0, arr) \
                 .truncate(cid, name, len(arr)) \
                 .setattr(cid, name, HINFO_KEY, hinfo.to_bytes())
            txns.append((s, t))
        self._fanout_txns(txns)
        for name, arr, _crc in items:
            self.object_sizes[name] = len(arr)
            self._log_write(name, live)

    def write_objects(self, objects, dead_osds=None) -> None:
        """Full-object writes: digest every equal-length group in one
        batched CRC launch, then fan identical bytes to each live
        replica (the repop fan-out, minus the network) — one combined
        transaction per replica per group."""
        live = self._live_slots(dead_osds)
        self._check_min_size(live)
        by_len: dict[int, list[tuple[str, np.ndarray]]] = {}
        for name, data in objects.items():
            arr = as_flat_u8(data)
            by_len.setdefault(len(arr), []).append((name, arr))
        for olen, group in by_len.items():
            if olen == 0:
                self._put_group([(n, a, 0xFFFFFFFF) for n, a in group],
                                live)
                continue
            crcs = self._batched_crcs(np.stack([a for _, a in group]))
            self._put_group([(n, a, int(c))
                             for (n, a), c in zip(group, crcs)], live)

    def write_ranges(self, ops, dead_osds=None) -> None:
        """Arbitrary (offset, len) overwrites. Replication needs no RMW
        of other shards — but the full-object digest does need the
        pre-image, read from any caught-up live replica."""
        dead = dead_osds or set()
        live = self._live_slots(dead)
        self._check_min_size(live)
        per_obj: dict[str, list[tuple[int, np.ndarray]]] = {}
        for name, offset, data in ops:
            if offset < 0:
                raise ValueError(f"negative offset {offset}")
            per_obj.setdefault(name, []).append((int(offset),
                                                as_flat_u8(data)))
        staged: list[tuple[str, np.ndarray]] = []
        for name, writes in per_obj.items():
            old_size = self.object_sizes.get(name, 0)
            writes = [(off, a) for off, a in writes if len(a)]
            if not writes:
                if name not in self.object_sizes:
                    self._put_full(name, np.zeros(0, np.uint8),
                                   0xFFFFFFFF, live)
                continue
            new_size = max(old_size,
                           max(off + len(a) for off, a in writes))
            buf = np.zeros(new_size, dtype=np.uint8)
            if old_size:
                src = self._fresh_for([name], live)
                if not src:
                    raise ValueError(
                        f"no caught-up live replica holds {name!r}; "
                        f"write blocked until recovery")
                buf[:old_size] = self._store(src[0]).read(
                    shard_cid(self.pg, src[0]), name)
            for off, arr in writes:
                buf[off:off + len(arr)] = arr
            staged.append((name, buf))
        # batched digest per equal new-length group, then ONE combined
        # txn per replica per group (the grouped put fan-out)
        by_len: dict[int, list[tuple[str, np.ndarray]]] = {}
        for name, buf in staged:
            by_len.setdefault(len(buf), []).append((name, buf))
        for olen, group in by_len.items():
            crcs = (self._batched_crcs(np.stack([b for _, b in group]))
                    if olen else [0xFFFFFFFF] * len(group))
            self._put_group([(n, b, int(c))
                             for (n, b), c in zip(group, crcs)], live)

    # -- read path -----------------------------------------------------------

    def read_objects(self, names, dead_osds=None,
                     verify: bool = True,
                     repair: bool = True,
                     helper_costs=None) -> dict[str, np.ndarray]:
        """Serve each object from the first caught-up live replica
        (primary-first, the reference's default read path), with
        verify-on-read: a digest mismatch fails over to the next good
        replica and repairs the rotten copy in place (the read-error
        EIO path). repair=False fails over without the writeback — the
        read-only contract of a degraded-read view served by a
        non-primary (only an activated primary may mutate shards).
        `helper_costs` (slot -> cost) reorders the candidate replicas
        cheapest-first — the replicated twin of the EC planner's
        cost-ranked helper pick."""
        alive = self._live_slots(dead_osds)
        out: dict[str, np.ndarray] = {}
        srcs_of: dict[str, list[int]] = {}
        # happy path batched per (chosen replica, size): ONE CRC launch
        # per group, matching the file's batch-per-equal-length
        # convention everywhere else
        plan: dict[tuple[int, int], list[str]] = {}
        for name in names:
            if name not in self.object_sizes:
                raise KeyError(f"no object {name!r}")
            srcs = self._fresh_for([name], alive)
            if helper_costs:
                srcs.sort(key=lambda s: (int(helper_costs.get(s, 0)),
                                         s))
            if not srcs:
                raise ValueError(f"no caught-up live replica for {name!r}")
            if not verify:
                out[name] = self._store(srcs[0]).read(
                    shard_cid(self.pg, srcs[0]), name)
                continue
            srcs_of[name] = srcs
            plan.setdefault((srcs[0], self.object_sizes[name]),
                            []).append(name)
        suspects: list[str] = []
        for (s, size), group in plan.items():
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            datas = {n: st.read(cid, n) for n in group}
            ok_len = [n for n in group if len(datas[n]) == size]
            for n in group:  # length rot can't even be stacked
                if n not in ok_len:
                    self.eio_stats["read_eio"] += 1
                    suspects.append(n)
            if not ok_len:
                continue
            crcs = (self._batched_crcs(
                np.stack([datas[n] for n in ok_len]))
                if size else [0xFFFFFFFF] * len(ok_len))
            for n, crc in zip(ok_len, crcs):
                hinfo = HashInfo.from_bytes(
                    st.getattr(cid, n, HINFO_KEY))
                if int(crc) == hinfo.get_chunk_hash(0):
                    out[n] = datas[n]
                else:
                    self.eio_stats["read_eio"] += 1
                    suspects.append(n)
        for name in suspects:  # EIO path: failover + repair
            out[name] = self._read_failover(name, srcs_of[name],
                                            {srcs_of[name][0]},
                                            repair=repair)
        return out

    def _read_failover(self, name: str, srcs: list[int],
                       bad: set[int],
                       repair: bool = True) -> np.ndarray:
        """Try the remaining fresh replicas in order; the first
        digest-valid copy wins and repairs every rotten one met
        (unless repair=False — the read-only degraded view)."""
        good = None
        for s in srcs:
            if s in bad:
                continue
            st = self._store(s)
            cid = shard_cid(self.pg, s)
            data = st.read(cid, name)
            crc = (int(self._batched_crcs(data[None, :])[0])
                   if data.size else 0xFFFFFFFF)
            hinfo = HashInfo.from_bytes(st.getattr(cid, name,
                                                   HINFO_KEY))
            if crc == hinfo.get_chunk_hash(0) \
                    and len(data) == self.object_sizes[name]:
                good = data
                break
            self.eio_stats["read_eio"] += 1
            bad.add(s)
        if good is None:
            raise ValueError(
                f"every replica of {name!r} fails its digest")
        if repair:
            for s in bad:
                self._rewrite_replica(name, s, good)
        return good

    def _rewrite_replica(self, name: str, s: int,
                         good: np.ndarray) -> None:
        crc = (int(self._batched_crcs(good[None, :])[0])
               if good.size else 0xFFFFFFFF)
        hinfo = HashInfo(1, len(good), [crc])
        t = (Transaction()
             .write(shard_cid(self.pg, s), name, 0, good)
             .truncate(shard_cid(self.pg, s), name, len(good))
             .setattr(shard_cid(self.pg, s), name,
                      HINFO_KEY, hinfo.to_bytes()))
        self._store(s).queue_transaction(t)
        self.eio_stats["repaired"] += 1

    def repair_pg(self, dead_osds: set[int] | None = None) -> dict:
        """`ceph pg repair`: deep-scrub, rewrite every inconsistent
        replica the scrub flagged from a digest-valid copy (not just
        the ones a read would stumble over). Dead slots are recovery's
        job, not repair's; replicas the verified read already fixed in
        passing are not rewritten (or counted) twice."""
        dead = dead_osds or set()
        rep = self.deep_scrub(dead_osds=dead)
        alive_set = set(self._live_slots(dead))
        by_name: dict[str, list[int]] = {}
        skipped = 0
        for name, slot in rep["inconsistent"]:
            if slot not in alive_set or name not in self.object_sizes:
                skipped += 1
                continue
            by_name.setdefault(name, []).append(slot)
        repaired = 0
        for name, slots in sorted(by_name.items()):
            good = self.read_objects([name], dead_osds,
                                     verify=True)[name]
            want_crc = (int(self._batched_crcs(good[None, :])[0])
                        if good.size else 0xFFFFFFFF)
            for s in slots:
                st = self._store(s)
                cid = shard_cid(self.pg, s)
                cur = st.read(cid, name)
                cur_crc = (int(self._batched_crcs(cur[None, :])[0])
                           if cur.size else 0xFFFFFFFF)
                if cur_crc == want_crc:
                    continue  # the verified read repaired it already
                self._rewrite_replica(name, s, good)
                repaired += 1
        return {"checked": rep["checked"], "repaired": repaired,
                "objects": len(by_name), "skipped": skipped,
                "strays_removed": self._remove_strays(dead)}

    # -- recovery ------------------------------------------------------------

    def recover_shards(self, lost_shards, replacement_osds=None,
                       batch: int = 128, verify_hinfo: bool = True,
                       names=None, helper_exclude=None,
                       helper_costs=None) -> dict:
        """Rebuild lost replicas by pushing verified copies from a
        surviving replica (ref: ReplicatedBackend::recover_object /
        prep_push). Copies are batched per equal length so the source-
        verify CRC is one device launch per group. `helper_costs`
        orders the candidate push sources cheapest-first.

        Same signature/counters as ECBackend.recover_shards so
        SimCluster's repeer/backfill/catch-up paths drive either."""
        lost = sorted(set(lost_shards))
        excluded = helper_exclude or set()
        full_plan = names is None
        names = sorted(self.object_sizes) if names is None \
            else sorted(set(names))
        provided = set(names)
        # a deletes-only replay pushes nothing and needs no source
        rebuild = [n for n in names if n in self.object_sizes]
        survivors: list[int] = []
        if rebuild:
            survivors = self._fresh_for(
                rebuild, [s for s in range(self.n)
                          if s not in lost and s not in excluded])
            if helper_costs:
                survivors.sort(
                    key=lambda s: (int(helper_costs.get(s, 0)), s))
            if not survivors:
                raise ValueError(
                    "no caught-up surviving replica to push from")
        repl = replacement_osds or {}
        for s in lost:
            new_osd = repl.get(s, self.acting[s])
            self.acting[s] = new_osd
            t = Transaction().create_collection(shard_cid(self.pg, s))
            self.cluster.osd(new_osd).queue_transaction(t)
        counters = {"objects": 0, "bytes": 0, "hinfo_failures": 0}
        # names whose last log entry was a DELETE replay as removals
        names = self._replay_deletes(lost, names)

        by_len: dict[int, list[str]] = {}
        for name in names:
            by_len.setdefault(self.object_sizes[name], []).append(name)
        for olen, group in by_len.items():
            for i in range(0, len(group), batch):
                sub = group[i:i + batch]
                self._push_batch(sub, olen, lost, survivors,
                                 verify_hinfo, counters)
        self._mark_caught_up(lost, full_plan, provided)
        return counters

    def _push_batch(self, sub: list[str], olen: int, lost: list[int],
                    survivors: list[int], verify: bool,
                    counters: dict) -> None:
        src = survivors[0]
        cid_src = shard_cid(self.pg, src)
        st = self._store(src)
        data = [st.read(cid_src, n) for n in sub]
        crcs = [0xFFFFFFFF] * len(sub)
        if olen:
            crcs = [int(c) for c in
                    self._batched_crcs(np.stack(data))]
        for ni, name in enumerate(sub):
            want = HashInfo.from_bytes(
                st.getattr(cid_src, name, HINFO_KEY)).get_chunk_hash(0)
            if verify and olen and crcs[ni] != want:
                # source copy is corrupt: try the other survivors (the
                # read-error failover the reference does on pull)
                counters["hinfo_failures"] += 1
                for alt in survivors[1:]:
                    cid_a = shard_cid(self.pg, alt)
                    cand = self._store(alt).read(cid_a, name)
                    cc = int(self._batched_crcs(cand[None, :])[0])
                    aw = HashInfo.from_bytes(self._store(alt).getattr(
                        cid_a, name, HINFO_KEY)).get_chunk_hash(0)
                    if cc == aw:
                        data[ni], crcs[ni] = cand, cc
                        break
                else:
                    raise ValueError(
                        f"all surviving replicas of {name!r} fail digest")
        # ONE combined txn per recovering replica for the whole batch
        # (was one per (object, slot)), fanned out pipelined
        txns = []
        for s in lost:
            cid = shard_cid(self.pg, s)
            t = Transaction()
            for ni, name in enumerate(sub):
                hinfo = HashInfo(1, olen, [crcs[ni]])
                t.write(cid, name, 0, data[ni]) \
                 .truncate(cid, name, olen) \
                 .setattr(cid, name, HINFO_KEY, hinfo.to_bytes())
                counters["bytes"] += olen
            txns.append((s, t))
        self._fanout_txns(txns)
        counters["objects"] += len(sub)

    # -- scrub ---------------------------------------------------------------

    def deep_scrub(self, dead_osds: set[int] | None = None) -> dict:
        """Read every LIVE replica of every object, verify its stored
        digest (batched CRC per replica), and cross-check replicas
        agree (ref: be_deep_scrub + the scrubber's authoritative-copy
        compare). Dead slots are skipped — touching their stores would
        resurrect destroyed OSD ids."""
        dead = dead_osds or set()
        bad: list[tuple[str, int]] = []
        checked = 0
        digests: dict[str, set[int]] = {}
        for s in range(self.n):
            if self.acting[s] in dead:
                continue
            store = self._store(s)
            cid = shard_cid(self.pg, s)
            # a replica that missed an object's last write is behind
            # (pending replay), not corrupt — the scrubber's "missing"
            # bucket; filter BEFORE reading so stale rows cost nothing
            # strays (objects the PG metadata doesn't know — e.g. a
            # non-primary rejoiner's divergent leftovers) may lack
            # hinfo entirely: they are repair's to REMOVE, not the
            # digest audit's to crash on
            names = [n for n in store.list_objects(cid)
                     if n in self.object_sizes
                     and self.shard_applied[s]
                     >= self.object_versions.get(n, 0)]
            by_len: dict[int, list[str]] = {}
            for n in names:
                by_len.setdefault(store.stat(cid, n), []).append(n)
            for ln, group in by_len.items():
                if ln:
                    crcs = self._batched_crcs(
                        np.stack([store.read(cid, n) for n in group]))
                else:
                    crcs = [0xFFFFFFFF] * len(group)
                for n, c in zip(group, crcs):
                    hinfo = HashInfo.from_bytes(
                        store.getattr(cid, n, HINFO_KEY))
                    checked += 1
                    if hinfo.get_chunk_hash(0) != int(c):
                        bad.append((n, s))
                    digests.setdefault(n, set()).add(int(c))
        # replicas that all self-verify but disagree with each other
        # (e.g. a stale-but-internally-consistent copy)
        split = [n for n, ds in digests.items() if len(ds) > 1]
        return {"checked": checked, "inconsistent": bad,
                "digest_mismatch": sorted(split)}
