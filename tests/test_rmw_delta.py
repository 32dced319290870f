"""Partial-stripe write fast path (r16): GF parity-delta RMW, the
per-PG stripe journal, and append streams.

Contracts under test:
  * BIT-EXACTNESS — parity after `apply_delta` (the xor store op fed
    by the fused delta encode) is bit-identical to a full re-encode
    oracle of the final logical bytes, across RS/LRC/Clay geometries
    and both integrity modes (native host crc32c and the device
    launch), including the incremental hinfo CRCs (CRC32C
    GF(2)-linearity — no full-shard re-read ever happens);
  * REFUSAL — a degraded stripe refuses the delta path and ladders to
    the full-stripe RMW (a delta against a reconstructed pre-image
    would fold garbage into parity);
  * CRASH CONSISTENCY — SIGKILL at every stripe-journal phase
    boundary recovers (TinStore remount + `stripe_journal_replay`) to
    a state bit-exact with either the old or the new stripe, never a
    torn mix, fsck-clean;
  * APPEND — tail appends into stripe padding skip the read phase
    entirely and never re-encode previously appended bytes.
"""

import os

import numpy as np
import pytest

from ceph_tpu.ec.registry import factory
from ceph_tpu.osd import ecbackend as ecb
from ceph_tpu.osd.ecbackend import ECBackend, ShardSet, shard_cid
from ceph_tpu.osd.pgbackend import HINFO_KEY
from ceph_tpu.osd.stripe import HashInfo


def _integrity_modes(tier1_device: bool = True):
    """Both integrity modes when the native host path is built. The
    device mode duplicates ride the nightly (-m slow) except where
    `tier1_device` keeps one tier-1 representative — the 870 s tier-1
    budget is nearly full and the device path is one code path, not
    one per geometry."""
    from ceph_tpu.osd.ecbackend import _host_crc_available
    if not _host_crc_available():
        return ["device"]
    dev = pytest.param("device", marks=()) if tier1_device \
        else pytest.param("device", marks=pytest.mark.slow)
    return ["host", dev]


@pytest.fixture
def integrity(request, monkeypatch):
    """Force the RMW integrity mode: 'device' pins every CRC and
    delta encode onto the batched launches even when the native host
    path is built."""
    if request.param == "device":
        monkeypatch.setattr(ecb, "_host_crc_available", lambda: False)
    return request.param


GEOMETRIES = [
    ("plugin=tpu_rs k=4 m=2", 256),
    pytest.param("plugin=tpu_rs k=3 m=3 technique=cauchy_good", 256,
                 marks=pytest.mark.slow),
    ("plugin=lrc k=4 m=2 l=3", 256),
    ("plugin=clay k=4 m=2", 512),
    pytest.param("plugin=clay k=4 m=2 d=5 impl=ref", None,
                 marks=pytest.mark.slow),
]


def _make(profile, chunk_size):
    coder = factory(profile)
    n = coder.get_chunk_count()
    cluster = ShardSet()
    be = ECBackend(profile, "1.0", list(range(n)), cluster,
                   chunk_size=chunk_size)
    return be, cluster


def _assert_stores_match_oracle(be, name, logical):
    """Every live shard's bytes AND hinfo CRC must equal a from-
    scratch re-encode of the final logical content."""
    sl = be._shard_len(len(logical))
    dshards = be.sinfo.object_to_shards(
        np.asarray(logical, np.uint8)[None, :])
    parity = np.asarray(be.coder.encode_chunks(dshards))
    full = be._slots_from_dense(
        np.concatenate([dshards, parity], axis=1))[0]       # (n, sl)
    crcs = be._batched_hinfo_crcs(full)
    for s in range(be.n):
        st = be._store(s)
        cid = shard_cid(be.pg, s)
        np.testing.assert_array_equal(
            st.read(cid, name), full[s],
            err_msg=f"shard {s} bytes diverge from re-encode oracle")
        hinfo = HashInfo.from_bytes(st.getattr(cid, name, HINFO_KEY))
        assert hinfo.total_chunk_size == sl, f"shard {s} hinfo len"
        assert hinfo.get_chunk_hash(0) == int(crcs[s]), \
            f"shard {s}: incremental hinfo CRC != recomputed CRC"


class TestDeltaBitExact:
    @pytest.mark.parametrize("integrity",
                             _integrity_modes(tier1_device=False),
                             indirect=True)
    @pytest.mark.parametrize("profile,chunk", GEOMETRIES)
    def test_parity_after_delta_matches_reencode_oracle(
            self, profile, chunk, integrity):
        be, _ = _make(profile, chunk)
        rng = np.random.default_rng(42)
        size = be.sinfo.stripe_width * 2 + 123
        base = rng.integers(0, 256, size, np.uint8)
        be.write_objects({"o": base})
        shadow = base.copy()
        # several partial overwrites: single-column, cross-column,
        # second-stripe, and an in-padding extension
        cs = be.sinfo.chunk_size
        for off, ln in [(10, 50), (cs - 7, 30),
                        (be.sinfo.stripe_width + 5, 2 * cs - 9),
                        (size - 3, 40)]:
            patch = rng.integers(0, 256, ln, np.uint8)
            be.write_at("o", off, patch)
            if off + ln > len(shadow):
                grown = np.zeros(off + ln, np.uint8)
                grown[:len(shadow)] = shadow
                shadow = grown
            shadow[off:off + ln] = patch
            np.testing.assert_array_equal(be.read_object("o"), shadow)
        d = be.perf.dump()
        assert d["rmw_ops"] >= 4, "writes did not ride the delta path"
        assert d["rmw_full_fallbacks"] == 0
        _assert_stores_match_oracle(be, "o", shadow)
        assert be.deep_scrub()["inconsistent"] == []

    @pytest.mark.parametrize("integrity", _integrity_modes(),
                             indirect=True)
    def test_only_touched_plus_parity_shards_move(self, integrity):
        """The wire contract: a single-column overwrite transacts on
        exactly 1 data + m parity shards — untouched data shards see
        no store transaction at all."""
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(7)
        base = rng.integers(0, 256, 3000, np.uint8)
        be.write_objects({"o": base})
        before = {s: be._store(s).committed_txns for s in range(be.n)}
        patch = rng.integers(0, 256, 64, np.uint8)
        be.write_at("o", 300, patch)    # column 1, stripe 0
        touched = {s for s in range(be.n)
                   if be._store(s).committed_txns != before[s]}
        parity_slots = {be.chunk_mapping[be.k + j]
                        for j in range(be.m)}
        assert touched == {be.data_slots[1]} | parity_slots
        d = be.perf.dump()
        assert d["rmw_shard_ios"] == 1 + be.m
        assert d["rmw_ops"] == 1

    def test_delta_program_key_shared_across_instances(self):
        """The process-wide program contract: two coders with one
        geometry expose EQUAL delta keys (the r10 sharing rule — one
        compiled program per process, not per PG per daemon); a
        different geometry does not."""
        a = factory("plugin=tpu_rs k=4 m=2")
        b = factory("plugin=tpu_rs k=4 m=2")
        c = factory("plugin=tpu_rs k=4 m=2 technique=cauchy_good")
        assert a.delta_program_key((1,)) == b.delta_program_key((1,))
        assert a.delta_program_key((1,)) != c.delta_program_key((1,))
        # vector codes have no static form; the generic path serves
        clay = factory("plugin=clay k=4 m=2 d=5 impl=ref")
        assert clay.delta_program_key((1,)) is None


class TestDeltaRefusal:
    def test_degraded_stripe_refuses_and_ladders_to_full(self):
        be, cluster = _make("plugin=tpu_rs k=4 m=2",
                            256)
        rng = np.random.default_rng(9)
        base = rng.integers(0, 256, 3000, np.uint8)
        be.write_objects({"o": base})
        dead_osd = be.acting[1]
        cluster.stores.pop(dead_osd)
        patch = rng.integers(0, 256, 64, np.uint8)
        be.write_at("o", 10, patch, dead_osds={dead_osd})
        want = base.copy()
        want[10:74] = patch
        np.testing.assert_array_equal(
            be.read_object("o", dead_osds={dead_osd}), want)
        d = be.perf.dump()
        assert d["rmw_full_fallbacks"] >= 1
        assert d["rmw_ops"] == 0, \
            "a degraded stripe must never take the delta path"

    def test_stale_shard_refuses_delta(self):
        """A revived-but-behind shard (cursor below the object's
        version) is as unsafe a delta base as a dead one."""
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(10)
        be.write_objects({"o": rng.integers(0, 256, 2000, np.uint8)})
        be.shard_applied[2] = 0          # simulate a lagging shard
        be.write_at("o", 5, rng.integers(0, 256, 40, np.uint8))
        d = be.perf.dump()
        assert d["rmw_ops"] == 0 and d["rmw_full_fallbacks"] >= 1

    def test_overlapping_writes_in_one_wave_refuse(self):
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(11)
        base = rng.integers(0, 256, 2000, np.uint8)
        be.write_objects({"o": base})
        a = rng.integers(0, 256, 50, np.uint8)
        b = rng.integers(0, 256, 50, np.uint8)
        be.write_ranges([("o", 100, a), ("o", 120, b)])
        want = base.copy()
        want[100:150] = a
        want[120:170] = b                # later op wins the overlap
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.perf.dump()["rmw_ops"] == 0

    def test_clay_length_change_refuses(self):
        """Vector codes couple bytes across the chunk: an extension
        that changes shard length must re-encode, not delta."""
        be, _ = _make("plugin=clay k=2 m=2 impl=ref", 512)
        rng = np.random.default_rng(12)
        sw = be.sinfo.stripe_width
        base = rng.integers(0, 256, sw, np.uint8)
        be.write_objects({"o": base})
        tail = rng.integers(0, 256, 300, np.uint8)
        be.write_at("o", sw, tail)       # grows the shard
        want = np.concatenate([base, tail])
        np.testing.assert_array_equal(be.read_object("o"), want)
        assert be.perf.dump()["rmw_ops"] == 0
        assert be.deep_scrub()["inconsistent"] == []


class TestAppendStreams:
    @pytest.mark.parametrize("integrity",
                             _integrity_modes(tier1_device=False),
                             indirect=True)
    def test_appends_skip_preread_and_reencode(self, integrity):
        """The append-optimized layout: successive tail appends into
        the padded stripe read NOTHING (the pre-image is zeros by the
        layout rule) and never re-encode previously appended bytes —
        no full-stripe encode launches after the create."""
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(13)
        first = rng.integers(0, 256, 100, np.uint8)
        be.write_objects({"log": first})
        d0 = be.perf.dump()
        shadow = first
        for _ in range(6):
            chunk = rng.integers(0, 256,
                                 int(rng.integers(30, 200)), np.uint8)
            be.append_objects({"log": chunk})
            shadow = np.concatenate([shadow, chunk])
        d1 = be.perf.dump()
        assert d1["rmw_append_fast"] - d0["rmw_append_fast"] == 6
        assert d1["rmw_preread_bytes"] == d0["rmw_preread_bytes"], \
            "appends into padding must not read a pre-image"
        # no full-stripe encode after the create: the tail stripe is
        # never re-encoded, only delta-folded
        for key in ("fused_write_launches", "host_encode_launches",
                    "encode_launches", "write_wire_bytes"):
            assert d1[key] == d0[key], key
        np.testing.assert_array_equal(be.read_object("log"), shadow)
        _assert_stores_match_oracle(be, "log", shadow)
        assert be.deep_scrub()["inconsistent"] == []


class _SimulatedKill(Exception):
    pass


def _tin_cluster(root):
    from ceph_tpu.osd.tinstore import TinStore
    return ShardSet(store_factory=lambda osd: TinStore(
        os.path.join(root, f"osd.{osd}")))


def _rebuild(cluster, meta_src):
    """A post-crash primary: fresh backend view over the remounted
    stores, carrying the persisted-metadata analog (sizes/versions/
    log/cursors survive on the wire tier's meta plane)."""
    be2 = ECBackend("plugin=tpu_rs k=4 m=2", "1.0",
                    list(range(6)), cluster, chunk_size=256,
                    ensure_collections=False)
    be2.object_sizes = dict(meta_src.object_sizes)
    be2.object_versions = dict(meta_src.object_versions)
    be2.pg_log = meta_src.pg_log
    be2.shard_applied = list(meta_src.shard_applied)
    return be2


PHASES = ["before_prepare", "mid_prepare", "after_prepare",
          "mid_apply", "after_apply"]


class TestStripeJournalCrashMatrix:
    @pytest.mark.parametrize("phase", PHASES)
    def test_sigkill_at_phase_boundary_never_tears(self, phase,
                                                   tmp_path):
        """Kill the whole store set at each journal phase boundary;
        after remount + replay the stripe is bit-exact with either
        the OLD or the NEW content (prepare incomplete -> old;
        prepare complete -> new), hinfo verifies, deep scrub is
        clean, and offline fsck finds nothing."""
        from ceph_tpu.osd.tinstore import TinStore
        root = str(tmp_path)
        cluster = _tin_cluster(root)
        be = ECBackend("plugin=tpu_rs k=4 m=2", "1.0",
                       list(range(6)), cluster, chunk_size=256)
        rng = np.random.default_rng(21)
        base = rng.integers(0, 256, 3000, np.uint8)
        be.write_objects({"o": base})
        patch = rng.integers(0, 256, 100, np.uint8)
        new = base.copy()
        new[500:600] = patch

        def hook(p):
            if p == phase:
                for st in cluster.stores.values():
                    st.crash()           # SIGKILL semantics: RAM gone
                raise _SimulatedKill(p)
        be._rmw_crash_hook = hook
        with pytest.raises(_SimulatedKill):
            be.write_at("o", 500, patch)
        for st in cluster.stores.values():
            st.remount()
        be2 = _rebuild(cluster, be)
        rep = be2.stripe_journal_replay()
        got = be2.read_object("o")
        if np.array_equal(got, new):
            state = "new"
        elif np.array_equal(got, base):
            state = "old"
        else:
            state = "torn"
        assert state != "torn", f"phase {phase}: torn stripe"
        # prepare-incomplete phases MUST resolve old; post-prepare
        # phases MUST roll forward to new
        want = {"before_prepare": "old", "mid_prepare": "old",
                "after_prepare": "new", "mid_apply": "new",
                "after_apply": "new"}[phase]
        assert state == want, (phase, state, rep)
        oracle = new if state == "new" else base
        _assert_stores_match_oracle(be2, "o", oracle)
        assert be2.deep_scrub()["inconsistent"] == []
        # replay is idempotent: a second crash-during-replay rerun
        # must be a no-op
        rep2 = be2.stripe_journal_replay()
        assert rep2["entries"] == 0
        np.testing.assert_array_equal(be2.read_object("o"), oracle)
        for osd in range(6):
            path = os.path.join(root, f"osd.{osd}")
            fr = TinStore.fsck(path)
            assert not (fr["errors"] or fr["extent_errors"]
                        or fr["bad_objects"]), (osd, fr)

    @pytest.mark.parametrize("phase", PHASES)
    def test_sigkill_with_the_metadata_riding_never_tears_nor_outranks(
            self, phase, tmp_path):
        """The same matrix with write_objects' `shard_txn_extra` passed
        (PR 32): the wave is logged before the rounds and a record of
        the PG's metadata rides every shard's apply transaction. The
        post-crash primary knows what the freshest record on the
        remounted stores says, no more: the stripe still resolves old
        or new by the same table, and no shard's record names a
        version its bytes do not hold. At `mid_apply` shard 0, which
        moves no byte, holds the new version's record while all four
        participants still hold their intents: the replay has to roll
        forward under metadata that already names the write."""
        import json
        from ceph_tpu.osd.pglog import PGLog
        root = str(tmp_path)
        cluster = _tin_cluster(root)
        be = ECBackend("plugin=tpu_rs k=4 m=2", "1.0",
                       list(range(6)), cluster, chunk_size=256)

        def riding(names):
            rec = json.dumps({
                "sizes": be.object_sizes, "versions": be.object_versions,
                "applied": be.shard_applied,
                "log": list(be.pg_log._entries)}).encode()
            return lambda shard, t: t.omap_set(
                shard_cid(be.pg, shard), "__pg_meta__", {b"rec": rec})
        rng = np.random.default_rng(32)
        base = rng.integers(0, 256, 3000, np.uint8)
        be.write_objects({"o": base}, shard_txn_extra=riding)
        patch = rng.integers(0, 256, 100, np.uint8)
        new = base.copy()
        new[500:600] = patch

        def hook(p):
            if p == phase:
                for st in cluster.stores.values():
                    st.crash()
                raise _SimulatedKill(p)
        be._rmw_crash_hook = hook
        with pytest.raises(_SimulatedKill):
            be.write_at("o", 500, patch, shard_txn_extra=riding)
        for st in cluster.stores.values():
            st.remount()
        recs = [json.loads(dict(cluster.osd(s).omap_iter(
            shard_cid("1.0", s), "__pg_meta__"))[b"rec"])
            for s in range(6)]
        heads = [rec["log"][-1][0] for rec in recs]
        best = recs[int(np.argmax(heads))]
        be2 = ECBackend("plugin=tpu_rs k=4 m=2", "1.0",
                        list(range(6)), cluster, chunk_size=256,
                        ensure_collections=False)
        be2.object_sizes = dict(best["sizes"])
        be2.object_versions = dict(best["versions"])
        be2.shard_applied = list(best["applied"])
        be2.pg_log = PGLog()
        for v, name in best["log"]:
            be2.pg_log.append_entry(v, name)
        rep = be2.stripe_journal_replay()
        got = be2.read_object("o")
        state = "new" if np.array_equal(got, new) else \
            "old" if np.array_equal(got, base) else "torn"
        want = {"before_prepare": "old", "mid_prepare": "old",
                "after_prepare": "new", "mid_apply": "new",
                "after_apply": "new"}[phase]
        assert state == want, (phase, state, rep, heads)
        # version 1 is the full write, 2 the overwrite
        assert max(heads) <= (2 if state == "new" else 1), heads
        assert heads == {"mid_apply": [2, 1, 1, 1, 1, 1],
                         "after_apply": [2] * 6}.get(phase, [1] * 6)
        _assert_stores_match_oracle(be2, "o", new if state == "new"
                                    else base)
        assert be2.deep_scrub()["inconsistent"] == []
        assert be2.stripe_journal_replay()["entries"] == 0

    @pytest.mark.parametrize("lost", ["non-participant", "participant"])
    def test_a_shard_that_does_not_acknowledge_the_apply_round(self,
                                                               lost):
        """With the metadata riding, the apply round reaches every
        shard. One that moves no byte and does not acknowledge is
        handed back to the caller (the daemon suspects it, as its
        metadata persist does) and the write stands; a participant's
        failure raises as it always did, the wave's log entries stay
        (the retry logs past them) and the sizes are the stores'."""
        be, cluster = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(33)
        base = rng.integers(0, 256, 3000, np.uint8)
        be.write_objects({"o": base})
        slot = 0 if lost == "non-participant" else 4   # 1, 2, 4, 5 move
        store = cluster.osd(slot)
        keep, calls = store.queue_transaction, []

        def flaky(txn, *a, **kw):
            calls.append(txn)
            if len(calls) == (1 if slot == 0 else 2):  # its apply txn
                raise ConnectionError("gone")
            return keep(txn, *a, **kw)
        store.queue_transaction = flaky
        records = []

        def riding(names):
            records.append(list(names))
            return lambda shard, t: t.omap_set(
                shard_cid(be.pg, shard), "__pg_meta__", {b"rec": b"r"})
        patch = rng.integers(0, 256, 100, np.uint8)
        if lost == "non-participant":
            assert be.write_at("o", 500, patch,
                               shard_txn_extra=riding) == [0]
            new = base.copy()
            new[500:600] = patch
            np.testing.assert_array_equal(be.read_object("o"), new)
            # columns 1 and 2 and the two parity slots moved bytes
            assert int(be.perf.get("rmw_shard_ios")) == 4
        else:
            with pytest.raises(ConnectionError):
                be.append_objects({"o": patch}, shard_txn_extra=riding)
            assert be.object_sizes["o"] == 3000
            assert be.pg_log.head == be.object_versions["o"] == 2
            # the retry, degraded: the full path, one version on
            be.append_objects({"o": patch}, dead_osds={4},
                              shard_txn_extra=riding)
            assert be.pg_log.head == be.object_versions["o"] == 3
            np.testing.assert_array_equal(
                be.read_object("o", dead_osds={4}),
                np.concatenate([base, patch]))
            # whatever the failed wave left in the journal is inert
            assert be.stripe_journal_replay(dead_osds={4})["forward"] == 0
            np.testing.assert_array_equal(
                be.read_object("o", dead_osds={4}),
                np.concatenate([base, patch]))
        assert records == [["o"]]

    def test_replay_seq_reanchors_past_crash(self, tmp_path):
        """New RMWs after a replay must not reuse journal sequence
        numbers an old watermark already covers (a reused seq would
        fake the roll-forward evidence)."""
        cluster = _tin_cluster(str(tmp_path))
        be = ECBackend("plugin=tpu_rs k=4 m=2", "1.0",
                       list(range(6)), cluster, chunk_size=256)
        rng = np.random.default_rng(22)
        base = rng.integers(0, 256, 2000, np.uint8)
        be.write_objects({"o": base})
        be.write_at("o", 10, rng.integers(0, 256, 40, np.uint8))
        be.write_at("o", 90, rng.integers(0, 256, 40, np.uint8))
        high = be._rmw_seq
        be2 = _rebuild(cluster, be)
        be2.stripe_journal_replay()
        assert be2._rmw_seq >= high
        patch = rng.integers(0, 256, 40, np.uint8)
        be2.write_at("o", 200, patch)    # must journal cleanly
        assert be2.deep_scrub()["inconsistent"] == []


class TestPrepareFetchCoalescing:
    """r17 follow-up: the delta prepare's 1+m tiny per-shard getattrs
    and per-span pre-reads coalesce into ONE combined fetch wave per
    delta group — one frame per participant shard, however many jobs
    and spans the group carries."""

    def test_one_wave_one_frame_per_participant(self):
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(31)
        base = rng.integers(0, 256, 3000, np.uint8)
        be.write_objects({"a": base, "b": base[::-1].copy()})
        w0, f0 = (be.perf.get("rmw_fetch_waves"),
                  be.perf.get("rmw_fetch_frames"))
        # two jobs, same (touched, window) shape -> ONE group, ONE
        # wave; participants = 1 data + m parity = 3 shards
        pa = rng.integers(0, 256, 40, np.uint8)
        pb = rng.integers(0, 256, 40, np.uint8)
        be.write_ranges([("a", 10, pa), ("b", 10, pb)])
        assert be.perf.get("rmw_fetch_waves") - w0 == 1
        assert be.perf.get("rmw_fetch_frames") - f0 == 1 + be.m
        want = base.copy()
        want[10:50] = pa
        _assert_stores_match_oracle(be, "a", want)

    def test_growth_wave_touches_every_shard_once(self):
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(32)
        base = rng.integers(0, 256, 900, np.uint8)
        be.write_objects({"g": base})
        f0 = be.perf.get("rmw_fetch_frames")
        # growth (nsl != osl: the append lands in the NEXT stripe, so
        # every shard zero-extends): all n participate, one frame each
        be.write_at("g", 1100, rng.integers(0, 256, 30, np.uint8))
        assert be.perf.get("rmw_fetch_frames") - f0 == be.n

    def test_wire_tier_prefetch_round_trips(self):
        """On the wire tier the wave really is pipelined RemoteStore
        frames: rmw_fetch store ops serve it, and the overwrite's
        bytes land bit-exact."""
        from ceph_tpu.osd.standalone import StandaloneCluster
        c = StandaloneCluster(n_osds=5,
                              profile="plugin=tpu_rs k=2 m=1",
                              pg_num=2)
        try:
            cl = c.client()
            rng = np.random.default_rng(33)
            base = rng.integers(0, 256, 1500, np.uint8).tobytes()
            cl.write({"w": base})
            def waves():
                return sum(d.ec_perf.get("rmw_fetch_waves")
                           for d in c.osds.values()
                           if not d._stop.is_set())
            w0 = waves()
            patch = rng.integers(0, 256, 64, np.uint8).tobytes()
            cl.write_at("w", 100, patch)
            assert waves() > w0
            want = bytearray(base)
            want[100:164] = patch
            assert cl.read("w") == bytes(want)
        finally:
            c.shutdown()


class TestJournalAwareDeepScrub:
    """r17 follow-up: deep scrub audits pending __stripe_journal__
    intents (seq/version/geometry consistency against the applied
    watermark) instead of skipping the collection."""

    def test_clean_pg_reports_empty_journal_blocks(self):
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(41)
        be.write_objects({"o": rng.integers(0, 256, 2000, np.uint8)})
        be.write_at("o", 10, rng.integers(0, 256, 40, np.uint8))
        rep = be.deep_scrub()
        assert rep["inconsistent"] == []
        assert rep["journal_bad"] == []
        assert rep["journal_pending"] == 0     # applied + dropped

    def test_corrupt_intent_detected(self):
        from ceph_tpu.osd.memstore import Transaction
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(42)
        be.write_objects({"o": rng.integers(0, 256, 2000, np.uint8)})
        s = 0
        cid = shard_cid(be.pg, s)
        # (1) garbage bytes under a journal key
        be._store(s).queue_transaction(Transaction().omap_set(
            cid, be.JOURNAL_OBJ, {be._jkey(99): b"\x07garbage"}))
        rep = be.deep_scrub()
        assert any("undecodable" in why for sl, why in
                   rep["journal_bad"] if sl == s), rep
        assert rep["inconsistent"] == []       # journal findings stay
        #                                        out of auto-repair's
        #                                        rebuild list
        # (2) a decodable intent whose seq sits below the watermark
        be._store(s).queue_transaction(Transaction().omap_set(
            cid, be.JOURNAL_OBJ,
            {be._J_APPLIED: __import__("struct").pack("<Q", 50),
             be._jkey(7): be._encode_jentry(
                 7, "o", s, [s], 2000, 500, 500, 0, b"", 0, 1)}))
        rep2 = be.deep_scrub()
        assert any("watermark" in why for sl, why in
                   rep2["journal_bad"] if sl == s), rep2
        # (3) a geometry overrun: delta runs past the shard length
        be._store(s).queue_transaction(Transaction().omap_set(
            cid, be.JOURNAL_OBJ,
            {be._jkey(60): be._encode_jentry(
                60, "o", s, [s], 2000, 500, 500, 400,
                b"\x00" * 200, 0, 999)}))
        rep3 = be.deep_scrub()
        assert any("overruns" in why for sl, why in
                   rep3["journal_bad"] if sl == s), rep3

    def test_pending_intent_counts_not_flags(self):
        """A legitimate in-flight intent (prepare done, apply not) is
        journal_pending — crash-recovery state, never 'bad'."""
        be, _ = _make("plugin=tpu_rs k=4 m=2", 256)
        rng = np.random.default_rng(43)
        be.write_objects({"o": rng.integers(0, 256, 2000, np.uint8)})

        class _Stop(Exception):
            pass

        def hook(p):
            if p == "after_prepare":
                raise _Stop()
        be._rmw_crash_hook = hook
        with pytest.raises(_Stop):
            be.write_at("o", 10, rng.integers(0, 256, 40, np.uint8))
        be._rmw_crash_hook = None
        rep = be.deep_scrub()
        assert rep["journal_bad"] == []
        assert rep["journal_pending"] > 0
