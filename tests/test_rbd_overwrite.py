"""Small random overwrites of an RBD image on a served EC pool, at a size
a test run can hold: the deployment of bench/configs/rbd_ec_k8m3_12osd.json
(PR 31) with k=2 m=1, six OSDs, a 2-object image of 64 KiB objects, and
the stripe unit the deployment states, 4096.

Four loops of seeded 4 KiB `Client.write_at` on a TinStore cluster with
cephx, the EC backend's host shortcut for the CPU backend switched off so
that the fused delta program runs (the program a chip runs). What comes
out is held to the plain references, as the benchmark's comparison holds
the cell: every block read back equals the block device's
(bench/reference/block_image.py), every stored parity row the reference
encode of the stored data rows, every hinfo crc the reference crc of the
stored row; each write moved one data shard and the parity shard, none
fell back to the full-stripe path, none was computed on the host, and
the stripe journal is drained. With the harness's 256-byte stripe unit
the same write spans two stripes and takes the full path: why the
configuration states 4096.
"""

import os
import struct
import threading
import time

import numpy as np
import pytest

from bench.reference import block_image, crc32c, gf256

K, M = 2, 1
OBJECT, OBJECTS, BLOCK = 65536, 2, 4096
LOOPS, WRITES = 4, 12
NAMES = [f"rbd_data.10226b8b4567.{j:016x}" for j in range(OBJECTS)]
COUNTERS = ("rmw_ops", "rmw_shard_ios", "rmw_full_fallbacks",
            "rmw_delta_launches", "rmw_host_delta_launches",
            "host_encode_launches", "journal_entries")


def _cluster(tmp_path_factory, unit):
    from ceph_tpu.osd.standalone import StandaloneCluster
    c = StandaloneCluster(
        n_osds=6, pg_num=2, profile=f"plugin=jerasure "
        f"technique=reed_sol_van k={K} m={M}", cephx=True,
        secret=os.urandom(32), hb_interval=0.5, hb_grace=30.0, store="tin",
        store_dir=str(tmp_path_factory.mktemp(f"tin-{unit}")),
        chunk_size=unit)
    c.wait_for_clean(timeout=40)
    return c


def _unsuspecting(boot, tries=4):
    """A cluster no daemon of which holds a live peer for unreachable: a
    boot on a busy host can leave such a suspicion (PERF section 6, PR
    24), and a primary that has one takes the degraded path."""
    for _ in range(tries):
        c = boot()
        if not any(d.suspect for d in c.osds.values()):
            return c
        c.shutdown()
    raise RuntimeError("every boot left a suspicion")


def _counters(cluster, keys=COUNTERS):
    dumps = [d.ec_perf.dump() for d in cluster.osds.values()]
    return {key: sum(int(d[key]) for d in dumps) for key in keys}


def _write_at(client, payloads, w):
    import time
    block = payloads[w["payload"]][w["cut"]:w["cut"] + BLOCK]
    w["start"] = time.perf_counter()
    client.write_at(NAMES[w["object"]], w["offset"], block)
    w["end"], w["ok"] = time.perf_counter(), True


@pytest.fixture(scope="module")
def image(tmp_path_factory):
    """The image filled, then overwritten by four loops; what the cluster
    holds afterwards, the history, and the counters' rise."""
    from ceph_tpu.osd import ecbackend
    from ceph_tpu.osd.pgbackend import HINFO_KEY, shard_cid
    rng = np.random.default_rng(31)
    payloads = [rng.integers(0, 256, OBJECT, dtype=np.uint8).tobytes()
                for _ in range(4)]
    fill = [0, 1]
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        cluster = _cluster(tmp_path_factory, 4096)
        try:
            client = cluster.client()
            for name, pay in zip(NAMES, fill):
                client.write({name: payloads[pay]})
            before = _counters(cluster)
            history = [[{"object": int(r.integers(OBJECTS)),
                         "offset": int(r.integers(OBJECT // BLOCK)) * BLOCK,
                         "payload": int(r.integers(len(payloads))),
                         "cut": int(r.integers(OBJECT // BLOCK)) * BLOCK,
                         "ok": False} for _ in range(WRITES)]
                       for r in (np.random.default_rng([31, i])
                                 for i in range(LOOPS))]
            # the first write from one thread: a cold shared client
            # fails to authorize under concurrent callers (ROADMAP A3b)
            _write_at(client, payloads, history[0][0])
            threads = [threading.Thread(
                target=lambda ws: [_write_at(client, payloads, w)
                                   for w in ws if not w["ok"]], args=(ws,))
                for ws in history]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            after = _counters(cluster)
            stored, journal = {}, []
            for obj, name in enumerate(NAMES):
                pg = client.osdmap.object_to_pg(1, name)[1]
                acting = client.osdmap.pg_to_up_acting_osds(1, pg)[2]
                rows, crcs = [], []
                for shard, osd in enumerate(acting):
                    store = cluster.osds[osd].store
                    cid = shard_cid(f"1.{pg}", shard)
                    rows.append(np.asarray(store.read(cid, name), np.uint8))
                    crcs.append(struct.unpack_from(
                        "<III", store.getattr(cid, name, HINFO_KEY))[2])
                    if store.exists(cid, "__stripe_journal__"):
                        journal += [bytes(key) for key, _ in store.omap_iter(
                            cid, "__stripe_journal__")]
                stored[obj] = rows, crcs
            back = np.stack([np.frombuffer(client.read(name), np.uint8)
                             for name in NAMES])
        finally:
            cluster.shutdown()
    writes = [w for ws in history for w in ws]
    assert all(w["ok"] for w in writes)
    want = block_image.filled(payloads, fill)
    racing = block_image.replay(want, payloads, writes, BLOCK)
    block_image.settle(want, racing, back, BLOCK)
    return {"want": want, "back": back, "stored": stored, "journal": journal,
            "rose": {key: after[key] - before[key] for key in COUNTERS},
            "writes": len(writes)}


def _data_rows(obj_bytes: np.ndarray) -> np.ndarray:
    return obj_bytes.reshape(-1, K, 4096).transpose(1, 0, 2).reshape(K, -1)


def test_every_block_read_back_is_the_block_devices(image):
    assert image["back"].shape == (OBJECTS, OBJECT)
    assert block_image.blocks_differing(image["want"], image["back"],
                                        BLOCK) == 0
    # and the overwrites changed it: the reference is not the fill
    assert image["writes"] == LOOPS * WRITES


@pytest.mark.parametrize("obj", range(OBJECTS))
@pytest.mark.parametrize("what", ["data", "parity", "crc"])
def test_every_shard_follows_the_data(image, obj, what):
    rows, crcs = image["stored"][obj]
    data = _data_rows(image["want"][obj])
    if what == "data":
        assert all(np.array_equal(rows[s], data[s]) for s in range(K))
    elif what == "parity":
        parity = gf256.rs_encode(gf256.reed_sol_van(K, M), data)
        assert all(np.array_equal(rows[K + j], parity[j]) for j in range(M))
    else:
        want = crc32c.crc32c_rows(0xFFFFFFFF, np.stack(rows))
        assert [int(c) for c in crcs] == [int(c) for c in want]


@pytest.mark.parametrize("key,rise", [
    ("rmw_ops", LOOPS * WRITES), ("rmw_shard_ios", (1 + M) * LOOPS * WRITES),
    ("rmw_delta_launches", LOOPS * WRITES), ("rmw_full_fallbacks", 0),
    ("rmw_host_delta_launches", 0), ("host_encode_launches", 0),
    ("journal_entries", (1 + M) * LOOPS * WRITES)])
def test_every_write_took_the_delta_path_on_the_device(image, key, rise):
    assert image["rose"][key] == rise


def test_the_stripe_journal_is_drained(image):
    # every participating shard keeps its watermark and no intent
    assert image["journal"] and set(image["journal"]) == {b"applied"}


def test_under_the_256_byte_stripe_unit_the_same_write_takes_the_full_path(
        tmp_path_factory):
    """The harness's as-found stripe unit: a stripe is 512 bytes at k=2,
    a 4 KiB write spans eight, and the delta path refuses it."""
    from ceph_tpu.osd import ecbackend
    rng = np.random.default_rng(32)
    whole = rng.integers(0, 256, OBJECT, dtype=np.uint8).tobytes()
    block = rng.integers(0, 256, BLOCK, dtype=np.uint8).tobytes()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        cluster = _cluster(tmp_path_factory, 256)
        try:
            client = cluster.client()
            client.write({NAMES[0]: whole})
            before = _counters(cluster)
            client.write_at(NAMES[0], 3 * BLOCK, block)
            rose = {key: n - before[key]
                    for key, n in _counters(cluster).items()}
            back = client.read(NAMES[0])
        finally:
            cluster.shutdown()
    assert back == whole[:3 * BLOCK] + block + whole[4 * BLOCK:]
    assert rose["rmw_full_fallbacks"] == 1
    assert rose["rmw_ops"] == rose["rmw_shard_ios"] == 0
    assert rose["rmw_delta_launches"] == rose["journal_entries"] == 0


# -- the PG's metadata rides the RMW's apply round (PR 32) ---------------------
#
# When `write_at` is acknowledged, every live shard of the PG holds a
# (base, delta) pair of `__pg_meta__` whose rank covers the write: on the
# delta path the record went out on the apply round's transactions, to the
# 1 + m participants with the XOR and to the other shards alone, and no
# round of its own followed.

META_COUNTERS = ("meta_rides", "meta_persist_rounds", "rmw_ops",
                 "rmw_shard_ios", "rmw_full_fallbacks", "journal_entries")


def _ec(cluster):
    return _counters(cluster, META_COUNTERS)


def _rose(cluster, before):
    return {key: n - before[key] for key, n in _ec(cluster).items()
            if n != before[key]}


def _placed(cluster, client, name):
    """(pg seed, acting, the primary's daemon and its backend)."""
    ps = client.osdmap.object_to_pg(1, name)[1]
    acting = client.osdmap.pg_to_up_acting_osds(1, ps)[2]
    primary = cluster.osds[acting[0]]
    return ps, acting, primary, primary.backends[ps]


def _pairs(cluster, ps, acting):
    """slot -> the (base, delta) pair that slot's store holds."""
    from ceph_tpu.osd.pgbackend import shard_cid
    from ceph_tpu.osd.standalone import PG_META_DELTA_KEY, PG_META_KEY
    out = {}
    for slot, osd in enumerate(acting):
        omap = dict(cluster.osds[osd].store.omap_iter(
            shard_cid(f"1.{ps}", slot), "__pg_meta__"))
        out[slot] = (bytes(omap[PG_META_KEY]),
                     bytes(omap.get(PG_META_DELTA_KEY, b"")))
    return out


def _heads(pairs):
    from ceph_tpu.osd.standalone import OSDDaemon
    return {slot: OSDDaemon._meta_rank(pair)[1]
            for slot, pair in pairs.items()}


def _covered(cluster, client, name):
    ps, acting, primary, be = _placed(cluster, client, name)
    heads = _heads(_pairs(cluster, ps, acting))
    return heads == dict.fromkeys(range(len(acting)), be.pg_log.head), heads


def test_a_pool_with_a_snap_takes_the_full_base_every_time(
        tmp_path_factory):
    from ceph_tpu.osd import ecbackend
    whole, block = os.urandom(OBJECT), os.urandom(BLOCK)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        cluster = _unsuspecting(lambda: _cluster(tmp_path_factory, 4096))
        try:
            client = cluster.client()
            client.write({NAMES[0]: whole})
            client.snap_create("before")
            client.write_at(NAMES[0], 0, block)       # clones, then RMW
            ps, acting, primary, be = _placed(cluster, client, NAMES[0])
            for i in (1, 2, 3):
                before = _ec(cluster)
                client.write_at(NAMES[0], i * BLOCK, block)
                pairs = _pairs(cluster, ps, acting)
                assert all(not delta for _base, delta in pairs.values())
                assert _heads(pairs) == dict.fromkeys(
                    range(K + M), be.pg_log.head)
                assert _rose(cluster, before) == {
                    "meta_rides": 1, "rmw_ops": 1, "rmw_shard_ios": 1 + M,
                    "journal_entries": 1 + M}
            back = client.read(NAMES[0])
        finally:
            cluster.shutdown()
    assert back == block * 4 + whole[4 * BLOCK:]


def test_the_new_primary_serves_the_bytes_and_logs_at_head_plus_one(
        tmp_path_factory):
    """Kill the primary after an acknowledged `write_at`: the pairs the
    apply round left on the other shards are what the takeover has."""
    from ceph_tpu.osd import ecbackend
    whole, block = os.urandom(OBJECT), os.urandom(BLOCK)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        cluster = _unsuspecting(lambda: _cluster(tmp_path_factory, 4096))
        try:
            client = cluster.client()
            client.write({NAMES[0]: whole})
            client.write_at(NAMES[0], 0, block)
            before = _ec(cluster)
            client.write_at(NAMES[0], 2 * BLOCK, block)
            assert _rose(cluster, before) == {
                "meta_rides": 1, "rmw_ops": 1, "rmw_shard_ios": 1 + M,
                "journal_entries": 1 + M}
            ps, acting, primary, be = _placed(cluster, client, NAMES[0])
            head = be.pg_log.head
            cluster.kill_osd(acting[0])
            client.osd_down(acting[0])     # the mark, not 30 s of grace
            cluster.wait_for_clean(timeout=60)
            want = block + whole[BLOCK:2 * BLOCK] + block \
                + whole[3 * BLOCK:]
            assert client.read(NAMES[0]) == want
            ps, now, taker, be2 = _placed(cluster, client, NAMES[0])
            assert now[0] != acting[0]
            assert be2.object_versions[NAMES[0]] == head
            at = be2.pg_log.head           # recovery may have logged
            client.write_at(NAMES[0], BLOCK, block)
            assert be2.pg_log.head == be2.object_versions[NAMES[0]] \
                == at + 1
            ok, heads = _covered(cluster, client, NAMES[0])
            assert ok, heads
            back = client.read(NAMES[0])
        finally:
            cluster.shutdown()
    assert back == block * 3 + whole[3 * BLOCK:]


@pytest.mark.parametrize("pool", ["ec-full-path", "replicated"])
def test_a_write_at_off_the_delta_path_still_covers_every_shard(
        tmp_path_factory, pool):
    """The 256-byte stripe unit (the delta path refuses the write) and
    a replicated pool: the metadata rode no fan-out, so `_persist_meta`'s
    round follows, once."""
    from ceph_tpu.osd import ecbackend
    from ceph_tpu.osd.standalone import StandaloneCluster
    whole, block = os.urandom(OBJECT), os.urandom(BLOCK)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        if pool == "replicated":
            cluster = StandaloneCluster(
                n_osds=4, pg_num=2, profile="replicated size=3",
                hb_interval=0.5, hb_grace=30.0)
            cluster.wait_for_clean(timeout=40)
        else:
            cluster = _cluster(tmp_path_factory, 256)
        try:
            client = cluster.client()
            client.write({NAMES[0]: whole})
            before = _ec(cluster)
            client.write_at(NAMES[0], 3 * BLOCK, block)
            rose = _rose(cluster, before)
            ok, heads = _covered(cluster, client, NAMES[0])
            back = client.read(NAMES[0])
        finally:
            cluster.shutdown()
    assert ok, heads
    assert rose == ({"meta_persist_rounds": 1, "rmw_full_fallbacks": 1}
                    if pool == "ec-full-path"
                    else {"meta_persist_rounds": 1})
    assert back == whole[:3 * BLOCK] + block + whole[4 * BLOCK:]


@pytest.fixture(scope="module")
def wide(tmp_path_factory):
    """The deployment's own width: k=8 m=3 on 12 OSDs, one PG of 11
    shards, the stripe unit 4096, one 64 KiB object written whole."""
    from ceph_tpu.osd import ecbackend
    from ceph_tpu.osd.standalone import StandaloneCluster
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(ecbackend, "_host_crc_available", lambda: False)
        def boot():
            c = StandaloneCluster(
                n_osds=12, pg_num=1, profile="plugin=jerasure "
                "technique=reed_sol_van k=8 m=3", cephx=True,
                secret=os.urandom(32), hb_interval=0.5, hb_grace=30.0,
                store="tin", chunk_size=4096,
                store_dir=str(tmp_path_factory.mktemp("tin-wide")))
            c.wait_for_clean(timeout=60)
            return c
        c = _unsuspecting(boot)
        try:
            client = c.client()
            whole = np.random.default_rng(32).integers(
                0, 256, OBJECT, dtype=np.uint8).tobytes()
            client.write({NAMES[0]: whole})
            # the column's delta program, compiled before any count
            client.write_at(NAMES[0], 3 * BLOCK, whole[:BLOCK])
            yield c, client, bytearray(whole[:3 * BLOCK] + whole[:BLOCK]
                                       + whole[4 * BLOCK:])
        finally:
            c.shutdown()


def test_every_shard_s_pair_ranks_at_the_acknowledged_write(wide):
    cluster, client, image = wide
    ps, acting, primary, be = _placed(cluster, client, NAMES[0])
    assert len(acting) == 11
    before, head = _ec(cluster), be.pg_log.head
    block = os.urandom(BLOCK)
    client.write_at(NAMES[0], 3 * BLOCK, block)
    image[3 * BLOCK:4 * BLOCK] = block
    assert be.pg_log.head == be.object_versions[NAMES[0]] == head + 1
    assert _heads(_pairs(cluster, ps, acting)) == dict.fromkeys(
        range(11), head + 1)
    # one record on the apply round, no round of its own; four shards
    # moved bytes, the other seven took the record alone
    assert _rose(cluster, before) == {
        "meta_rides": 1, "rmw_ops": 1, "rmw_shard_ios": 4,
        "journal_entries": 4}
    assert client.read(NAMES[0]) == bytes(image)


def test_one_write_at_is_three_rounds_and_nineteen_sub_ops(
        wide, tmp_path, monkeypatch):
    """4 prefetch reads, 4 journal transactions, 11 apply transactions
    (the parent: 23 and 19, its metadata in a fourth round of 11); the
    span log holds one `ecbackend.rmw.apply` and the `osd.persist_meta`
    record that the per-layer reader sums."""
    from ceph_tpu.osd.tinstore import TinStore
    from ceph_tpu.utils.tracing import span_log, start_trace, stop_trace
    cluster, client, image = wide
    ps, acting, primary, be = _placed(cluster, client, NAMES[0])
    commits, keep = [], TinStore.queue_transaction

    def counted(self, txn, *a, **kw):
        commits.append(self)
        return keep(self, txn, *a, **kw)
    monkeypatch.setattr(TinStore, "queue_transaction", counted)
    frames = int(primary.ec_perf.get("rmw_fetch_frames"))
    block = os.urandom(BLOCK)
    assert start_trace(str(tmp_path / "capture"))
    try:
        t0 = time.perf_counter()
        client.write_at(NAMES[0], 5 * BLOCK, block)
        logged = [r for r in span_log(since=t0)]
    finally:
        stop_trace()
    image[5 * BLOCK:6 * BLOCK] = block
    reads = int(primary.ec_perf.get("rmw_fetch_frames")) - frames
    by_store = {id(s): commits.count(s) for s in commits}
    stores = {id(cluster.osds[osd].store): slot
              for slot, osd in enumerate(acting)}
    assert set(by_store) <= set(stores)
    per_slot = {stores[i]: n for i, n in by_store.items()}
    # column 5 and the three parity slots: intent, then apply; the
    # other seven: the metadata alone
    assert per_slot == {s: 2 if s in (5, 8, 9, 10) else 1
                        for s in range(11)}
    assert (reads, len(commits), reads + len(commits)) == (4, 15, 19)
    rounds = [r["name"] for r in logged if r["name"] in (
        "ecbackend.rmw.prefetch", "ecbackend.rmw.journal",
        "ecbackend.rmw.apply", "ecbackend.write.fanout")]
    assert sorted(rounds) == ["ecbackend.rmw.apply", "ecbackend.rmw.journal",
                              "ecbackend.rmw.prefetch"]
    (apply,) = [r for r in logged if r["name"] == "ecbackend.rmw.apply"]
    (meta,) = [r for r in logged if r["name"] == "osd.persist_meta"]
    # the record is made before the journal round and sent by the apply
    assert meta["start"] + meta["dur"] <= apply["start"] + 1e-6
    assert meta["trace_id"] == apply["trace_id"]


def test_the_full_base_goes_out_every_meta_delta_max_entries(wide):
    from ceph_tpu.osd.standalone import _META_DELTA_MAX
    cluster, client, image = wide
    ps, acting, primary, be = _placed(cluster, client, NAMES[0])
    before, bases = _ec(cluster), []
    for i in range(_META_DELTA_MAX):
        block = os.urandom(BLOCK)
        client.write_at(NAMES[0], 3 * BLOCK, block)
        image[3 * BLOCK:4 * BLOCK] = block
        pairs = _pairs(cluster, ps, acting)
        assert _heads(pairs) == dict.fromkeys(range(11), be.pg_log.head)
        if not pairs[0][1]:
            bases.append(i)
            # the base subsumes the window: every shard's delta key is
            # empty, in the same transaction
            assert all(not delta for _base, delta in pairs.values())
            assert len({base for base, _delta in pairs.values()}) == 1
    assert len(bases) == 1
    assert _rose(cluster, before) == {
        "meta_rides": _META_DELTA_MAX, "rmw_ops": _META_DELTA_MAX,
        "rmw_shard_ios": 4 * _META_DELTA_MAX,
        "journal_entries": 4 * _META_DELTA_MAX}
    assert client.read(NAMES[0]) == bytes(image)


def test_a_restore_that_hears_none_of_the_four_participants(wide):
    """`_load_meta` restores on a majority of the acting set, and the
    participants of one overwrite are four of eleven: the seven other
    shards' pairs alone have to name the write (they cannot serve the
    bytes at k=8: this is a test of the metadata)."""
    cluster, client, image = wide
    ps, acting, primary, be = _placed(cluster, client, NAMES[0])
    block = os.urandom(BLOCK)
    client.write_at(NAMES[0], 2 * BLOCK, block)       # column 2
    image[2 * BLOCK:3 * BLOCK] = block
    participants = {acting[s] for s in (2, 8, 9, 10)}
    witness = cluster.osds[acting[6]]
    best, _local, quorum_ok = witness._load_meta(
        ps, acting, suspect_extra=participants)
    assert quorum_ok
    assert witness._meta_rank(best)[1] == be.pg_log.head
    view = witness._degraded_view(ps, participants)
    assert view.pg_log.head == be.pg_log.head
    assert view.object_versions[NAMES[0]] == be.object_versions[NAMES[0]] \
        == be.pg_log.head
    assert view.object_sizes == be.object_sizes
    assert view.shard_applied == be.shard_applied == [be.pg_log.head] * 11
    assert view.acting == be.acting
