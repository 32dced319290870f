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


def _counters(cluster):
    dumps = [d.ec_perf.dump() for d in cluster.osds.values()]
    return {key: sum(int(d[key]) for d in dumps) for key in COUNTERS}


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
