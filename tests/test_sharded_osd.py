"""Sharded-OSD dispatch invariants (r13): per-PG ordering under
`osd_op_num_shards > 1`, batch split-join across shards, per-shard
occupancy observability, and host-encode bit-parity.

The ordering contract: ops hash by PG id to a shard, each shard
drains FIFO (same mClock class, seq-ordered heap) on one worker —
interleaved writes to ONE PG must execute in arrival order even while
cross-PG ops overlap on other shards. The test submits pipelined raw
MOSDOp frames (no client-side waits, the test_op_window idiom) so the
queue really holds many same-PG ops at once."""

import numpy as np
import pytest

from ceph_tpu.msgr.messenger import Messenger
from ceph_tpu.osd.standalone import (MOSDOp, MOSDOpReply,
                                     StandaloneCluster, _Rpc)
from ceph_tpu.utils.encoding import Decoder, Encoder


@pytest.fixture(scope="module")
def sharded_cluster():
    c = StandaloneCluster(
        n_osds=4, pg_num=4, op_shards=2, msgr_workers=2,
        profile="plugin=tpu_rs k=2 m=1")
    c.wait_for_clean(timeout=30)
    yield c
    c.shutdown()


def _raw_client(c):
    """A bare messenger + rpc speaking the client op protocol (no
    cephx on this cluster, so the auth gate is off)."""
    m = Messenger("client.raw")
    rpc = _Rpc(m, MOSDOpReply.type_id)
    for d in c.osds.values():
        m.add_peer(d.name, d.msgr.addr)
    return m, rpc


def _write_body(ps: int, name: str, data: bytes) -> bytes:
    e = Encoder()
    e.u32(ps)
    e.u64(0)                     # snapc
    e.mapping({name: data}, Encoder.string, Encoder.blob)
    return e.bytes()


def _read_body(ps: int, name: str) -> bytes:
    e = Encoder()
    e.u32(ps)
    e.string(name)
    return e.bytes()


def _primary(c, ps: int) -> str:
    m = c.mons[0].osdmap
    return f"osd.{m.pg_to_up_acting_osds(1, ps)[2][0]}"


class TestPerPGOrdering:
    def test_interleaved_same_pg_writes_stay_ordered(
            self, sharded_cluster):
        """30 pipelined writes to ONE object (same PG, no waits
        between submits) interleaved with cross-PG traffic: the final
        bytes must be the LAST submitted value — a queue-level
        reorder would leave an earlier value on top."""
        c = sharded_cluster
        m, rpc = _raw_client(c)
        try:
            handles = []
            for i in range(30):
                tgt0 = _primary(c, 0)
                handles.append(rpc.submit(
                    tgt0, lambda rid, i=i: MOSDOp(
                        rid, True, "write",
                        _write_body(0, "ordered", bytes([i]) * 512))))
                # overlapping cross-PG op: lands in the OTHER shard
                # (pg 1 % 2 != pg 0 % 2) and must not perturb pg 0's
                # order
                tgt1 = _primary(c, 1)
                handles.append(rpc.submit(
                    tgt1, lambda rid, i=i: MOSDOp(
                        rid, True, "write",
                        _write_body(1, f"x{i}", b"z" * 256))))
            for h in handles:
                rep = h.wait(20.0)
                assert rep.ok, rep.err
            rep = rpc.call(_primary(c, 0),
                           lambda rid: MOSDOp(rid, True, "read",
                                              _read_body(0,
                                                         "ordered")),
                           timeout=20.0)
            assert rep.ok, rep.err
            assert bytes(rep.blob) == bytes([29]) * 512
        finally:
            m.shutdown()

    def test_cross_pg_ops_really_spread_over_shards(
            self, sharded_cluster):
        """The occupancy evidence: after traffic to every PG, at
        least one daemon's dump_op_shards shows grants on BOTH
        shards (pg % 2 covers both residues)."""
        c = sharded_cluster
        cl = c.client()
        objs = {f"spread-{i}": bytes([i]) * 1024 for i in range(32)}
        cl.write(objs)
        for n, v in objs.items():
            assert bytes(cl.read(n)) == v
        spread = False
        for osd in c.osd_ids():
            dump = cl.daemon(osd, "dump_op_shards")
            assert set(dump) == {"shard_0", "shard_1"}
            served = [sum(row["served"] for row in shard.values())
                      for shard in dump.values()]
            if all(s > 0 for s in served):
                spread = True
        assert spread, "no daemon served ops on both shards"

    def test_batch_frame_splits_and_rejoins_in_slot_order(
            self, sharded_cluster):
        """A `batch` frame whose sub-ops span BOTH shards: the reply
        must carry every slot, in the original order, each ok — the
        split-join path (_BatchJoin) at work. PGs are chosen so one
        primary owns PGs in both shard residues when possible;
        otherwise the single-group fast path serves it (both are
        correct, the wire contract is identical)."""
        c = sharded_cluster
        m, rpc = _raw_client(c)
        try:
            # find a primary owning >= 2 PGs in different shards
            by_primary: dict[str, list[int]] = {}
            for ps in range(4):
                by_primary.setdefault(_primary(c, ps), []).append(ps)
            tgt, pgs = max(by_primary.items(),
                           key=lambda kv: len({p % 2
                                               for p in kv[1]}))
            e = Encoder()
            subs = [(ps, f"batch-{ps}-{j}") for ps in pgs
                    for j in range(2)]
            e.u32(len(subs))
            for slot, (ps, name) in enumerate(subs):
                e.string("write")
                e.blob(_write_body(ps, name, bytes([slot]) * 128))
            rep = rpc.call(tgt, lambda rid: MOSDOp(
                rid, True, "batch", e.bytes()), timeout=20.0)
            assert rep.ok, rep.err
            d = Decoder(rep.blob)
            nslots = d.u32()
            assert nslots == len(subs)
            for slot in range(nslots):
                ok, blob, err = d.boolean(), d.blob(), d.string()
                assert ok, (slot, err)
            # and the writes really landed, bit-exact
            for slot, (ps, name) in enumerate(subs):
                rep = rpc.call(tgt, lambda rid, ps=ps, name=name:
                               MOSDOp(rid, True, "read",
                                      _read_body(ps, name)),
                               timeout=20.0)
                assert rep.ok and bytes(rep.blob) == \
                    bytes([slot]) * 128, (slot, name)
        finally:
            m.shutdown()


class TestHostEncodeParity:
    def test_host_encode_bit_identical_to_fused_device_launch(self):
        """The r13 write-path host-encode mode (native SSE RS +
        hardware crc32c on the CPU backend) must produce EXACTLY the
        fused device launch's shards and hinfo CRCs — same coding
        matrix, bit-for-bit."""
        from ceph_tpu.osd import ecbackend as EB
        from ceph_tpu.osd.ecbackend import ECBackend, ShardSet
        if not EB._host_crc_available():
            pytest.skip("native codec/hw-crc unavailable")
        profile = "plugin=tpu_rs k=4 m=2"
        be = ECBackend(profile, "1.0", list(range(6)), ShardSet(),
                       chunk_size=256)
        rng = np.random.default_rng(7)
        data = rng.integers(0, 256, (5, 4, 1024), np.uint8)
        host_shards, host_crcs = be._encode_shards_with_crcs(data,
                                                             1024)
        # force the device path by disabling the host gate
        orig = EB._host_crc_available
        EB._host_crc_available = lambda: False
        try:
            dev_shards, dev_crcs = be._encode_shards_with_crcs(data,
                                                               1024)
        finally:
            EB._host_crc_available = orig
        assert np.array_equal(host_shards, dev_shards)
        assert np.array_equal(np.asarray(host_crcs, np.uint32),
                              np.asarray(dev_crcs, np.uint32))
