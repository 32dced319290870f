"""Messenger tests: typed dispatch, crc-protected frames, lossless
reconnect-with-replay, exactly-once delivery (refs: src/msg/async/
ProtocolV2.cc crc mode + reconnect; Messenger/Dispatcher contract)."""

import struct
import threading
import time

import pytest

from ceph_tpu.msgr.messenger import (Message, Messenger,
                                     register_message)
from ceph_tpu.utils.encoding import Decoder, Encoder


@register_message
class Ping(Message):
    type_id = 0x70

    def __init__(self, stamp: int, note: str = ""):
        self.stamp = stamp
        self.note = note

    def encode_payload(self, e: Encoder) -> None:
        e.start(1, 1).u64(self.stamp).string(self.note).finish()

    @classmethod
    def decode_payload(cls, d: Decoder) -> "Ping":
        d.start(1)
        m = cls(d.u64(), d.string())
        d.finish()
        return m


@register_message
class OpReply(Message):
    type_id = 0x71

    def __init__(self, result: int):
        self.result = result

    def encode_payload(self, e: Encoder) -> None:
        e.start(1, 1).i32(self.result).finish()

    @classmethod
    def decode_payload(cls, d: Decoder) -> "OpReply":
        d.start(1)
        m = cls(d.i32())
        d.finish()
        return m


def pair(secret_a=None, secret_b=None):
    a = Messenger("osd.0", secret=secret_a)
    b = Messenger("osd.1", secret=secret_b)
    a.add_peer("osd.1", b.addr)
    b.add_peer("osd.0", a.addr)
    return a, b


def wait_for(pred, timeout=10.0):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if pred():
            return True
        time.sleep(0.01)
    return False


class TestMessenger:
    def test_typed_roundtrip_both_directions(self):
        a, b = pair()
        try:
            got_b, got_a = [], []
            b.register_handler(Ping.type_id,
                               lambda p, m: got_b.append((p, m)))
            a.register_handler(OpReply.type_id,
                               lambda p, m: got_a.append((p, m)))
            for i in range(5):
                a.send("osd.1", Ping(i, f"hb{i}"))
            assert wait_for(lambda: len(got_b) == 5)
            assert [m.stamp for _, m in got_b] == list(range(5))
            assert got_b[0][0] == "osd.0"
            assert got_b[3][1].note == "hb3"
            # reply over the reverse direction
            b.send("osd.0", OpReply(-17))
            assert wait_for(lambda: len(got_a) == 1)
            assert got_a[0] == ("osd.1", got_a[0][1])
            assert got_a[0][1].result == -17
            assert a.flush("osd.1") and b.flush("osd.0")
        finally:
            a.shutdown()
            b.shutdown()

    def test_reconnect_replays_unacked_exactly_once(self):
        a, b = pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got == [1])
            # kill every live connection out from under the session
            for conn in list(a._conns.values()):
                conn.close()
            time.sleep(0.05)
            for i in (2, 3, 4):
                a.send("osd.1", Ping(i))
            assert a.flush("osd.1", timeout=15)
            assert wait_for(lambda: got == [1, 2, 3, 4]), got
            time.sleep(0.2)
            assert got == [1, 2, 3, 4]  # no duplicates from replay
        finally:
            a.shutdown()
            b.shutdown()

    def test_corrupt_frame_kills_connection_then_replay_heals(self):
        a, b = pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got == [1])
            # inject a corrupt frame directly onto the live socket
            conn = next(iter(a._conns.values()))
            body = struct.pack("<QH", 99, Ping.type_id) + b"garbage"
            frame = struct.pack("<I", len(body)) + body
            frame += struct.pack("<I", 0xDEADBEEF)  # wrong crc
            with conn.wlock:
                conn.sock.sendall(frame)
            # receiver must drop the connection, not dispatch garbage
            assert wait_for(lambda: not conn.alive)
            assert got == [1]
            # the session continues: new sends reconnect + deliver
            a.send("osd.1", Ping(2))
            assert a.flush("osd.1", timeout=15)
            assert wait_for(lambda: got == [1, 2])
        finally:
            a.shutdown()
            b.shutdown()

    def test_unknown_peer_raises(self):
        a = Messenger("osd.9")
        try:
            with pytest.raises(KeyError):
                a.send("nobody", Ping(1))
        finally:
            a.shutdown()

    def test_many_threads_one_peer(self):
        a, b = pair()
        try:
            got = []
            lock = threading.Lock()

            def h(p, m):
                with lock:
                    got.append(m.stamp)
            b.register_handler(Ping.type_id, h)
            ts = [threading.Thread(
                target=lambda base=i: [a.send("osd.1",
                                              Ping(base * 100 + j))
                                       for j in range(20)])
                for i in range(5)]
            [t.start() for t in ts]
            [t.join(10) for t in ts]
            assert a.flush("osd.1", timeout=20)
            assert wait_for(lambda: len(got) == 100), len(got)
            assert len(set(got)) == 100  # every message exactly once
        finally:
            a.shutdown()
            b.shutdown()


class TestReconnectEdges:
    def test_acceptor_replays_its_stranded_queue(self):
        # B's outbound dial is unreachable (NAT-ish); its queued
        # messages must still flow when A redials IN, via the
        # symmetric handshake's last-seen exchange
        a, b = pair()
        try:
            got_a, got_b = [], []
            a.register_handler(OpReply.type_id,
                               lambda p, m: got_a.append(m.result))
            b.register_handler(Ping.type_id,
                               lambda p, m: got_b.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got_b == [1])
            # sever everything; make B unable to dial out
            for c in list(a._conns.values()) + list(b._conns.values()):
                c.close()
            b._connect_blocked = b._connect
            b._connect = lambda peer: (_ for _ in ()).throw(
                ConnectionError("unreachable"))
            time.sleep(0.05)
            b.send("osd.0", OpReply(42))   # strands in b's queue
            time.sleep(0.1)
            assert not got_a
            # A redials: the inbound handshake must trigger B's replay
            a.send("osd.1", Ping(2))
            assert wait_for(lambda: got_a == [42]), got_a
            assert wait_for(lambda: got_b == [1, 2])
            assert b.flush("osd.0", timeout=10)
        finally:
            a.shutdown()
            b.shutdown()

    def test_simultaneous_dials_converge(self):
        a, b = pair()
        try:
            got_a, got_b = [], []
            a.register_handler(OpReply.type_id,
                               lambda p, m: got_a.append(m.result))
            b.register_handler(Ping.type_id,
                               lambda p, m: got_b.append(m.stamp))
            # both first-contact each other at the same instant
            ta = threading.Thread(target=a.send,
                                  args=("osd.1", Ping(7)))
            tb = threading.Thread(target=b.send,
                                  args=("osd.0", OpReply(8)))
            ta.start(); tb.start()
            ta.join(10); tb.join(10)
            assert a.flush("osd.1", timeout=15)
            assert b.flush("osd.0", timeout=15)
            assert wait_for(lambda: got_b == [7]), got_b
            assert wait_for(lambda: got_a == [8]), got_a
        finally:
            a.shutdown()
            b.shutdown()

    def test_poison_handler_does_not_kill_session(self):
        a, b = pair()
        try:
            got = []

            def handler(p, m):
                if m.stamp == 13:
                    raise RuntimeError("poison")
                got.append(m.stamp)
            b.register_handler(Ping.type_id, handler)
            for i in (12, 13, 14):
                a.send("osd.1", Ping(i))
            assert a.flush("osd.1", timeout=10)
            assert wait_for(lambda: got == [12, 14]), got
        finally:
            a.shutdown()
            b.shutdown()


class TestSecureMode:
    """ProtocolV2 secure session analog (ref: src/msg/async/
    ProtocolV2.cc secure handshake; cephx collapsed to one PSK):
    mutual auth, AES-GCM frames, strict mode negotiation."""

    SECRET = b"0123456789abcdef0123456789abcdef"

    def secure_pair(self):
        return pair(secret_a=self.SECRET, secret_b=self.SECRET)

    def test_roundtrip_and_exactly_once_replay(self):
        a, b = self.secure_pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1, "sealed"))
            assert wait_for(lambda: got == [1])
            # every live conn carries a box (frames are ciphertext)
            assert all(c.box is not None for c in a._conns.values())
            for conn in list(a._conns.values()):
                conn.close()
            time.sleep(0.05)
            for i in (2, 3):
                a.send("osd.1", Ping(i))
            assert a.flush("osd.1", timeout=15)
            assert wait_for(lambda: got == [1, 2, 3]), got
            time.sleep(0.2)
            assert got == [1, 2, 3]    # replay stays exactly-once
        finally:
            a.shutdown()
            b.shutdown()

    def test_tampered_ciphertext_kills_session_then_heals(self):
        a, b = self.secure_pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got == [1])
            conn = next(iter(a._conns.values()))
            # a validly-framed but bit-flipped ciphertext: GCM tag
            # must fail and the receiver must drop the session
            plain = struct.pack("<QH", 99, Ping.type_id) + b"evil"
            hdr = struct.pack("<I", 12 + len(plain) + 16)
            with conn.wlock:
                sealed = conn.box.seal(plain, hdr)
                sealed = sealed[:-1] + bytes([sealed[-1] ^ 0x01])
                conn.sock.sendall(hdr + sealed)
            assert wait_for(lambda: not conn.alive)
            assert got == [1]          # nothing forged was dispatched
            a.send("osd.1", Ping(2))
            assert a.flush("osd.1", timeout=15)
            assert wait_for(lambda: got == [1, 2])
        finally:
            a.shutdown()
            b.shutdown()

    def test_wrong_secret_refused(self):
        a, b = pair(secret_a=self.SECRET,
                    secret_b=b"not the same secret at all!!....")
        try:
            with pytest.raises((ConnectionError, OSError)):
                a.send("osd.1", Ping(1))
                # dialer may only notice at proof check on 2nd leg
                assert not a.flush("osd.1", timeout=2)
                raise ConnectionError("never authenticated")
            assert not b._in_seq     # nothing ever dispatched
        finally:
            a.shutdown()
            b.shutdown()

    def test_mode_mismatch_refused_no_downgrade(self):
        a, b = pair(secret_a=self.SECRET, secret_b=None)
        try:
            with pytest.raises((ConnectionError, OSError)):
                a.send("osd.1", Ping(1))
                assert not a.flush("osd.1", timeout=2)
                raise ConnectionError("secure endpoint accepted crc")
            assert not b._in_seq
        finally:
            a.shutdown()
            b.shutdown()


class TestIncarnation:
    """ProtocolV2 cookie/RESET_SESSION analog: a rebooted process
    reuses its NAME but restarts its sequence space — peers must reset
    their receive cursor, not drop the new incarnation's frames as
    replayed duplicates."""

    def test_rebooted_peer_delivers_despite_stale_in_seq(self):
        a, b = pair()
        try:
            got = []
            a.register_handler(OpReply.type_id,
                               lambda p, m: got.append(m.result))
            for i in range(5):     # a's in_seq for osd.1 climbs to 5
                b.send("osd.0", OpReply(i))
            assert wait_for(lambda: len(got) == 5)
            b.shutdown()           # SIGKILL the process behind osd.1
            b2 = Messenger("osd.1")    # fresh incarnation, seqs from 1
            b2.add_peer("osd.0", a.addr)
            a.add_peer("osd.1", b2.addr)
            try:
                b2.send("osd.0", OpReply(99))
                assert b2.flush("osd.0", timeout=10)
                assert wait_for(lambda: got[-1:] == [99]), got
            finally:
                b2.shutdown()
        finally:
            a.shutdown()
            b.shutdown()


class TestCompression:
    """Per-connection compression negotiation (ref: ProtocolV2
    compression handshake, src/compressor/): the {crc, secure} x
    {plain, compressed} matrix, mismatch downgrade, and tamper."""

    SECRET = b"0123456789abcdef0123456789abcdef"
    BIG = "x" * 4096          # compressible payload over the min size

    def _pair(self, secret=None, comp_a="zlib", comp_b="zlib"):
        a = Messenger("osd.0", secret=secret, compress=comp_a)
        b = Messenger("osd.1", secret=secret, compress=comp_b)
        a.add_peer("osd.1", b.addr)
        b.add_peer("osd.0", a.addr)
        return a, b

    @pytest.mark.parametrize("secret", [None, SECRET],
                             ids=["crc", "secure"])
    def test_roundtrip_compressed(self, secret):
        a, b = self._pair(secret=secret)
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.note))
            for i in range(4):
                a.send("osd.1", Ping(i, note=self.BIG))
            assert wait_for(lambda: len(got) == 4)
            assert all(n == self.BIG for n in got)
            assert a.stats.get("tx_compressed", 0) >= 4
            assert b.stats.get("rx_compressed", 0) >= 4
        finally:
            a.shutdown()
            b.shutdown()

    @pytest.mark.parametrize("secret", [None, SECRET],
                             ids=["crc", "secure"])
    def test_small_frames_ship_plain(self, secret):
        a, b = self._pair(secret=secret)
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(7))       # tiny: below _COMPRESS_MIN
            assert wait_for(lambda: got == [7])
            assert a.stats.get("tx_compressed", 0) == 0
        finally:
            a.shutdown()
            b.shutdown()

    def test_mismatch_downgrades_to_plain(self):
        # unlike the security mode, an asymmetric offer must NOT
        # refuse the connection — compression is an optimization
        a, b = self._pair(comp_a="zlib", comp_b=None)
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.note))
            a.send("osd.1", Ping(1, note=self.BIG))
            assert wait_for(lambda: len(got) == 1)
            assert got[0] == self.BIG
            assert a.stats.get("tx_compressed", 0) == 0
            assert b.stats.get("rx_compressed", 0) == 0
        finally:
            a.shutdown()
            b.shutdown()

    def test_tampered_compressed_frame_kills_session_then_heals(self):
        from ceph_tpu.msgr.messenger import _COMP_FLAG
        a, b = self._pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1, note=self.BIG))
            assert wait_for(lambda: got == [1])
            # a frame flagged compressed whose body is NOT valid zlib:
            # crc is correct, so only the decompressor can object
            conn = next(iter(a._conns.values()))
            body = struct.pack("<QH", 99, Ping.type_id | _COMP_FLAG) \
                + b"not-zlib-data"
            frame = struct.pack("<I", len(body)) + body
            import zlib as _z
            from ceph_tpu.msgr.messenger import _crc
            frame += struct.pack("<I", _crc(frame))
            with conn.wlock:
                conn.sock.sendall(frame)
            assert wait_for(lambda: not conn.alive)
            assert got == [1]              # nothing dispatched
            a.send("osd.1", Ping(2, note=self.BIG))
            assert a.flush("osd.1", timeout=15)
            assert wait_for(lambda: got == [1, 2])
        finally:
            a.shutdown()
            b.shutdown()


class TestWriteBudget:
    """The write queue's byte budget is waited out before the per-peer
    order lock, never inside it: the reactor takes that lock to answer
    the same peer, and the reactor is who drains the queue (PR 33: a
    30 MiB frame parked a heartbeat sender inside the lock, the reactor
    behind it on a pong, and both for good)."""

    def test_send_waits_out_the_budget_before_the_peer_lock(self):
        a, b = pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got == [1])
            conn = a._conns["osd.1"]
            held, real = [], conn.await_budget

            def watched():
                held.append(a._plock("osd.1")._is_owned())
                real()
            conn.await_budget = watched
            a.send("osd.1", Ping(2))
            assert wait_for(lambda: got == [1, 2])
            assert held == [False]
        finally:
            a.shutdown()
            b.shutdown()

    def test_a_sender_parked_on_the_budget_does_not_park_the_reactor(self):
        from ceph_tpu.msgr.messenger import _TLS, _WQ_HIGH
        a, b = pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got == [1])
            conn = a._conns["osd.1"]
            stalls = a.perf.get("writeq_stalls")
            with conn.wlock:                 # a queue over its budget
                conn._wq_bytes += _WQ_HIGH + 1
            parked = threading.Thread(
                target=lambda: a.send("osd.1", Ping(2)), daemon=True)
            parked.start()
            time.sleep(0.3)
            assert parked.is_alive() and got == [1]

            def as_reactor():
                _TLS.in_reactor = True       # a pong from the reactor
                a.send("osd.1", Ping(3))
            reactor = threading.Thread(target=as_reactor, daemon=True)
            reactor.start()
            reactor.join(5)
            assert not reactor.is_alive()
            assert wait_for(lambda: got == [1, 3])
            with conn.wlock:                 # the queue drains
                conn._wq_bytes -= _WQ_HIGH + 1
                conn._wcond.notify_all()
            parked.join(5)
            assert not parked.is_alive()
            assert wait_for(lambda: got == [1, 3, 2])
            assert a.perf.get("writeq_stalls") == stalls + 1
        finally:
            a.shutdown()
            b.shutdown()

    def test_a_replay_over_the_budget_waits_for_nothing(self):
        """A reconnect with more than the budget unacked (recovery's
        8 MiB pushes) replays inside the per-peer lock, and must not
        park there: it resends what the unacked queue holds already."""
        from ceph_tpu.msgr.messenger import _Conn, _WQ_HIGH
        a, b = pair()
        real = _Conn.await_budget
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(1))
            assert wait_for(lambda: got == [1])
            for conn in list(a._conns.values()):
                conn.close()
            connect = a._connect
            a._connect = lambda peer: (_ for _ in ()).throw(
                ConnectionError("unreachable"))
            time.sleep(0.05)
            note = "x" * (8 << 20)
            for i in (2, 3, 4, 5):          # strand 32 MiB unacked
                a.send("osd.1", Ping(i, note))
            assert sum(len(seg) for _, _, p in a._unacked["osd.1"]
                       for seg in (p if isinstance(p, (list, tuple))
                                   else [p])) > _WQ_HIGH
            stalls = a.perf.get("writeq_stalls")
            held = []

            def watched(conn):
                held.append(a._plock("osd.1")._is_owned())
                real(conn)
            _Conn.await_budget = watched
            a._connect = connect
            a.send("osd.1", Ping(6))        # redials, replays 2..6
            assert a.flush("osd.1", timeout=30)
            assert wait_for(lambda: got == [1, 2, 3, 4, 5, 6]), got
            assert a.perf.get("replayed") >= 4
            assert not any(held)
            assert a.perf.get("writeq_stalls") == stalls
        finally:
            _Conn.await_budget = real
            a.shutdown()
            b.shutdown()
