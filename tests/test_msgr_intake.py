"""Frame intake: how the read side turns socket bytes into frames.

Short frames are cut out of the connection's staging buffer; a bulk
frame (at least _RX_DIRECT_MIN body bytes) is read straight into a
buffer of its own, which a secure connection keeps for its next bulk
frame. These tests hold the read side to the wire (every split of a
frame across reads delivers the same messages), to the aliasing rules
(a view a handler keeps never changes under it), and to the counters
that say the direct path engages."""

import functools
import select
import socket
import struct
import threading

import pytest

from ceph_tpu.msgr import messenger as M
from ceph_tpu.msgr.messenger import (_Conn, _SecureBox, Message,
                                     Messenger, register_message)
from ceph_tpu.utils.encoding import Decoder, Encoder
# bare import, matching how pytest imports test_msgr.py itself (no tests/
# __init__.py): a "tests.test_msgr" spelling would materialize a SECOND
# module object, re-run @register_message, and die on frame type 0x70
from test_msgr import Ping, pair, wait_for

SECRET = b"0123456789abcdef0123456789abcdef"
KEY = b"K" * 32
MODES = ["crc", "secure"]


@register_message
class Bulk(Message):
    """A blob the receiver keeps as a zero-copy view of the payload:
    what a handler holding on to the bytes it was given would see."""

    type_id = 0x7A

    def __init__(self, tag: int, data):
        self.tag = tag
        self.data = data

    def encode_payload(self, e: Encoder) -> None:
        e.start(1, 1).u64(self.tag).blob_ref(self.data).finish()

    @classmethod
    def decode_payload(cls, d: Decoder) -> "Bulk":
        d.start(1)
        tag = d.u64()
        m = cls(tag, d._take(d.u32()))
        d.finish()
        return m


def pattern(size: int, tag: int) -> bytes:
    """`size` bytes that differ from every other tag's at every
    stride of 251 (a prime, so no power-of-two buffer lines up)."""
    unit = bytes((tag * 7 + i) & 0xFF for i in range(251))
    return (unit * (size // 251 + 1))[:size]


def payload_of(msg: Message) -> list:
    e = Encoder()
    msg.encode_payload(e)
    return e.segments()


def wire(mode: str, msgs, seq0: int = 1) -> bytes:
    """The wire bytes the real send path writes for `msgs`, in order,
    as one sending connection (one nonce counter)."""
    a, b = socket.socketpair()
    box = _SecureBox(KEY, b"cli\x00", b"srv\x00") \
        if mode == "secure" else None
    got = bytearray()

    def drain():
        while True:
            chunk = b.recv(1 << 20)
            if not chunk:
                return
            got.extend(chunk)
    t = threading.Thread(target=drain, daemon=True)
    t.start()
    try:
        conn = _Conn(a, box=box)
        for i, m in enumerate(msgs):
            conn.send_frame(seq0 + i, m.type_id, payload_of(m))
        a.shutdown(socket.SHUT_WR)
        t.join(30)
    finally:
        a.close()
        b.close()
    return bytes(got)


class Intake:
    """One receiving connection driven by hand: bytes go in through a
    socketpair, and the messenger's own read path parses them."""

    PEER = "osd.0"

    def __init__(self, mode: str):
        self.m = Messenger("osd.1",
                           secret=SECRET if mode == "secure" else None)
        self.w, r = socket.socketpair()
        r.setblocking(False)
        box = _SecureBox(KEY, b"srv\x00", b"cli\x00") \
            if mode == "secure" else None
        self.conn = _Conn(r, box=box, perf=self.m.perf)
        self.got = []
        self.m.register_handler(Bulk.type_id,
                                lambda p, m: self.got.append(m))
        self.m.register_handler(Ping.type_id,
                                lambda p, m: self.got.append(m))

    def drain(self) -> None:
        r = self.conn.sock
        while select.select([r], [], [], 0)[0]:
            self.m._conn_read(self.PEER, self.conn)

    def feed(self, data: bytes, cuts=()) -> None:
        """Write `data` split at `cuts` (and in pieces of at most
        64 KiB, so a socket buffer never fills), reading after each."""
        edges = sorted({0, len(data), *cuts})
        for lo, hi in zip(edges, edges[1:]):
            for off in range(lo, hi, 1 << 16):
                self.w.sendall(data[off:min(hi, off + (1 << 16))])
                self.drain()

    def counter(self, key: str) -> int:
        return self.m.perf.get(key)

    def close(self) -> None:
        self.w.close()
        self.conn.sock.close()
        self.m.shutdown()


@pytest.fixture(params=MODES)
def intake(request):
    it = Intake(request.param)
    it.mode = request.param
    yield it
    it.close()


def _cuts(mode: str, start: int, flen: int) -> dict:
    """Split points of one frame at wire offset `start`, `flen` bytes
    long, by boundary class: inside and after the length header, inside
    and after the nonce (the seq field in crc mode), inside the body,
    inside and before the tail (crc word or GCM tag)."""
    tail = 4 if mode == "crc" else 16
    return {
        "len_1": [start + 1], "len_3": [start + 3],
        "after_len": [start + 4],
        "in_nonce": [start + 4 + 6], "after_nonce": [start + 4 + 12],
        "in_body": [start + flen // 2 + 1],
        "before_tail": [start + flen - tail],
        "in_tail": [start + flen - tail + 2],
        "last_byte": [start + flen - 1],
        "every": [start + c for c in (1, 3, 4, 10, 16, 17, 4099,
                                      flen // 2, flen - tail,
                                      flen - 2, flen - 1)],
    }


CUTS = list(_cuts("crc", 0, 100))


@functools.lru_cache(maxsize=None)
def _sandwich(mode: str):
    """A 4 MiB bulk frame between two short ones: (wire bytes, the
    bulk payload, the bulk frame's offset and length on the wire)."""
    big = pattern(4 << 20, 3)
    msgs = [Ping(1, "before"), Bulk(2, big), Ping(3, "after")]
    first = len(wire(mode, msgs[:1]))
    flen = len(wire(mode, msgs[1:2], seq0=2))
    return wire(mode, msgs), big, first, flen


class TestSplitReads:
    @pytest.mark.parametrize("cut", CUTS)
    def test_bulk_frame_split_at_every_boundary(self, intake, cut):
        data, big, first, flen = _sandwich(intake.mode)
        intake.feed(data, _cuts(intake.mode, first, flen)[cut])
        assert [m.stamp if isinstance(m, Ping) else m.tag
                for m in intake.got] == [1, 2, 3]
        assert bytes(intake.got[1].data) == big
        assert intake.counter("rx_frames_direct") == 1
        assert intake.conn._rx_frame is None and intake.conn._rx_len == 0

    @pytest.mark.parametrize("size", [80 << 10, 4 << 20],
                             ids=["whole_in_stage", "read_direct"])
    def test_short_frames_after_a_bulk_frame_in_one_read(
            self, intake, size):
        big = pattern(size, 5)
        msgs = [Bulk(1, big)] + [Ping(2 + i, "hb") for i in range(6)]
        data = wire(intake.mode, msgs)
        if size < M._RX_STAGE:
            # the bulk frame and all six short ones in ONE read
            assert len(data) < M._RX_STAGE
            intake.w.sendall(data)
            intake.m._conn_read(Intake.PEER, intake.conn)
        else:
            intake.feed(data)
        assert [getattr(m, "tag", getattr(m, "stamp", None))
                for m in intake.got] == list(range(1, 8))
        assert bytes(intake.got[0].data) == big
        assert intake.counter("rx_frames_direct") == 1

    def test_short_frames_take_the_staging_path(self, intake):
        msgs = [Ping(i, "n" * 4000) for i in range(1, 41)]
        intake.feed(wire(intake.mode, msgs), cuts=[4097, 70001])
        assert [m.stamp for m in intake.got] == list(range(1, 41))
        assert intake.counter("rx_frames_direct") == 0
        assert intake.counter("rx_buffer_reused") == 0


class TestTornAndBadFrames:
    @pytest.mark.parametrize("keep", ["header", "short", "bulk_stage",
                                      "bulk_direct"])
    def test_torn_frame_at_shutdown_raises(self, intake, keep):
        short = wire(intake.mode, [Ping(1, "x" * 100)])
        bulk = wire(intake.mode, [Bulk(1, pattern(1 << 20, 1))])
        data = {"header": short[:3], "short": short[:-1],
                "bulk_stage": bulk[:1000],
                "bulk_direct": bulk[:-5]}[keep]
        intake.feed(data)
        assert intake.got == []
        intake.w.shutdown(socket.SHUT_WR)
        with pytest.raises(ConnectionError, match="peer closed"):
            intake.m._conn_read(Intake.PEER, intake.conn)

    @pytest.mark.parametrize("blen", [5, (1 << 26) + 1],
                             ids=["under_floor", "over_cap"])
    def test_bad_length_refused_before_any_allocation(
            self, intake, monkeypatch, blen):
        allocs = []
        orig = M._rx_alloc
        intake.m._conn_read(Intake.PEER, intake.conn)  # staging buffer
        monkeypatch.setattr(M, "_rx_alloc",
                            lambda n: allocs.append(n) or orig(n))
        intake.w.sendall(struct.pack("<I", blen) + bytes(64))
        with pytest.raises(ConnectionError, match="bad frame length"):
            intake.m._conn_read(Intake.PEER, intake.conn)
        assert allocs == []


#: a bulk frame the size of a sub-op's, where the size is not under
#: test (crc mode runs the python crc where the native library is not
#: built)
SIZE = 512 << 10


def _bulk_pair(mode: str):
    secret = SECRET if mode == "secure" else None
    a, b = pair(secret_a=secret, secret_b=secret)
    got = []
    b.register_handler(Bulk.type_id, lambda p, m: got.append(m))
    return a, b, got


def _rx(m: Messenger, key: str) -> int:
    return m.perf.get(key)


class TestBuffers:
    @pytest.mark.parametrize("mode", MODES)
    def test_kept_blob_survives_the_next_bulk_frames(self, mode):
        """A handler keeps a view of its payload across the next 8
        bulk frames of its connection: a secure connection reads them
        into the buffer it kept, a crc one into new buffers; the view
        never changes under the handler in either."""
        a, b, got = _bulk_pair(mode)
        try:
            first = pattern(SIZE, 1)
            a.send("osd.1", Bulk(1, first))
            assert wait_for(lambda: len(got) == 1)
            kept = got[0].data
            assert bytes(kept) == first
            reused0 = _rx(b, "rx_buffer_reused")
            for i in range(2, 10):
                a.send("osd.1", Bulk(i, pattern(SIZE, i)))
            assert wait_for(lambda: len(got) == 9, timeout=30)
            assert bytes(kept) == first
            for m in got[1:]:
                assert bytes(m.data) == pattern(SIZE, m.tag)
            reused = _rx(b, "rx_buffer_reused") - reused0
            assert reused == (8 if mode == "secure" else 0)
        finally:
            a.shutdown()
            b.shutdown()

    def test_crc_mode_never_reuses_a_buffer(self):
        a, b, got = _bulk_pair("crc")
        try:
            for i in range(16):
                a.send("osd.1", Bulk(i, pattern(SIZE, i)))
            assert wait_for(lambda: len(got) == 16, timeout=30)
            assert _rx(b, "rx_frames_direct") == 16
            assert _rx(b, "rx_buffer_reused") == 0
            assert all(c._rx_keep is None for c in b._conns.values())
        finally:
            a.shutdown()
            b.shutdown()

    def test_counters_on_a_secure_pair(self):
        """16 bulk frames of 4 MiB each read direct, all but the first
        into the kept buffer; 16 heartbeat-size frames raise neither."""
        a, b, got = _bulk_pair("secure")
        hb = []
        b.register_handler(Ping.type_id, lambda p, m: hb.append(m))
        try:
            a.send("osd.1", Ping(0))
            assert wait_for(lambda: len(hb) == 1)
            d0 = _rx(b, "rx_frames_direct")
            r0 = _rx(b, "rx_buffer_reused")
            data = pattern(4 << 20, 9)
            for i in range(16):
                a.send("osd.1", Bulk(i, data))
            assert wait_for(lambda: len(got) == 16, timeout=60)
            assert _rx(b, "rx_frames_direct") - d0 == 16
            assert _rx(b, "rx_buffer_reused") - r0 >= 15
            d1 = _rx(b, "rx_frames_direct")
            r1 = _rx(b, "rx_buffer_reused")
            for i in range(1, 17):
                a.send("osd.1", Ping(i, "hb"))
            assert wait_for(lambda: len(hb) == 17)
            assert _rx(b, "rx_frames_direct") == d1
            assert _rx(b, "rx_buffer_reused") == r1
        finally:
            a.shutdown()
            b.shutdown()

    def test_frames_that_creep_up_reuse_the_kept_buffer(self):
        """Frames a few bytes longer each time (an op's name, a sub-op's
        log entries) all land in the buffer the first one left."""
        a, b, got = _bulk_pair("secure")
        try:
            for i in range(8):
                a.send("osd.1", Bulk(i, pattern(SIZE + 25 * i, i)))
            assert wait_for(lambda: len(got) == 8, timeout=30)
            for m in got:
                assert bytes(m.data) == pattern(SIZE + 25 * m.tag, m.tag)
            assert _rx(b, "rx_frames_direct") == 8
            assert _rx(b, "rx_buffer_reused") == 7
        finally:
            a.shutdown()
            b.shutdown()

    def test_frame_over_the_keep_cap_is_read_and_not_kept(self):
        a, b, got = _bulk_pair("secure")
        try:
            small, huge = pattern(4 << 20, 1), pattern(M._RX_KEEP_MAX + 1, 2)
            a.send("osd.1", Bulk(1, small))
            assert wait_for(lambda: len(got) == 1, timeout=30)
            conn = b._conns["osd.0"]
            kept = conn._rx_keep
            assert kept is not None and len(kept) < M._RX_KEEP_MAX
            r0 = _rx(b, "rx_buffer_reused")
            a.send("osd.1", Bulk(2, huge))
            assert wait_for(lambda: len(got) == 2, timeout=60)
            assert bytes(got[1].data) == huge
            assert conn._rx_keep is kept       # not replaced by it
            assert _rx(b, "rx_buffer_reused") == r0
            a.send("osd.1", Bulk(3, small))
            assert wait_for(lambda: len(got) == 3, timeout=30)
            assert bytes(got[2].data) == small
            assert _rx(b, "rx_buffer_reused") == r0 + 1
            assert _rx(b, "rx_frames_direct") == 3
        finally:
            a.shutdown()
            b.shutdown()
