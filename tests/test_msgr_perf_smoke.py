"""Zero-copy regression guard (tier-1-safe: no throughput threshold).

The scatter-gather framing path funnels every payload materialization
through ONE choke point — messenger._flatten. A counting shim over it
proves the O(1)-copies contract structurally: crc-mode frames make
ZERO payload copies between Encoder.blob_ref and sendmsg, secure mode
stages exactly ONE contiguous buffer per frame (the AEAD input), and
the Encoder really does carry caller buffers by reference."""

import struct

import pytest

from ceph_tpu.msgr import messenger as M
from ceph_tpu.utils.encoding import Decoder, Encoder
# bare import, matching how pytest imports test_msgr.py itself (no tests/
# __init__.py): a "tests.test_msgr" spelling would materialize a SECOND
# module object, re-run @register_message, and die on frame type 0x70
from test_msgr import Ping, pair, wait_for


class _CountingFlatten:
    """The counting-allocator shim: wraps messenger._flatten and
    counts every payload materialization (with byte totals)."""

    def __init__(self):
        self.calls = 0
        self.bytes = 0
        self._orig = M._flatten

    def __call__(self, payload):
        out = self._orig(payload)
        self.calls += 1
        self.bytes += len(out)
        return out


@pytest.fixture
def flatten_counter(monkeypatch):
    shim = _CountingFlatten()
    monkeypatch.setattr(M, "_flatten", shim)
    return shim


class TestZeroCopy:
    def test_encoder_blob_ref_is_zero_copy(self):
        big = b"D" * 65536
        e = Encoder()
        e.start(1, 1).u64(1).blob_ref(big).finish()
        segs = e.segments()
        refs = [s for s in segs
                if isinstance(s, memoryview) and s.obj is big]
        assert refs, "payload buffer was copied, not referenced"
        # and the joined form still equals the copying encoder's bytes
        e2 = Encoder()
        e2.start(1, 1).u64(1).blob(big).finish()
        assert b"".join(segs) == e2.bytes()

    def test_crc_mode_zero_payload_copies(self, flatten_counter):
        a, b = pair()
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(0))      # establish the connection
            assert wait_for(lambda: got == [0])
            flatten_counter.calls = 0
            flatten_counter.bytes = 0
            n = 8
            for i in range(1, n + 1):
                a.send("osd.1", Ping(i, note="P" * 65536))
            assert wait_for(lambda: len(got) == n + 1), got
            # O(1) per frame means ZERO here: crc mode gather-writes
            # the segments and runs the crc as a seeded continuation
            assert flatten_counter.calls == 0, (
                f"crc-mode framing flattened payloads "
                f"{flatten_counter.calls} times "
                f"({flatten_counter.bytes} bytes copied)")
        finally:
            a.shutdown()
            b.shutdown()

    def test_secure_mode_stages_exactly_one_buffer_per_frame(
            self, flatten_counter):
        secret = b"0123456789abcdef0123456789abcdef"
        a, b = pair(secret_a=secret, secret_b=secret)
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(0))
            assert wait_for(lambda: got == [0])
            flatten_counter.calls = 0
            n = 6
            for i in range(1, n + 1):
                a.send("osd.1", Ping(i, note="S" * 32768))
            assert wait_for(lambda: len(got) == n + 1), got
            # one staged buffer per data frame (the AEAD seal input);
            # acks/replies on the reverse path don't run through this
            # messenger's send_frame, but the flusher's acks on THIS
            # side might — allow n..n+acks, never 2n (a second copy
            # per frame would double it)
            assert n <= flatten_counter.calls < 2 * n, \
                flatten_counter.calls
        finally:
            a.shutdown()
            b.shutdown()

    def test_sealed_frame_queues_as_header_nonce_ciphertext(
            self, monkeypatch):
        """A sealed frame reaches the write queue as [hdr, nonce, ct]:
        the ciphertext is the very object the cipher returned, never
        copied again to join it to its nonce, and the cipher's input
        is still the one staged buffer."""
        secret = b"0123456789abcdef0123456789abcdef"
        a, b = pair(secret_a=secret, secret_b=secret)
        try:
            got = []
            b.register_handler(Ping.type_id,
                               lambda p, m: got.append(m.stamp))
            a.send("osd.1", Ping(0))
            assert wait_for(lambda: got == [0])
            conn = a._conns["osd.1"]
            plains, cts, queued = [], [], []
            encrypt = conn.box._gcm.encrypt
            enqueue = conn._enqueue_locked

            def sealing(nonce, plain, aad):
                plains.append(plain)
                cts.append(encrypt(nonce, plain, aad))
                return cts[-1]

            def queueing(parts):
                queued.append(list(parts))
                return enqueue(parts)
            monkeypatch.setattr(conn.box._gcm, "encrypt", sealing)
            monkeypatch.setattr(conn, "_enqueue_locked", queueing)
            a.send("osd.1", Ping(1, note="S" * 65536))
            assert wait_for(lambda: got == [0, 1])
            assert len(cts) == 1 and len(queued) == 1, (cts, queued)
            assert len(queued[0]) == 3
            hdr, nonce, ct = queued[0]
            assert ct is cts[0]
            assert len(nonce) == 12
            assert struct.unpack("<I", hdr) == (len(nonce) + len(ct),)
            # the cipher's input: the one staged buffer, whole
            assert type(plains[0]) is bytes
            assert len(plains[0]) == len(ct) - 16
        finally:
            a.shutdown()
            b.shutdown()

    def test_decoder_wraps_views_without_copy(self):
        buf = bytearray(b"\x05\x00\x00\x00hello")
        d = Decoder(memoryview(buf))
        assert d.blob() == b"hello"
        # zero-copy wrap: mutating the backing store is visible
        d2 = Decoder(memoryview(buf))
        buf[4] = ord("H")
        assert d2.blob() == b"Hello"
