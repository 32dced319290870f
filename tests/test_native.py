"""Native libec_tpu.so tests: build, ABI entry point, bit-exactness of
the C++ codec vs the Python/JAX field (same 0x11D tables, same
reed_sol_van construction), crc32c parity, round-trips."""

import numpy as np
import pytest

try:
    from ceph_tpu import native
    native.lib()
    HAVE_NATIVE = True
except Exception as e:  # pragma: no cover - toolchain missing
    HAVE_NATIVE = False
    REASON = str(e)

pytestmark = pytest.mark.skipif(not HAVE_NATIVE,
                                reason="native toolchain unavailable")


def test_version_and_entry_symbol():
    assert "gf256" in native.version()
    assert native.erasure_code_init("tpu") == 0
    assert native.lib().ec_registered_plugin() == b"tpu"


def test_matrix_matches_python_construction():
    from ceph_tpu.ec.matrices import reed_sol_van_matrix
    from ceph_tpu.native import NativeReedSolomon
    for k, m in ((4, 2), (8, 3), (6, 4)):
        nc = NativeReedSolomon({"k": str(k), "m": str(m)})
        np.testing.assert_array_equal(nc.matrix,
                                      reed_sol_van_matrix(k, m),
                                      err_msg=f"k={k} m={m}")


def test_encode_matches_python_oracle():
    from ceph_tpu.gf.numpy_ref import encode_ref
    from ceph_tpu.native import NativeReedSolomon
    nc = NativeReedSolomon({"k": "4", "m": "2"})
    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(3, 4, 512), dtype=np.uint8)
    np.testing.assert_array_equal(nc.encode_chunks(data),
                                  encode_ref(nc.matrix, data))


def test_decode_all_double_erasures():
    from itertools import combinations

    from ceph_tpu.native import NativeReedSolomon
    nc = NativeReedSolomon({"k": "4", "m": "2"})
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, size=(2, 4, 256), dtype=np.uint8)
    parity = nc.encode_chunks(data)
    full = {i: data[:, i, :] for i in range(4)}
    full.update({4 + j: parity[:, j, :] for j in range(2)})
    for erased in combinations(range(6), 2):
        have = {c: full[c] for c in full if c not in erased}
        rec = nc.decode_chunks(list(erased), have)
        for e in erased:
            np.testing.assert_array_equal(rec[e], full[e], err_msg=str(erased))


def test_injected_matrix_technique():
    from ceph_tpu.ec.matrices import coding_matrix
    from ceph_tpu.native import NativeReedSolomon
    nc = NativeReedSolomon({"k": "4", "m": "3", "technique": "cauchy_good"})
    np.testing.assert_array_equal(nc.matrix,
                                  coding_matrix("cauchy_good", 4, 3))
    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(1, 4, 128), dtype=np.uint8)
    parity = nc.encode_chunks(data)
    rec = nc.decode_chunks([0, 1, 2], {3: data[:, 3], 4: parity[:, 0],
                                       5: parity[:, 1], 6: parity[:, 2]})
    for e in range(3):
        np.testing.assert_array_equal(rec[e], data[:, e])


def test_registry_integration():
    from ceph_tpu.ec.registry import factory
    import ceph_tpu.native  # noqa: F401 - registers the plugin
    coder = factory("plugin=native k=4 m=2")
    rng = np.random.default_rng(3)
    obj = rng.integers(0, 256, size=3000, dtype=np.uint8).tobytes()
    chunks = coder.encode(list(range(6)), obj)
    rec = coder.decode_concat({c: chunks[c] for c in (1, 2, 4, 5)},
                              object_size=3000)
    assert rec.tobytes() == obj


def test_native_matches_jax_kernels():
    from ceph_tpu.native import NativeReedSolomon
    from ceph_tpu.ops.rs_kernels import make_encoder
    nc = NativeReedSolomon({"k": "8", "m": "3"})
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, size=(2, 8, 1024), dtype=np.uint8)
    jax_out = np.asarray(make_encoder(nc.matrix)(data))
    np.testing.assert_array_equal(nc.encode_chunks(data), jax_out)


def test_native_crc32c_matches_reference():
    from ceph_tpu.csum.reference import ceph_crc32c
    rng = np.random.default_rng(5)
    for n in (0, 1, 7, 100, 4096):
        buf = rng.integers(0, 256, size=n, dtype=np.uint8)
        assert native.native_crc32c(0xFFFFFFFF, buf) == \
            ceph_crc32c(0xFFFFFFFF, buf), n
    # chaining
    a, b = rng.integers(0, 256, size=(2, 50), dtype=np.uint8)
    step = native.native_crc32c(native.native_crc32c(0xFFFFFFFF, a), b)
    assert step == ceph_crc32c(0xFFFFFFFF, np.concatenate([a, b]))


def test_bad_geometry_rejected():
    from ceph_tpu.native import NativeReedSolomon
    with pytest.raises(ValueError):
        NativeReedSolomon({"k": "200", "m": "100"})


class TestRuntimeIPC:
    """The shim -> TPU-runtime forwarding hop (SURVEY §7 step 9): with
    a live ECRuntimeServer the flat C API dispatches over the Unix
    socket; without one it falls back to the CPU codec, bit-identical
    either way."""

    def _with_server(self):
        import os
        import tempfile

        from ceph_tpu.native.server import ECRuntimeServer
        path = os.path.join(tempfile.mkdtemp(), "ec.sock")
        return path, ECRuntimeServer(path)

    def test_encode_decode_roundtrip_via_runtime(self):
        import numpy as np

        from ceph_tpu.native import (NativeReedSolomon, runtime_ping,
                                     set_runtime_socket)
        path, srv = self._with_server()
        with srv:
            set_runtime_socket(path)
            try:
                assert runtime_ping()
                coder = NativeReedSolomon({"k": "4", "m": "2"})
                rng = np.random.default_rng(0)
                d = rng.integers(0, 256, (3, 4, 512), np.uint8)
                parity = coder.encode_chunks(d)
                assert srv.requests_handled >= 2  # ping + encode
                full = np.concatenate([d, parity], axis=1)
                rec = coder.decode_chunks(
                    [1, 4], {i: full[:, i] for i in (0, 2, 3, 5)})
                assert (rec[1] == d[:, 1]).all()
                assert (rec[4] == parity[:, 0]).all()
                served = srv.requests_handled
                assert served >= 3
                # CPU fallback produces the SAME bytes
                set_runtime_socket(None)
                assert (coder.encode_chunks(d) == parity).all()
                assert srv.requests_handled == served
            finally:
                set_runtime_socket(None)

    def test_dead_socket_falls_back_to_cpu(self):
        import numpy as np

        from ceph_tpu.native import NativeReedSolomon, set_runtime_socket
        set_runtime_socket("/nonexistent/ec.sock")
        try:
            coder = NativeReedSolomon({"k": "3", "m": "2"})
            rng = np.random.default_rng(1)
            d = rng.integers(0, 256, (2, 3, 256), np.uint8)
            parity = coder.encode_chunks(d)      # silently CPU
            set_runtime_socket(None)
            assert (coder.encode_chunks(d) == parity).all()
        finally:
            set_runtime_socket(None)

    def test_server_rejects_garbage_and_survives(self):
        import socket
        import struct

        from ceph_tpu.native import (NativeReedSolomon, runtime_ping,
                                     set_runtime_socket)
        path, srv = self._with_server()
        with srv:
            # garbage frame: server answers an error and keeps serving
            c = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            c.connect(path)
            c.sendall(struct.pack("<I", 8) + b"garbage!")
            ln = struct.unpack("<I", c.recv(4))[0]
            body = c.recv(ln)
            assert body[4] == 1  # status: error
            c.close()
            assert srv.errors == 1
            set_runtime_socket(path)
            try:
                assert runtime_ping()
            finally:
                set_runtime_socket(None)


def test_sanitizer_harness_clean():
    """ASAN+UBSAN build + standalone ABI harness must pass (SURVEY §5
    sanitizers; skipped if the toolchain lacks libasan)."""
    import shutil
    import subprocess
    if not shutil.which("g++"):
        import pytest
        pytest.skip("no g++")
    r = subprocess.run(["make", "-C", "native", "sancheck"],
                       capture_output=True, text=True, timeout=300)
    if "asan" in (r.stdout + r.stderr).lower() and r.returncode != 0 \
            and "cannot find" in (r.stdout + r.stderr):
        import pytest
        pytest.skip("libasan unavailable")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sancheck OK" in r.stdout


class TestAes256Gcm:
    """The native codec's AES-256-GCM (the secure messenger's cipher
    when the `cryptography` wheel is absent): pinned to the NIST GCM
    test vectors — the same algorithm the wheel implements, so the two
    paths are interchangeable on the wire."""

    def _seal(self, key, nonce, aad, plain):
        from ceph_tpu import native
        if not native.aes256gcm_supported():
            pytest.skip("no AES-NI/PCLMUL or native lib not built")
        return native.aes256gcm_seal(key, nonce, plain, aad)

    def test_nist_case_13_empty(self):
        assert self._seal(bytes(32), bytes(12), b"", b"").hex() == \
            "530f8afbc74536b9a963b4f1c4cb738b"

    def test_nist_case_14_one_block(self):
        assert self._seal(bytes(32), bytes(12), b"", bytes(16)).hex() \
            == ("cea7403d4d606b6e074ec5d3baf39d18"
                "d0d1c8a799996bf0265b98b5d48ab919")

    def test_nist_case_15_four_blocks(self):
        # 64-byte plaintext: exercises the aggregated 4-block GHASH
        key = bytes.fromhex("feffe9928665731c6d6a8f9467308308"
                            "feffe9928665731c6d6a8f9467308308")
        iv = bytes.fromhex("cafebabefacedbaddecaf888")
        p = bytes.fromhex(
            "d9313225f88406e5a55909c5aff5269a86a7a9531534f7da2e4c303d"
            "8a318a721c3c0c95956809532fcf0e2449a6b525b16aedf5aa0de657"
            "ba637b391aafd255")
        want = ("522dc1f099567d07f47f37a32a84427d643a8cdcbfe5c0c97598"
                "a2bd2555d1aa8cb08e48590dbb3da7b08b1056828838c5f61e639"
                "3ba7a0abcc9f662898015adb094dac5d93471bdec1a502270e3cc"
                "6c")
        assert self._seal(key, iv, b"", p).hex() == want

    def test_nist_case_16_with_aad(self):
        key = bytes.fromhex("feffe9928665731c6d6a8f9467308308"
                            "feffe9928665731c6d6a8f9467308308")
        iv = bytes.fromhex("cafebabefacedbaddecaf888")
        p = bytes.fromhex(
            "d9313225f88406e5a55909c5aff5269a86a7a9531534f7da2e4c303d"
            "8a318a721c3c0c95956809532fcf0e2449a6b525b16aedf5aa0de657"
            "ba637b39")
        aad = bytes.fromhex("feedfacedeadbeeffeedfacedeadbeef"
                            "abaddad2")
        want = ("522dc1f099567d07f47f37a32a84427d643a8cdcbfe5c0c97598"
                "a2bd2555d1aa8cb08e48590dbb3da7b08b1056828838c5f61e639"
                "3ba7a0abcc9f66276fc6ece0f4e1768cddf8853bb2d551b")
        assert self._seal(key, iv, aad, p).hex() == want

    def test_roundtrip_and_tamper_all_block_boundaries(self):
        from ceph_tpu import native
        if not native.aes256gcm_supported():
            pytest.skip("no AES-NI/PCLMUL or native lib not built")
        import os as _os
        key, nonce = _os.urandom(32), _os.urandom(12)
        for n in (0, 1, 15, 16, 17, 63, 64, 65, 4096):
            p = _os.urandom(n)
            blob = native.aes256gcm_seal(key, nonce, p, b"aad")
            assert native.aes256gcm_open(key, nonce, blob, b"aad") == p
            if n:
                bad = bytearray(blob)
                bad[n // 2] ^= 1
                with pytest.raises(ValueError):
                    native.aes256gcm_open(key, nonce, bytes(bad),
                                          b"aad")
            # wrong aad refuses too
            with pytest.raises(ValueError):
                native.aes256gcm_open(key, nonce, blob, b"other")

    def test_aead_class_uses_native_and_roundtrips(self):
        from ceph_tpu import native
        if not native.aes256gcm_supported():
            pytest.skip("no AES-NI/PCLMUL or native lib not built")
        try:
            import cryptography  # noqa: F401 — wheel wins if present
            pytest.skip("cryptography wheel present")
        except ImportError:
            pass
        import os as _os
        from ceph_tpu.auth.aead import AEAD, InvalidTag
        box = AEAD(_os.urandom(32))
        assert box._native is not None
        n = _os.urandom(12)
        ct = box.encrypt(n, b"payload", b"aad")
        assert box.decrypt(n, ct, b"aad") == b"payload"
        with pytest.raises(InvalidTag):
            box.decrypt(n, ct[:-1] + bytes([ct[-1] ^ 1]), b"aad")
        # segment-list input stages to the same bytes as joined input
        n2 = _os.urandom(12)
        assert box.encrypt(n2, [b"pay", b"load"], b"aad") == \
            box.encrypt(n2, b"payload", b"aad")
