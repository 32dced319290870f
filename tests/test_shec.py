"""SHEC plugin tests — mirrors the reference's TestErasureCodeShec*.cc
pattern: every <=c erasure subset must round-trip; recovery reads must
beat RS's k for local failures."""

from itertools import combinations

import numpy as np
import pytest

from ceph_tpu.ec.registry import factory
from ceph_tpu.ec.shec import Shec, gf_express


def make(k, m, c, **extra):
    prof = {"k": str(k), "m": str(m), "c": str(c), "impl": "ref"}
    prof.update({key: str(v) for key, v in extra.items()})
    return Shec(prof)


def rand_chunks(coder, B=2, L=256, seed=0):
    rng = np.random.default_rng(seed)
    data = rng.integers(0, 256, size=(B, coder.k, L), dtype=np.uint8)
    parity = coder.encode_chunks(data)
    full = {i: data[:, i, :] for i in range(coder.k)}
    full.update({coder.k + j: parity[:, j, :] for j in range(coder.m)})
    return full


def test_registry_and_default_profile():
    c = factory("plugin=shec k=4 m=3 c=2")
    assert isinstance(c, Shec)
    assert c.l == 3  # ceil(4*2/3)
    assert len(c.windows) == 3


def test_windows_shingle_and_cover():
    c = make(6, 3, 2)
    assert c.l == 4
    cover = np.zeros(6, int)
    for w in c.windows:
        for j in w:
            cover[j] += 1
    assert (cover >= c.c).all()  # every chunk covered at least c times


def test_gf_express_basic():
    A = np.array([[1, 0, 0], [0, 1, 0]], np.uint8)
    B = np.array([[1, 1, 0]], np.uint8)
    X = gf_express(A, B)
    assert X is not None and X.tolist() == [[1, 1]]
    assert gf_express(A, np.array([[0, 0, 1]], np.uint8)) is None


@pytest.mark.parametrize("k,m,c", [(4, 3, 2), (6, 3, 2), (8, 4, 3), (5, 2, 1)])
def test_all_c_erasure_subsets_roundtrip(k, m, c):
    coder = make(k, m, c)
    full = rand_chunks(coder)
    n = k + m
    for r in range(1, c + 1):
        for erased in combinations(range(n), r):
            avail = [i for i in range(n) if i not in erased]
            need = coder.minimum_to_decode(list(erased), avail)
            rec = coder.decode_chunks(list(erased),
                                      {s: full[s] for s in need})
            for e in erased:
                np.testing.assert_array_equal(rec[e], full[e],
                                              err_msg=f"{erased}")


def test_recovery_reads_beat_rs():
    # single data-chunk repair must read fewer chunks than RS's k
    coder = make(8, 4, 3)  # l = ceil(24/4) = 6
    reads = [coder.recovery_read_count(j) for j in range(coder.k)]
    assert max(reads) <= coder.l  # window parity + window-1 data
    assert max(reads) < coder.k


def test_minimum_to_decode_prefers_local_group():
    coder = make(6, 3, 2)
    # chunk 0 sits in parity p0's window {0,1,2,3} (and p2's wrap window)
    need = coder.minimum_to_decode([0], list(range(1, 9)))
    assert len(need) <= coder.l
    assert any(p >= coder.k for p in need)  # uses a parity


def test_non_mds_beyond_c_may_fail_but_never_corrupts():
    coder = make(4, 3, 2)
    full = rand_chunks(coder)
    n = 7
    ok = bad = 0
    for erased in combinations(range(n), 3):  # c+1 failures
        avail = [i for i in range(n) if i not in erased]
        try:
            need = coder.minimum_to_decode(list(erased), avail)
            rec = coder.decode_chunks(list(erased), {s: full[s] for s in need})
            for e in erased:
                np.testing.assert_array_equal(rec[e], full[e])
            ok += 1
        except ValueError:
            bad += 1
    assert ok + bad == 35
    assert ok > 0  # some triple failures are recoverable...
    # (non-MDS: not required that all are)


def test_bad_profiles():
    with pytest.raises(ValueError):
        make(4, 3, 4)  # c > m
    with pytest.raises(ValueError):
        make(2, 3, 2)  # m > k


def test_full_object_api():
    coder = make(4, 3, 2)
    rng = np.random.default_rng(5)
    obj = rng.integers(0, 256, size=3000, dtype=np.uint8).tobytes()
    chunks = coder.encode(list(range(7)), obj)
    rec = coder.decode_concat({c: chunks[c] for c in (0, 1, 3, 4, 5, 6)},
                              object_size=3000)
    assert rec.tobytes() == obj


def test_want_available_passthrough():
    coder = make(4, 3, 2)
    assert coder.minimum_to_decode([1, 2], range(7)) == {1, 2}


def test_device_impl_matches_ref():
    ref = make(4, 3, 2)
    dev = Shec({"k": "4", "m": "3", "c": "2"})
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, size=(2, 4, 256), dtype=np.uint8)
    np.testing.assert_array_equal(ref.encode_chunks(data),
                                  dev.encode_chunks(data))


def test_batch_decoder_fused_path():
    """SHEC inherits the derived static-matrix fast path (base-class
    batch_decoder via ec/linearize): bit-exact vs decode_chunks for
    single and double losses."""
    import numpy as np
    from ceph_tpu.ec.registry import factory
    coder = factory("plugin=shec k=4 m=3 c=2")
    n = coder.get_chunk_count()
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (3, 4, 512), np.uint8)
    parity = np.asarray(coder.encode_chunks(data))
    full = np.concatenate([data, parity], axis=1)
    for lost in ([2], [0, 4]):
        avail = [i for i in range(n) if i not in lost]
        helpers = sorted(coder.minimum_to_decode(lost, avail))
        fn = coder.batch_decoder(lost, helpers)
        assert fn is not None
        got = np.asarray(fn(full[:, helpers]))
        np.testing.assert_array_equal(got, full[:, lost])
