"""Clay plugin tests — mirrors the reference's TestErasureCodeClay.cc
pattern (encode random buffers, erase every <=m subset, decode,
byte-compare) plus the MSR repair-bandwidth properties."""

import numpy as np
import pytest

from ceph_tpu.ec.clay import Clay
from ceph_tpu.ec.registry import factory
from itertools import combinations


def make(k, m, d=None, **extra):
    prof = {"k": str(k), "m": str(m), "impl": "ref"}
    if d is not None:
        prof["d"] = str(d)
    prof.update({key: str(v) for key, v in extra.items()})
    return Clay(prof)


def rand_chunks(coder, B=2, seed=0):
    rng = np.random.default_rng(seed)
    L = coder.get_chunk_size(coder.k * coder.sub_chunk_count * 4)
    data = rng.integers(0, 256, size=(B, coder.k, L), dtype=np.uint8)
    parity = coder.encode_chunks(data)
    full = {i: data[:, i, :] for i in range(coder.k)}
    full.update({coder.k + j: parity[:, j, :] for j in range(coder.m)})
    return full, L


def test_registry():
    c = factory("plugin=clay k=4 m=2 impl=ref")
    assert isinstance(c, Clay)
    assert c.d == 5 and c.q == 2 and c.t == 3
    assert c.get_sub_chunk_count() == 8


def test_geometry_default_d():
    c = make(4, 2)
    assert (c.q, c.t, c.nu) == (2, 3, 0)
    c = make(8, 4, 11)
    assert (c.q, c.t, c.nu) == (4, 3, 0)
    c = make(5, 4, 8)  # k+m=9, q=4 -> t=3, nu=3 virtual nodes
    assert (c.q, c.t, c.nu) == (4, 3, 3)


def test_bad_profiles():
    with pytest.raises(ValueError):
        make(4, 1)
    with pytest.raises(ValueError):
        make(4, 2, d=4)  # d < k+1
    with pytest.raises(ValueError):
        make(4, 2, d=6)  # d > k+m-1
    with pytest.raises(ValueError):
        make(4, 2, gamma=1)


@pytest.mark.parametrize("k,m,d", [(4, 2, 5), (2, 2, 3), (4, 3, 6), (3, 2, 4)])
def test_all_erasure_subsets_roundtrip(k, m, d):
    coder = make(k, m, d)
    full, L = rand_chunks(coder)
    n = k + m
    for r in range(1, m + 1):
        for erased in combinations(range(n), r):
            have = {c: full[c] for c in range(n) if c not in erased}
            rec = coder.decode_chunks(list(erased), have)
            for e in erased:
                np.testing.assert_array_equal(rec[e], full[e], err_msg=f"{erased}")


def test_roundtrip_with_virtual_nodes():
    coder = make(5, 4, 8)  # nu=3
    full, L = rand_chunks(coder)
    for erased in [(0,), (5,), (0, 5), (1, 2, 6, 8), (0, 1, 2, 3)]:
        have = {c: full[c] for c in full if c not in erased}
        rec = coder.decode_chunks(list(erased), have)
        for e in erased:
            np.testing.assert_array_equal(rec[e], full[e], err_msg=f"{erased}")


def test_flagship_geometry_random_erasures():
    coder = make(8, 4, 11)
    full, L = rand_chunks(coder, B=1)
    rng = np.random.default_rng(1)
    for _ in range(6):
        r = int(rng.integers(1, 5))
        erased = tuple(sorted(rng.choice(12, size=r, replace=False).tolist()))
        have = {c: full[c] for c in full if c not in erased}
        rec = coder.decode_chunks(list(erased), have)
        for e in erased:
            np.testing.assert_array_equal(rec[e], full[e], err_msg=f"{erased}")


@pytest.mark.parametrize("k,m,d", [(4, 2, 5), (4, 3, 6), (8, 4, 11)])
def test_repair_single_failure_all_positions(k, m, d):
    coder = make(k, m, d)
    full, L = rand_chunks(coder)
    for failed in range(k + m):
        rebuilt = coder.repair_from_chunks(
            failed, {c: full[c] for c in full if c != failed})
        np.testing.assert_array_equal(rebuilt, full[failed], err_msg=str(failed))


def test_repair_bandwidth_is_msr_optimal():
    # d helpers x beta sub-chunks, beta = subchunks/q -> total read
    # d/(d-k+1) chunk-equivalents, vs k chunks for plain RS.
    k, m, d = 8, 4, 11
    coder = make(k, m, d)
    need = coder.minimum_to_decode_subchunks(0, list(range(1, k + m)))
    assert len(need) == d
    beta = coder.sub_chunk_count // coder.q
    for h, planes in need.items():
        assert len(planes) == beta
    read_fraction = d * beta / (k * coder.sub_chunk_count)
    assert read_fraction == pytest.approx(d / (k * (d - k + 1)))
    assert read_fraction < 0.5  # strictly less than half of RS's k-chunk read


def test_repair_with_virtual_nodes():
    coder = make(5, 4, 8)  # nu=3: exercises virtual partners in repair
    full, L = rand_chunks(coder)
    for failed in range(9):
        rebuilt = coder.repair_from_chunks(
            failed, {c: full[c] for c in full if c != failed})
        np.testing.assert_array_equal(rebuilt, full[failed], err_msg=str(failed))


def test_repair_with_real_nonhelper():
    # d=5 < k+m-1=6: one real chunk sits out of the repair entirely
    coder = make(4, 3, 5)  # k+m=7, q=2 -> t=4, nu=1
    assert coder.q == 2 and coder.nu == 1
    full, L = rand_chunks(coder)
    for failed in range(7):
        need = coder.minimum_to_decode_subchunks(
            failed, [c for c in range(7) if c != failed])
        assert len(need) == coder.d
        picked = {}
        for h, planes in need.items():
            sub = coder._split(full[h])
            picked[h] = sub[..., planes, :]
        rebuilt = coder.repair_chunk(failed, picked)
        np.testing.assert_array_equal(rebuilt, full[failed], err_msg=str(failed))


def test_helper_set_must_cover_failed_column():
    # excluding the failed node's grid-column mate makes the coupled
    # system underdetermined — the plugin must refuse, not corrupt
    coder = make(4, 3, 5)
    failed = 5
    mate = next(c for c in range(7) if c != failed and
                coder._xy(coder._node_of_chunk(c))[1]
                == coder._xy(coder._node_of_chunk(failed))[1])
    bad = tuple(sorted(set(range(7)) - {failed, mate}))[:coder.d]
    assert len(bad) == coder.d
    with pytest.raises(ValueError, match="underdetermined"):
        coder._affine_repair(failed, tuple(bad))
    # and the helper picker always includes the column mate
    picked = coder._pick_helpers(failed, [c for c in range(7) if c != failed])
    assert mate in picked


def test_encode_decode_full_object_api():
    coder = make(4, 2, 5)
    rng = np.random.default_rng(3)
    obj = rng.integers(0, 256, size=4000, dtype=np.uint8).tobytes()
    chunks = coder.encode(list(range(6)), obj)
    rec = coder.decode_concat({c: chunks[c] for c in (0, 2, 4, 5)},
                              object_size=4000)
    assert rec.tobytes() == obj


def test_minimum_to_decode_semantics():
    coder = make(4, 2, 5)
    # no erasure: want itself
    assert coder.minimum_to_decode([0, 1], range(6)) == {0, 1}
    # single erasure with d survivors -> d helpers
    got = coder.minimum_to_decode([0], [1, 2, 3, 4, 5])
    assert len(got) == coder.d and 0 not in got
    # double erasure -> all survivors
    got = coder.minimum_to_decode([0, 1], [2, 3, 4, 5])
    assert got == {2, 3, 4, 5}


def test_mxu_impl_matches_ref():
    import os
    prof_ref = make(4, 2, 5)
    prof_dev = Clay({"k": "4", "m": "2", "d": "5"})
    rng = np.random.default_rng(7)
    L = prof_ref.get_chunk_size(4 * prof_ref.sub_chunk_count * 4)
    data = rng.integers(0, 256, size=(2, 4, L), dtype=np.uint8)
    np.testing.assert_array_equal(
        prof_ref.encode_chunks(data), prof_dev.encode_chunks(data))


def test_decode_with_only_d_helpers_routes_to_repair():
    # the minimum_to_decode -> read -> decode flow for a single erasure
    # hands decode_chunks exactly d chunks; it must produce correct bytes
    coder = make(4, 3, 5)
    full, L = rand_chunks(coder)
    failed = 2
    helpers = coder.minimum_to_decode([failed], [c for c in range(7)
                                                 if c != failed])
    rec = coder.decode_chunks([failed], {h: full[h] for h in helpers})
    np.testing.assert_array_equal(rec[failed], full[failed])


def test_decode_partial_survivors_treated_as_erased():
    # survivors not provided are erased, never silently assumed zero
    coder = make(4, 2, 5)
    full, L = rand_chunks(coder)
    # erase 0, withhold 5: both unknown -> still within m=2, must work
    rec = coder.decode_chunks([0], {c: full[c] for c in (1, 2, 3, 4)})
    np.testing.assert_array_equal(rec[0], full[0])
    assert set(rec) == {0}
    # withholding two more exceeds m -> must raise, not corrupt
    with pytest.raises(ValueError):
        coder.decode_chunks([0], {c: full[c] for c in (1, 2, 3)})


def test_decode_passthrough_of_provided_wanted_chunks():
    # minimum_to_decode with no erasure says "read the chunks themselves";
    # decode_chunks must then return them, not raise
    coder = make(4, 2, 5)
    full, L = rand_chunks(coder)
    got = coder.decode_chunks([0, 1], {0: full[0], 1: full[1]})
    np.testing.assert_array_equal(got[0], full[0])
    np.testing.assert_array_equal(got[1], full[1])
    # mixed: one provided, one missing (degraded read)
    have = {c: full[c] for c in (1, 2, 3, 4)}
    got = coder.decode_chunks([0, 1], have)
    np.testing.assert_array_equal(got[0], full[0])
    np.testing.assert_array_equal(got[1], full[1])
