"""End-to-end ErasureCode contract tests for the RS plugin.

Pattern from the reference's plugin tests (ref: src/test/erasure-code/
TestErasureCodePlugin*.cc + TestErasureCode.cc): build a coder from a
profile, encode, erase every <= m subset, minimum_to_decode, decode,
byte-compare; plus registry behavior.
"""

from itertools import combinations

import numpy as np
import pytest

from ceph_tpu.ec import registry
from ceph_tpu.ec.interface import CHUNK_ALIGNMENT, profile_from_string


def test_registry_known_plugins():
    assert "tpu_rs" in registry.plugins()
    assert "jerasure" in registry.plugins()
    with pytest.raises(ValueError):
        registry.factory({"plugin": "no_such_plugin"})


def test_profile_string_roundtrip():
    prof = profile_from_string("k=8 m=3 plugin=jerasure technique=reed_sol_van")
    assert prof == {"k": "8", "m": "3", "plugin": "jerasure",
                    "technique": "reed_sol_van"}
    coder = registry.factory(prof)
    assert (coder.k, coder.m) == (8, 3)


def test_geometry():
    coder = registry.factory("k=4 m=2 plugin=tpu_rs")
    assert coder.get_chunk_count() == 6
    assert coder.get_data_chunk_count() == 4
    assert coder.get_coding_chunk_count() == 2
    assert coder.get_chunk_mapping() == list(range(6))
    cs = coder.get_chunk_size(1000)
    assert cs % CHUNK_ALIGNMENT == 0 and cs * 4 >= 1000


@pytest.mark.parametrize("technique", ["reed_sol_van", "cauchy_orig", "cauchy_good"])
def test_full_roundtrip_all_patterns(technique):
    k, m = 4, 2
    coder = registry.factory(f"k={k} m={m} technique={technique}")
    rng = np.random.default_rng(7)
    obj = rng.integers(0, 256, size=1000, dtype=np.uint8).tobytes()
    encoded = coder.encode(range(k + m), obj)
    assert set(encoded) == set(range(k + m))
    for nerased in (1, m):
        for erased in combinations(range(k + m), nerased):
            avail = [i for i in range(k + m) if i not in erased]
            need = coder.minimum_to_decode(list(range(k)), avail)
            assert need.issubset(set(avail))
            have = {i: encoded[i] for i in need}
            out = coder.decode_concat(have, object_size=len(obj))
            assert out.tobytes() == obj, f"erased={erased}"


def test_batched_encode_decode():
    coder = registry.factory("k=8 m=3")
    rng = np.random.default_rng(8)
    batch = rng.integers(0, 256, size=(16, 4096), dtype=np.uint8)
    enc = coder.encode(range(11), batch)
    assert enc[0].shape[0] == 16
    # lose 3 chunks including data and parity
    have = {i: enc[i] for i in range(11) if i not in (1, 5, 9)}
    rec = coder.decode([1, 5, 9], have)
    np.testing.assert_array_equal(rec[1], enc[1])
    np.testing.assert_array_equal(rec[5], enc[5])
    np.testing.assert_array_equal(rec[9], enc[9])


def test_minimum_to_decode_prefers_available_wanted():
    coder = registry.factory("k=4 m=2")
    # all wanted available -> returns exactly the wanted set
    assert coder.minimum_to_decode([0, 1], [0, 1, 2, 3, 4, 5]) == {0, 1}
    # chunk 0 lost -> needs k chunks
    need = coder.minimum_to_decode([0], [1, 2, 3, 4, 5])
    assert len(need) == 4 and need.issubset({1, 2, 3, 4, 5})
    with pytest.raises(ValueError):
        coder.minimum_to_decode([0], [1, 2, 3])


def test_minimum_to_decode_with_cost():
    coder = registry.factory("k=2 m=2")
    costs = {1: 10, 2: 1, 3: 1}
    assert coder.minimum_to_decode_with_cost([0], costs) == {2, 3}


def test_padding_trim():
    coder = registry.factory("k=4 m=2")
    obj = b"hello erasure world" * 3
    enc = coder.encode(range(6), obj)
    out = coder.decode_concat({i: enc[i] for i in (0, 2, 4, 5)},
                              object_size=len(obj))
    assert out.tobytes() == obj


def test_reed_sol_r6_op():
    import pytest as _pytest
    from ceph_tpu.ec.matrices import coding_matrix
    mat = coding_matrix("reed_sol_r6_op", 4, 2)
    assert mat[0].tolist() == [1, 1, 1, 1]
    assert mat[1].tolist() == [1, 2, 4, 8]
    coder = registry.factory("k=4 m=2 technique=reed_sol_r6_op")
    obj = bytes(range(256)) * 4
    enc = coder.encode(range(6), obj)
    out = coder.decode_concat({i: enc[i] for i in (1, 3, 4, 5)},
                              object_size=len(obj))
    assert out.tobytes() == obj
    with _pytest.raises(ValueError):
        registry.factory("k=4 m=3 technique=reed_sol_r6_op")


def test_bitmatrix_techniques_dispatch():
    # liberation/blaum_roth/liber8tion route to the XOR-schedule coder
    # (full coverage in tests/test_bitmatrix.py)
    from ceph_tpu.ec.bitmatrix import JerasureBitmatrix
    for tech, w in (("liberation", 5), ("blaum_roth", 4), ("liber8tion", 8)):
        coder = registry.factory(f"k=4 m=2 technique={tech} w={w}")
        assert isinstance(coder, JerasureBitmatrix)


@pytest.mark.parametrize("impl", ["bitlinear", "mxu", "logexp", "pallas"])
@pytest.mark.parametrize("profile", [
    "plugin=tpu_rs k=4 m=2", "plugin=jerasure k=4 m=2",
    "plugin=isa k=4 m=2", "plugin=shec k=4 m=3 c=2",
    "plugin=clay k=4 m=2", "plugin=lrc k=4 m=2 l=3"])
def test_bad_impl_rejected_with_choices(profile, impl):
    # the lowering was a profile key once; profiles arrive from outside
    with pytest.raises(ValueError, match="no longer selectable"):
        registry.factory(f"{profile} impl={impl}")


@pytest.mark.parametrize("profile,builds", [
    ("plugin=clay k=4 m=2", True), ("plugin=shec k=4 m=3 c=2", True),
    ("plugin=tpu_rs k=4 m=2", False), ("plugin=lrc k=4 m=2 l=3", False)])
def test_impl_ref_is_the_oracle_of_clay_and_shec_only(profile, builds):
    if builds:
        assert registry.factory(f"{profile} impl=ref").ref_oracle
        assert not registry.factory(profile).ref_oracle
    else:
        with pytest.raises(ValueError, match="impl=ref"):
            registry.factory(f"{profile} impl=ref")


def test_isa_plugin_distinct_matrix():
    isa = registry.factory("k=4 m=2 plugin=isa")
    jer = registry.factory("k=4 m=2 plugin=jerasure")
    assert isa.matrix[0].tolist() == [1, 1, 1, 1]
    assert isa.matrix[1].tolist() == [1, 2, 4, 8]  # powers of 2
    assert isa.matrix.tolist() != jer.matrix.tolist()
    obj = bytes(range(256)) * 2
    enc = isa.encode(range(6), obj)
    out = isa.decode_concat({i: enc[i] for i in (0, 2, 4, 5)},
                            object_size=len(obj))
    assert out.tobytes() == obj
    with pytest.raises(ValueError):
        registry.factory("k=4 m=2 plugin=isa technique=liberation")


def test_minimum_to_decode_rejects_bad_ids():
    coder = registry.factory("k=4 m=2")
    with pytest.raises(ValueError):
        coder.minimum_to_decode([7], [0, 1, 2, 3, 4, 5])
    with pytest.raises(ValueError):
        coder.minimum_to_decode_with_cost([0], {9: 1})


def test_isa_non_mds_geometry_rejected():
    factory = registry.factory
    # gf_gen_rs_matrix-style construction is not MDS at k=12 m=5 (18 of
    # 6188 five-erasure patterns hit a singular survivor submatrix);
    # accepting it would advertise fault tolerance that fails at decode.
    with pytest.raises(ValueError, match="not MDS"):
        factory({"plugin": "isa", "k": "12", "m": "5"})


def test_isa_cauchy_matches_isal_construction():
    factory = registry.factory
    # ISA-L gf_gen_cauchy1: element (i, j) = 1/((k+i) XOR j) — distinct
    # from jerasure's cauchy_orig 1/(i XOR (m+j)).
    coder = factory({"plugin": "isa", "k": "4", "m": "2",
                     "technique": "cauchy"})
    assert coder.matrix.tolist() == [[71, 167, 122, 186],
                                     [167, 71, 186, 122]]
    jer = factory({"plugin": "jerasure", "k": "4", "m": "2",
                   "technique": "cauchy_orig"})
    assert coder.matrix.tolist() != jer.matrix.tolist()


def test_isa_cauchy_always_mds():
    from ceph_tpu.ec.matrices import is_mds, isa_cauchy_matrix
    for k, m in ((4, 2), (8, 3), (12, 5)):
        assert is_mds(isa_cauchy_matrix(k, m), k)


def test_encode_rejects_bad_chunk_ids():
    factory = registry.factory
    coder = factory({"plugin": "tpu_rs", "k": "4", "m": "2"})
    with pytest.raises(ValueError, match=r"chunk ids must be in \[0, 6\)"):
        coder.encode([99], b"hello world")
