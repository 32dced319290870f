"""The benchmark's own references start equal to the program's oracles
and import nothing of them."""

import numpy as np
import pytest

from bench.reference import crc32c, gf256


@pytest.mark.parametrize("k,m", [(8, 3), (2, 1), (4, 2)])
def test_matrices_equal_the_programs(k, m):
    from ceph_tpu.ec.matrices import coding_matrix
    assert np.array_equal(gf256.reed_sol_van(k, m),
                          coding_matrix("reed_sol_van", k, m))
    assert np.array_equal(gf256.cauchy_orig(k, m),
                          coding_matrix("cauchy_orig", k, m))
    assert not np.array_equal(gf256.reed_sol_van(k, m),
                              gf256.cauchy_orig(k, m))


@pytest.mark.parametrize("seed", [1, (1 << 31) + 5])
def test_rs_encode_equals_encode_ref(seed):
    from ceph_tpu.gf.numpy_ref import encode_ref
    matrix = gf256.reed_sol_van(8, 3)
    data = np.random.default_rng(seed).integers(0, 256, (3, 8, 4096),
                                                dtype=np.uint8)
    assert np.array_equal(gf256.rs_encode(matrix, data),
                          encode_ref(matrix, data))


@pytest.mark.parametrize("length,seed", [(0, 7), (1000, 0), (4096, 0xFFFFFFFF),
                                         (3 * 4096, 0xFFFFFFFF)])
def test_crc32c_rows_equals_ceph_crc32c(length, seed):
    from ceph_tpu.csum.reference import ceph_crc32c
    rows = np.random.default_rng(length).integers(0, 256, (5, length),
                                                  dtype=np.uint8)
    got = crc32c.crc32c_rows(seed, rows)
    assert [int(c) for c in got] == [ceph_crc32c(seed, r.tobytes())
                                     for r in rows]


def test_crc32c_check_value():
    # the standard check: CRC-32C("123456789") = 0xE3069283 with the
    # register started at -1 and inverted at the end
    row = np.frombuffer(b"123456789", np.uint8)[None, :]
    assert int(crc32c.crc32c_rows(0xFFFFFFFF, row)[0]) ^ 0xFFFFFFFF == 0xE3069283


def test_references_import_nothing_of_the_program():
    import inspect
    import re
    for module in (gf256, crc32c):
        assert not re.search(r"^\s*(import|from)\s+(ceph_tpu|bench)",
                             inspect.getsource(module), re.M)


def test_reed_sol_van_is_the_programs_variant_not_upstream_jerasures():
    # upstream jerasure scales on until the first coding row and column
    # are all ones; the program stops after the column reduction, the
    # configurations say so, and the reference follows the program
    matrix = gf256.reed_sol_van(8, 3)
    assert matrix[0].tolist() == [26, 132, 186, 51, 231, 16, 198, 39]
    assert not (matrix[0] == 1).all() and not (matrix[:, 0] == 1).all()
