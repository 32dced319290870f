"""Non-regression: encoded bytes are pinned and must never drift.

The rebuild's ceph_erasure_code_non_regression (ref: src/test/
erasure-code/ceph_erasure_code_non_regression.cc): the corpus freezes
the stripe byte format; every kernel implementation must reproduce it
exactly. Regenerate only deliberately via tools/make_corpus.py.
"""

import hashlib
import json
import os

import numpy as np
import pytest

from ceph_tpu.ec.matrices import coding_matrix
from ceph_tpu.gf.numpy_ref import encode_ref
from ceph_tpu.gf.tables import GF_EXP
from ceph_tpu.ops.rs_kernels import _apply_bitlinear, _apply_mxu

CORPUS = os.path.join(os.path.dirname(__file__), "corpus", "corpus.json")

with open(CORPUS) as f:
    _C = json.load(f)


def _data_for(e):
    rng = np.random.default_rng(0xCE9 + e["k"] * 16 + e["m"])
    return rng.integers(0, 256, size=(1, e["k"], 512), dtype=np.uint8)


def test_gf_tables_pinned():
    assert hashlib.sha256(GF_EXP.tobytes()).hexdigest() == _C["gf_exp_sha256"]
    assert _C["prim_poly"] == 0x11D


@pytest.mark.parametrize("entry", _C["entries"],
                         ids=[f"{e['technique']}-k{e['k']}m{e['m']}"
                              for e in _C["entries"]])
def test_matrix_pinned(entry):
    mat = coding_matrix(entry["technique"], entry["k"], entry["m"])
    assert mat.tolist() == entry["matrix"]


@pytest.mark.parametrize("entry", _C["entries"],
                         ids=[f"{e['technique']}-k{e['k']}m{e['m']}"
                              for e in _C["entries"]])
def test_parity_bytes_pinned(entry):
    data = _data_for(entry)
    assert hashlib.sha256(data.tobytes()).hexdigest() == entry["data_sha256"]
    mat = np.array(entry["matrix"], dtype=np.uint8)
    ref = encode_ref(mat, data)
    assert hashlib.sha256(ref.tobytes()).hexdigest() == entry["parity_sha256"]
    assert ref[0, :, :16].tolist() == entry["parity_head"]
    # every device lowering reproduces the pinned bytes
    for lowering in (_apply_bitlinear, _apply_mxu):
        got = np.asarray(lowering(mat, data))
        assert hashlib.sha256(got.tobytes()).hexdigest() \
            == entry["parity_sha256"], lowering.__name__
