"""LRC plugin tests (ref: src/test/erasure-code/TestErasureCodeLrc.cc
pattern: kml expansion, layered encode/decode round-trips, and the
locality property — single-failure repair touches only the local group)."""

from itertools import combinations

import numpy as np
import pytest

from ceph_tpu.ec import registry
from ceph_tpu.ec.lrc import _expand_kml

DOC_MAPPING = "__DD__DD"
DOC_LAYERS = [["_cDD_cDD", ""], ["cDDD____", ""], ["____cDDD", ""]]


def test_kml_expansion_matches_reference_doc():
    # the documented expansion of k=4 m=2 l=3
    mapping, layers = _expand_kml(4, 2, 3)
    assert mapping == DOC_MAPPING
    assert layers == DOC_LAYERS


def test_kml_validation():
    with pytest.raises(ValueError, match="multiple of"):
        _expand_kml(4, 3, 3)  # k+m=7 not divisible by 3
    with pytest.raises(ValueError):
        _expand_kml(4, 2, 1)


@pytest.fixture
def coder():
    return registry.factory({"plugin": "lrc", "k": "4", "m": "2", "l": "3"})


def test_geometry(coder):
    assert coder.get_chunk_count() == 8
    assert coder.get_data_chunk_count() == 4
    assert coder.get_coding_chunk_count() == 4
    assert coder.data_positions == (2, 3, 6, 7)


def test_encode_roundtrip_no_loss(coder):
    rng = np.random.default_rng(0)
    obj = rng.integers(0, 256, size=997, dtype=np.uint8)
    chunks = coder.encode(range(8), obj)
    out = coder.decode_concat(chunks, object_size=997)
    np.testing.assert_array_equal(out, obj)


def test_local_parity_is_consistent(coder):
    # each local parity equals its layer's RS parity over the group
    rng = np.random.default_rng(1)
    obj = rng.integers(0, 256, size=4 * 128, dtype=np.uint8)
    chunks = coder.encode(range(8), obj)
    for layer in coder.layers[1:]:  # local layers
        ldata = np.stack([chunks[p] for p in layer.d_pos])[None]
        parity = np.asarray(layer.coder.encode_chunks(ldata))[0]
        for i, p in enumerate(layer.c_pos):
            np.testing.assert_array_equal(parity[i], chunks[p])


def test_single_failure_repair_is_local(coder):
    # the LRC selling point: one lost chunk reads only its local group
    for lost in range(8):
        avail = [i for i in range(8) if i != lost]
        need = coder.minimum_to_decode([lost], avail)
        assert len(need) <= 3, (lost, need)  # l = 3, not k = 4
        group = range(0, 4) if lost < 4 else range(4, 8)
        assert need <= set(group), (lost, need)


def test_single_failure_repair_bytes(coder):
    rng = np.random.default_rng(2)
    obj = rng.integers(0, 256, size=4 * 128, dtype=np.uint8)
    chunks = coder.encode(range(8), obj)
    for lost in range(8):
        avail = {i: chunks[i] for i in range(8) if i != lost}
        need = coder.minimum_to_decode([lost], list(avail))
        rec = coder.decode([lost], {i: avail[i] for i in need})
        np.testing.assert_array_equal(rec[lost], chunks[lost])


def test_double_failure_repair(coder):
    rng = np.random.default_rng(3)
    obj = rng.integers(0, 256, size=4 * 128, dtype=np.uint8)
    chunks = coder.encode(range(8), obj)
    for lost in combinations(range(8), 2):
        avail = {i: chunks[i] for i in range(8) if i not in lost}
        need = coder.minimum_to_decode(list(lost), list(avail))
        rec = coder.decode(list(lost), {i: avail[i] for i in need})
        for p in lost:
            np.testing.assert_array_equal(rec[p], chunks[p], err_msg=str(lost))


def test_mapping_layers_profile_form():
    import json
    coder = registry.factory({
        "plugin": "lrc", "mapping": DOC_MAPPING,
        "layers": json.dumps(DOC_LAYERS)})
    kml = registry.factory({"plugin": "lrc", "k": "4", "m": "2", "l": "3"})
    obj = np.arange(512, dtype=np.uint16).astype(np.uint8)
    a = coder.encode(range(8), obj)
    b = kml.encode(range(8), obj)
    for i in range(8):
        np.testing.assert_array_equal(a[i], b[i])


def test_unreconstructible_raises(coder):
    # lose a whole local group incl. its global + local parity + 2 data
    chunks = coder.encode(range(8), np.zeros(512, np.uint8))
    avail = [0, 1, 2, 3]  # entire second group gone (4 chunks > tolerance)
    with pytest.raises(ValueError, match="cannot reconstruct"):
        coder.minimum_to_decode([6], avail)


def test_bad_profiles_rejected():
    with pytest.raises(ValueError, match="no layers"):
        registry.factory({"plugin": "lrc", "mapping": "DD__"})
    with pytest.raises(ValueError, match="length"):
        registry.factory({"plugin": "lrc", "mapping": "DD_",
                          "layers": [["cDDD", ""]]})
    with pytest.raises(ValueError, match="neither data nor written"):
        registry.factory({"plugin": "lrc", "mapping": "DD__",
                          "layers": [["DDc_", ""]]})


def test_batched_encode(coder):
    rng = np.random.default_rng(4)
    objs = rng.integers(0, 256, size=(5, 512), dtype=np.uint8)
    chunks = coder.encode(range(8), objs)
    assert chunks[0].shape == (5, 128)
    single = coder.encode(range(8), objs[2])
    for i in range(8):
        np.testing.assert_array_equal(chunks[i][2], single[i])


def test_layer_order_validation():
    # a layer consuming a position no earlier layer wrote is rejected
    with pytest.raises(ValueError, match="layer order"):
        registry.factory({"plugin": "lrc", "mapping": "_DDD",
                          "layers": [["DDDc", ""], ["cDD_", ""]]})
    # same layers in producing order are fine
    registry.factory({"plugin": "lrc", "mapping": "_DDD",
                      "layers": [["cDD_", ""], ["DDDc", ""]]})


def test_minimum_to_decode_with_cost_is_layer_aware(coder):
    # chunk 2 lost; group-1 chunks made artificially cheap — the MDS
    # default would pick {4,5,6,7}, an undecodable set for position 2
    costs = {0: 10, 1: 10, 3: 10, 4: 1, 5: 1, 6: 1, 7: 1}
    need = coder.minimum_to_decode_with_cost([2], costs)
    assert need <= {0, 1, 3}
    rng = np.random.default_rng(9)
    obj = rng.integers(0, 256, size=512, dtype=np.uint8)
    chunks = coder.encode(range(8), obj)
    rec = coder.decode([2], {i: chunks[i] for i in need})
    np.testing.assert_array_equal(rec[2], chunks[2])


# -- the static forms the served path fuses -------------------------------

PROFILES = {
    "k4m2l3": {"plugin": "lrc", "k": "4", "m": "2", "l": "3"},
    "k8m4l3": {"plugin": "lrc", "k": "8", "m": "4", "l": "3"},
    "k8m4l4": {"plugin": "lrc", "k": "8", "m": "4", "l": "4"},
    "doc_low_level": {"plugin": "lrc", "mapping": DOC_MAPPING,
                      "layers": DOC_LAYERS},
}


@pytest.mark.parametrize("name", sorted(PROFILES))
def test_the_composed_generator_is_encode_chunks(name):
    """`encode_matrix`: the layers composed on the host, what the
    layer-by-layer `encode_chunks` computes, as one (m, k) matrix."""
    from ceph_tpu.gf.numpy_ref import gf_matmul
    coder = registry.factory(PROFILES[name])
    G = coder.encode_matrix()
    assert G.shape == (coder.m, coder.k) and G.dtype == np.uint8
    data = np.random.default_rng(4001).integers(0, 256, (3, coder.k, 384),
                                                np.uint8)
    want = np.asarray(coder.encode_chunks(data))
    for b in range(3):
        np.testing.assert_array_equal(gf_matmul(G, data[b]), want[b])


def test_a_layer_without_a_matrix_leaves_no_generator():
    coder = registry.factory(PROFILES["k4m2l3"])
    fresh = registry.factory(PROFILES["k4m2l3"])
    fresh.layers[1].coder.encode_matrix = lambda: None
    assert fresh.encode_matrix() is None
    assert coder.encode_matrix() is not None


@pytest.fixture(scope="module")
def k8m4l3():
    return registry.factory(PROFILES["k8m4l3"])


@pytest.mark.parametrize("lost", range(16))
def test_the_local_batch_decoder_is_decode_chunks(k8m4l3, lost):
    """A single loss of k=8 m=4 l=3 decodes inside its group: the fused
    program is that layer's own decode row over the 3 helpers the plan
    reads, bit for bit what `decode_chunks` rebuilds, under a key equal
    across coder instances."""
    coder = k8m4l3
    survivors = [s for s in range(16) if s != lost]
    helpers = sorted(coder.minimum_to_decode([lost], survivors))
    assert len(helpers) == 3 and {h // 4 for h in helpers} == {lost // 4}
    fn = coder.batch_decoder([lost], helpers)
    rng = np.random.default_rng(4002 + lost)
    obj = rng.integers(0, 256, (4, 8 * 256), np.uint8)
    chunks = coder.encode(range(16), obj)
    stack = np.stack([chunks[h] for h in helpers], axis=1)   # (B, 3, L)
    got = np.asarray(fn(stack))[:, 0]
    want = coder.decode_chunks([lost], {h: chunks[h] for h in helpers})
    np.testing.assert_array_equal(got, want[lost])
    np.testing.assert_array_equal(got, chunks[lost])
    other = registry.factory(PROFILES["k8m4l3"])
    key = coder.decode_program_key([lost], helpers)
    assert key is not None and key == other.decode_program_key([lost],
                                                               helpers)


@pytest.mark.parametrize("lost,every_survivor", [([2, 3], True),
                                                  ([0, 2], False)])
def test_a_loss_past_the_group(k8m4l3, lost, every_survivor):
    """Two losses in one group break locality. From every survivor two
    data rows decode in one step through the global layer, by its own
    rows; from the fewest helpers (`minimum_to_decode`) the plan ladders
    through several layers and keeps the probed decoder. Both give the
    lost rows."""
    coder = k8m4l3
    survivors = [s for s in range(16) if s not in lost]
    if not every_survivor:
        survivors = sorted(coder.minimum_to_decode(lost, survivors))
    plan, _, _ = coder._repair_plan(set(lost), set(survivors))
    static = coder.repair_matrix(tuple(lost), tuple(survivors))
    assert (len(plan) == 1) == every_survivor == (static is not None)
    fn = coder.batch_decoder(lost, survivors)
    obj = np.random.default_rng(4003).integers(0, 256, (2, 8 * 256),
                                               np.uint8)
    chunks = coder.encode(range(16), obj)
    got = np.asarray(fn(np.stack([chunks[s] for s in survivors], axis=1)))
    for i, p in enumerate(lost):
        np.testing.assert_array_equal(got[:, i], chunks[p])


@pytest.mark.parametrize("k,m,l", [(4, 2, 3), (8, 4, 3), (8, 4, 4)])
def test_the_benchmarks_reference_is_the_codec(k, m, l):
    """`bench/reference/lrc_codeword.py`, written apart from the codec,
    lays out and encodes the same codeword on seeded data, and rebuilds
    every row from its group."""
    from bench.reference import lrc_codeword, recovered_pool
    coder = registry.factory({"plugin": "lrc", "k": str(k), "m": str(m),
                              "l": str(l)})
    assert lrc_codeword.mapping(k, m, l) == coder.mapping
    unit = 64
    payload = np.random.default_rng(4004 + k + l).integers(
        0, 256, k * unit * 5, np.uint8).tobytes()
    data = recovered_pool.data_rows(payload, k, unit)
    parity = np.asarray(coder.encode_chunks(data[None]))[0]
    rows = [None] * coder.get_chunk_count()
    for j, p in enumerate(coder.get_chunk_mapping()):
        rows[p] = data[j] if j < k else parity[j - k]
    assert lrc_codeword.check(payload, rows, k, m, l, unit) == []
    for p in range(coder.get_chunk_count()):
        np.testing.assert_array_equal(
            lrc_codeword.rebuilt(rows, p, k, m, l), rows[p])


def test_the_rs_write_program_is_unchanged():
    """An RS pool's fused write keeps its bytes and its arguments: the
    coding matrix itself, and no `planes`, so its cache key and HLO are
    what they were before a matrix code could bring its own."""
    from ceph_tpu.osd.ecbackend import ECBackend
    be = ECBackend("plugin=jerasure technique=reed_sol_van k=8 m=3", "0.0",
                   list(range(11)), chunk_size=256)
    mat = np.ascontiguousarray(be.coder.matrix, np.uint8)
    assert be._write_matrix == (mat.tobytes(), 1)
    fn = be._fused_write_program(4096, 2)
    hits = ECBackend._fused_write_fn.cache_info().hits
    assert fn is ECBackend._fused_write_fn(mat.tobytes(), 3, 8, 4096, 2)
    assert ECBackend._fused_write_fn.cache_info().hits == hits + 1
