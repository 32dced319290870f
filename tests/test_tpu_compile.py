"""The served path's device programs, compiled at real widths for a
described (not attached) TPU v5e chip: k=8 m=3, 4 MiB objects, 512 KiB
shard rows. Nothing runs; what the chip's compiler refuses, or the
scratch memory a program asks for, shows here at no chip time.

All in this one file and one process: only one process at a time may
load the TPU's library, so the topology is described inside a
module-scoped fixture (never at import) and every compile happens in
the worker that was handed this file.
"""

import numpy as np
import pytest

K, M, SHARD = 8, 3, 512 << 10
MiB = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep it out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def coder():
    from ceph_tpu.ec.registry import factory
    return factory(f"plugin=jerasure technique=reed_sol_van k={K} m={M}")


def _struct(one_chip, shape, dtype):
    import jax
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compile(fn, one_chip, *shapes_dtypes):
    import jax
    args = [_struct(one_chip, s, d) for s, d in shapes_dtypes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("lowering", ["_apply_bitlinear", "_apply_mxu"])
def test_rs_encode_compiles_at_32_objects(one_chip, coder, lowering):
    import functools

    from ceph_tpu.ops import rs_kernels
    enc = functools.partial(getattr(rs_kernels, lowering), coder.matrix)
    out = _compile(enc, one_chip, ((32, K, SHARD), np.uint8))
    assert out.memory_analysis().temp_size_in_bytes < 2048 * MiB


@pytest.mark.parametrize("sub_batch", [False, True])
def test_host_face_program_is_words_in_a_flat_row_major_result_out(
        one_chip, coder, sub_batch):
    """What `encode_chunks` launches: on a bucket under the line the
    whole of it (here 32 x 4 MiB, the shape every call had before PR
    35), on 32 x 4 MiB itself 16 sub-batches of the shape
    `_sub_batch_rows` gives. Words in, the parity out as (N, 128) uint32
    in layout {1,0:T(8,128)}, the one form the copy to the host neither
    de-tiles nor transposes. (The uint8 (32, 3, L) parity leaves as
    {2,0,1:T(8,128)(4,1)}; a cast of it to words on the device asks for
    73.5 GB: PERF.md, PR 30.)"""
    import re

    from ceph_tpu.ops import rs_kernels
    rows = rs_kernels._sub_batch_rows(32, K * SHARD) if sub_batch else 32
    assert rows == (2 if sub_batch else 32)
    fn = rs_kernels._make_jitted_words(coder.matrix.tobytes(), M, K)
    out = fn.lower(_struct(one_chip, (rows, K, SHARD // 4),
                           np.uint32)).compile()
    layout = re.search(r"entry_computation_layout=\{(.*?)\}\}",
                       out.as_text()).group(1)
    flat_rows = rows * M * SHARD // 512
    assert layout.endswith(f"->u32[{flat_rows},128]{{1,0:T(8,128)"), layout
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < 3072 * MiB


def _no_gather(compiled):
    """What PR 26 bought: the crc's chunk stage is masked XORs on the
    VPU, not table gathers (8.6 ms each for 1 M indices on the chip)."""
    return " gather(" not in compiled.as_text()


def test_crc32c_rows_of_one_object_fit(one_chip):
    """The defects this guards: 11 rows x 512 KiB took 3,168 MiB of
    scratch (~580x the input) and a minute to compile (PR 22); then
    41 MiB and eight gathers (PR 26 read 0 MiB and none)."""
    from ceph_tpu.csum.kernels import crc32c_blocks
    out = _compile(lambda b: crc32c_blocks(b, init=0xFFFFFFFF, xorout=0),
                   one_chip, ((K + M, SHARD), np.uint8))
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < 4 * MiB


def _fused_write(one_chip, coder, bucket):
    from ceph_tpu.osd.ecbackend import ECBackend
    fn = ECBackend._fused_write_fn(coder.matrix.tobytes(), M, K, SHARD,
                                   bucket)
    return _compile(fn, one_chip, ((bucket, K, SHARD), np.uint8))


def test_fused_write_compiles_at_bucket_1(one_chip, coder):
    """What a served write launches. With the gathers it touched
    37.2 GB by the compiler's count; PR 26 read 0.35 GB."""
    out = _fused_write(one_chip, coder, 1)
    assert _no_gather(out)
    assert out.cost_analysis()["bytes accessed"] < 2e9


def test_fused_write_compiles_at_bucket_16(one_chip, coder):
    out = _fused_write(one_chip, coder, 16)
    assert _no_gather(out)
    # 64 MiB in, 88 MiB of rows to checksum; 1,174 MiB is what the
    # gathers asked for: the program after them may not ask for more
    assert out.memory_analysis().temp_size_in_bytes < 1174 * MiB


@pytest.mark.parametrize("lost", [(0, 9), (4,)])
def test_recover_program_compiles_at_the_staged_batch(one_chip, coder, lost):
    """The batch RecoveryRunner really stages at 4 MiB objects: its byte
    bound, not osd_recovery_batch (128 objects asked for 6 GiB of
    scratch per launch, two launches in flight per daemon). Two lost
    rows, and the one data row a PG of configuration
    rados_k8m3_12osd_1out loses with its OSD."""
    from ceph_tpu.osd.ecbackend import (RECOVERY_STAGE_BYTES,
                                        _build_recover_program)
    batch = RECOVERY_STAGE_BYTES // (K * SHARD)
    assert batch == 32
    helper = tuple(i for i in range(K + M) if i not in lost)[:K]
    fn = _build_recover_program(coder.batch_decoder(lost, helper),
                                verify=True, host_crc=False)
    out = _compile(fn, one_chip, ((batch, K, SHARD), np.uint8),
                   ((batch,), np.uint32))
    assert _no_gather(out)
    # 1,687 MiB with the gathers (PR 26 read 1,010)
    assert out.memory_analysis().temp_size_in_bytes < 1687 * MiB


@pytest.mark.parametrize("lost", [(1,), (3,), (4,), (6,), (7,)])
def test_recover_program_compiles_at_the_wire_tiers_grant(one_chip, coder,
                                                          lost):
    """What one grant of a recovery round launches under the default
    settings: osd_recovery_max_active x osd_recovery_max_chunk = 24 MiB
    of helper rows is 4 objects of 4 MiB (the power of two under 6); the
    five lost slots of configuration rados_k8m3_12osd_1out's PGs."""
    from ceph_tpu.osd.ecbackend import _build_recover_program
    from ceph_tpu.utils.config import OPTIONS
    defaults = {o.name: o.default for o in OPTIONS}
    fit = (defaults["osd_recovery_max_active"]
           * defaults["osd_recovery_max_chunk"]) // (K * SHARD)
    batch = 1 << (fit.bit_length() - 1)
    assert batch == 4
    helper = tuple(i for i in range(K + M) if i not in lost)[:K]
    fn = _build_recover_program(coder.batch_decoder(lost, helper),
                                verify=True, host_crc=False)
    out = _compile(fn, one_chip, ((batch, K, SHARD), np.uint8),
                   ((batch,), np.uint32))
    assert _no_gather(out)
    # an eighth of the 32-object launch's scratch
    assert out.memory_analysis().temp_size_in_bytes < 256 * MiB


@pytest.mark.parametrize("column", [0, 7])
def test_fused_delta_compiles_at_one_4k_block(one_chip, coder, column):
    """What a 4 KiB overwrite launches (configuration rbd_ec_k8m3_12osd,
    stripe unit 4096): the 3 x 1 column of the coding matrix on one
    4 KiB delta row and the zero-seed crc of the four rows, (1, 1, 4096)
    uint8 in, (1, 3, 4096) uint8 and (1, 4) uint32 out. A thousandth of
    the served write's shape: no gather, and scratch under a MiB."""
    from ceph_tpu.osd.ecbackend import ECBackend
    D = np.ascontiguousarray(coder.delta_matrix((column,)), np.uint8)
    assert D.shape == (M, 1)
    fn = ECBackend._fused_delta_fn(D.tobytes(), M, 1, 4096, 1)
    out = fn.lower(_struct(one_chip, (1, 1, 4096), np.uint8)).compile()
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < 1 * MiB


# -- a vector code: Clay k=8 m=4 d=11 (rados_clay_k8m4d11_13osd_1out) ------

@pytest.fixture(scope="module")
def clay():
    from ceph_tpu.ec.registry import factory
    return factory("plugin=clay k=8 m=4 d=11")


def test_the_fused_clay_write_compiles_at_bucket_1(one_chip, clay):
    """What a served write of the Clay pool launches: the 256 x 512
    encode over the (64 x 8, 8 KiB) sub-chunk view of the data rows on
    the dense lowering, then the crcs of all 12 rows."""
    from ceph_tpu.osd.ecbackend import ECBackend
    D, planes = clay.vector_encode_matrix()
    assert D.shape == (256, 512) and planes == 64
    fn = ECBackend._fused_write_fn(
        np.ascontiguousarray(D, np.uint8).tobytes(), 4, 8, SHARD, 1, planes)
    out = _compile(fn, one_chip, ((1, 8, SHARD), np.uint8))
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < 512 * MiB


def test_the_clay_range_repair_compiles_at_the_wire_tiers_grant(
        one_chip, clay):
    """One grant of the Clay pool's backfill: 24 MiB of helper bytes is
    16 objects of 11 quarter rows; the 64 x 176 repair matrix of lost
    slot 1, whose 16 repair planes are 16 ranges of 8 KiB."""
    from ceph_tpu.osd.ecbackend import _build_recover_program
    lost = 1
    helper = tuple(i for i in range(12) if i != lost)
    fn = _build_recover_program(clay.range_batch_decoder((lost,), helper),
                                verify=True, host_crc=False)
    out = _compile(fn, one_chip, ((16, 11, SHARD // 4), np.uint8),
                   ((16,), np.uint32))
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < 1024 * MiB


# -- a layered code: LRC k=8 m=4 l=3 (rados_lrc_k8m4l3_17osd_1out) --------

@pytest.fixture(scope="module")
def lrc():
    from ceph_tpu.ec.registry import factory
    return factory("plugin=lrc k=8 m=4 l=3")


@pytest.mark.parametrize("bucket", [1, 16])
def test_the_fused_lrc_write_compiles(one_chip, lrc, bucket):
    """What a served write of the LRC pool launches: the 8 x 8 generator
    of the layers composed (64 entries: the unrolled lowering) over the
    8 data rows, then the crcs of all 16 rows."""
    from ceph_tpu.osd.ecbackend import ECBackend
    G = np.ascontiguousarray(lrc.encode_matrix(), np.uint8)
    assert G.shape == (8, 8)
    fn = ECBackend._fused_write_fn(G.tobytes(), 8, 8, SHARD, bucket)
    out = _compile(fn, one_chip, ((bucket, 8, SHARD), np.uint8))
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < bucket * 64 * MiB


def test_the_lrc_local_repair_compiles_at_the_wire_tiers_grant(
        one_chip, lrc):
    """One grant of the LRC pool's backfill: 24 MiB of helper bytes is
    16 objects of 3 whole rows; lost slot 2 from the 3 other members of
    its group."""
    from ceph_tpu.osd.ecbackend import _build_recover_program
    fn = _build_recover_program(lrc.batch_decoder((2,), (0, 1, 3)),
                                verify=True, host_crc=False)
    out = _compile(fn, one_chip, ((16, 3, SHARD), np.uint8),
                   ((16,), np.uint32))
    assert _no_gather(out)
    assert out.memory_analysis().temp_size_in_bytes < 256 * MiB
