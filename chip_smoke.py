#!/usr/bin/env python3
"""Proof that the served erasure-coding path runs on the chip.

    python chip_smoke.py            # one TPU chip: every phase below
    python chip_smoke.py --chips 4  # the sharded mesh path, nothing else

One process, the entry points a user calls, the north-star geometry
(jerasure reed_sol_van k=8 m=3, 4 MiB objects, 512 KiB shard rows),
every result checked against the in-repo oracles. Each phase prints one
timed line; a phase that raises ends the run with a non-zero exit. The
last line of stdout is the JSON verdict. Any platform but `tpu` is an
error before any phase runs: there is no CPU substitute.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time

import numpy as np

from ceph_tpu.utils.jax_cache import enable_persistent_compile_cache

K, M = 8, 3
PROFILE = f"plugin=jerasure technique=reed_sol_van k={K} m={M}"
OBJECT_BYTES = 4 << 20
SHARD_BYTES = OBJECT_BYTES // K
ERASURES = ((0, 9), (2, 5, 10))
SEED = 22


def phase(name: str, t0: float, **facts) -> None:
    print(f"phase {name}: {time.perf_counter() - t0:.2f} s "
          + json.dumps(facts, sort_keys=True), flush=True)


def check_decodes(decode, chunks: np.ndarray) -> None:
    """decode(erasures, survivors) -> (B, E, L) must give back the
    erased rows of the dense (B, k+m, L) chunk stack byte for byte."""
    for lost in ERASURES:
        surv = tuple(i for i in range(K + M) if i not in lost)[:K]
        got = np.asarray(decode(lost, surv))
        if not np.array_equal(got, chunks[:, list(lost), :]):
            raise AssertionError(f"decode of erasures {lost} differs")


def codec_phase(n_objects: int, shard_bytes: int) -> None:
    """The pool's profile as the cells state it: one encode launch over
    n_objects, parity equal to the numpy oracle, two degraded decodes
    equal to the originals (its matrices take the unrolled lowering),
    and the host clock of one more `encode_chunks` call of the same
    shape, warm: host rows in, parity on the host (the host face).
    Then one encode through a matrix on the dense side of the rule
    (Clay k=4 m=2 d=5's solved 16 x 32) against the codec's numpy
    oracle, so both lowerings stay proved on the chip."""
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.gf.numpy_ref import encode_ref
    rng = np.random.default_rng(SEED)
    data = rng.integers(0, 256, (n_objects, K, shard_bytes), np.uint8)
    t0 = time.perf_counter()
    coder = factory(PROFILE)
    parity = coder.encode_chunks(data)
    t_enc = time.perf_counter() - t0
    if not np.array_equal(parity, encode_ref(coder.matrix, data)):
        raise AssertionError("parity differs from encode_ref")
    chunks = np.concatenate([data, parity], axis=1)

    def decode(lost, surv):
        got = coder.decode_chunks(lost, {s: chunks[:, s, :] for s in surv})
        return np.stack([got[e] for e in lost], axis=1)
    check_decodes(decode, chunks)
    t1 = time.perf_counter()
    coder.encode_chunks(data)
    t_warm = time.perf_counter() - t1
    phase("codec[rs]", t0, objects=n_objects, shard_bytes=shard_bytes,
          first_encode_s=round(t_enc, 2),
          warm_encode_ms=round(t_warm * 1e3, 2))

    t0 = time.perf_counter()
    clay, clay_data = "plugin=clay k=4 m=2 d=5", data[:, :4, :]
    parity = factory(clay).encode_chunks(clay_data)
    t_enc = time.perf_counter() - t0
    if not np.array_equal(
            parity, factory(f"{clay} impl=ref").encode_chunks(clay_data)):
        raise AssertionError("clay parity differs from encode_ref")
    phase("codec[clay]", t0, objects=n_objects, shard_bytes=shard_bytes,
          first_encode_s=round(t_enc, 2))


def _read_all(client, objects: dict[str, bytes], what: str) -> None:
    for name, want in objects.items():
        if client.read(name) != want:
            raise AssertionError(f"{what}: {name} differs")


def served_phase(n_objects: int, object_bytes: int) -> dict:
    """12 OSD daemons + 3 monitors in this process, cephx and secure
    frames, TinStore: write, read, kill an OSD and mark it down, read
    degraded while it is down and in, let it go out, recover to the
    spare OSD, read again. Returns the daemons' summed counters.

    op_timeout stays at the harness default (8 s): it also bounds every
    OSD-to-OSD call, and daemons that wait on each other under their
    locks only let go when it expires — at 300 s the cluster froze for
    minutes behind one hedged read. A client op that meets a first
    compile (one per erasure pattern and batch bucket, seconds each) is
    covered by the client's own retry rounds instead. The heartbeat
    runs at 2 s with 45 s of grace (Ceph's own defaults are 6 s and
    20 s): grace has to outlast such a wait and a compile under a
    daemon lock, and at the harness's 0.25 s / 1.2 s fifteen daemons
    under one interpreter lock report each other dead while they boot."""
    from ceph_tpu import native
    from ceph_tpu.osd.standalone import StandaloneCluster
    # the stores' and frames' host crc32c: built once here (a checkout
    # holds no binary), not raced by fifteen daemons on first use
    native.build()
    rng = np.random.default_rng(SEED + 1)
    objects = {f"smoke-{i}": rng.integers(0, 256, object_bytes,
                                          np.uint8).tobytes()
               for i in range(n_objects)}
    with tempfile.TemporaryDirectory(prefix="smoke-tin-") as store_dir:
        t0 = time.perf_counter()
        cluster = StandaloneCluster(
            n_osds=12, pg_num=8, profile=PROFILE, store="tin",
            store_dir=store_dir, cephx=True, secret=b"chip smoke key 1" * 2,
            hb_interval=2.0, hb_grace=45.0)
        try:
            cluster.wait_for_clean(timeout=60)
            client = cluster.client()
            phase("served.boot", t0, osds=12, pgs=8)

            t0 = time.perf_counter()
            client.write(objects)
            phase("served.write", t0, objects=n_objects,
                  object_bytes=object_bytes)
            t0 = time.perf_counter()
            _read_all(client, objects, "read")
            phase("served.read", t0, objects=n_objects)

            # a victim that holds a shard of every PG it can and is no
            # PG's primary: the kill then exercises degraded decode and
            # recovery, not fail-over
            osdmap = client.osdmap
            acting = [osdmap.pg_to_up_acting_osds(1, ps)[2]
                      for ps in range(cluster.pg_num)]
            primaries = {a[0] for a in acting}
            victim = max((o for o in cluster.osd_ids()
                          if o not in primaries),
                         key=lambda o: sum(o in a for a in acting))
            # down and not yet out: the admin `down` marks the dead
            # daemon at once (no 45 s of heartbeat grace to read
            # through), and the interval keeps it in, so every read of
            # a PG it held a data slot of is rebuilt by the decode
            t0 = time.perf_counter()
            client.config_set("mon_osd_down_out_interval", 600)
            cluster.kill_osd(victim)
            client.osd_down(victim)
            _read_all(client, objects, "degraded read")
            phase("served.degraded_read", t0, objects=n_objects,
                  killed_osd=victim,
                  pgs_hit=sum(victim in a for a in acting))

            # back to the harness's interval (0): the monitors' next
            # tick marks it out, the spare takes its slots, recovery
            t0 = time.perf_counter()
            client.config_rm("mon_osd_down_out_interval")
            cluster.wait_for_clean(timeout=600)
            phase("served.recovery", t0)
            t0 = time.perf_counter()
            _read_all(client, objects, "read after recovery")
            phase("served.read_after_recovery", t0, objects=n_objects)

            counters = {key: sum(int(d.ec_perf.get(key))
                                 for d in cluster.osds.values())
                        for key in ("fused_write_launches",
                                    "recover_launches", "decode_launches",
                                    "degraded_reads",
                                    "host_decode_launches",
                                    "host_encode_launches",
                                    "recovered_objects",
                                    "program_cache_misses")}
        finally:
            cluster.shutdown()
    return counters


def assert_device_did_the_work(counters: dict) -> None:
    print("served counters: " + json.dumps(counters, sort_keys=True),
          flush=True)
    if not (counters["fused_write_launches"] > 0
            and counters["recover_launches"] > 0
            and counters["degraded_reads"] > 0
            and counters["host_decode_launches"] == 0
            and counters["host_encode_launches"] == 0):
        raise AssertionError(
            f"the device did not serve the EC path: {counters}")


def placement_phase(n_pgs: int, n_osds: int, lanes: int,
                    sample: int) -> None:
    from ceph_tpu.crush.map import build_hierarchy, ec_rule
    from ceph_tpu.crush.mapper import VectorMapper, full_weights
    from ceph_tpu.crush.oracle import OracleMapper
    t0 = time.perf_counter()
    crush = build_hierarchy(n_osds, osds_per_host=10, hosts_per_rack=10)
    ec_rule(crush, rule_id=1, choose_type=1)
    vm, oracle = VectorMapper(crush), OracleMapper(crush)
    weights = full_weights(n_osds)
    xs = np.arange(n_pgs, dtype=np.uint32)
    placed = np.concatenate(
        [np.asarray(vm.do_rule(1, xs[i:i + lanes], weights, K + M))
         for i in range(0, n_pgs, lanes)])
    t_dev = time.perf_counter() - t0
    picks = np.random.default_rng(SEED + 2).choice(n_pgs, sample,
                                                   replace=False)
    for x in picks:
        want = oracle.do_rule(1, int(x), weights, K + M)
        if list(placed[x]) != list(want):
            raise AssertionError(f"crush: pg {x} placed {placed[x]}, "
                                 f"oracle {want}")
    phase("placement", t0, pgs=n_pgs, osds=n_osds, lanes=lanes,
          checked=sample, device_s=round(t_dev, 2))


def checksum_phase(total_bytes: int, block: int, unique: int) -> None:
    """One Checksummer pass per algorithm over total_bytes. The blocks
    are `unique` random ones in a seeded order, so that the pure-Python
    reference prices every block of the pass in seconds."""
    from ceph_tpu.csum import reference
    from ceph_tpu.csum.checksummer import Checksummer
    rng = np.random.default_rng(SEED + 3)
    pool = rng.integers(0, 256, (unique, block), np.uint8)
    order = rng.integers(0, unique, total_bytes // block)
    data = pool[order]
    refs = {"crc32c": lambda b: reference.ceph_crc32c(0xFFFFFFFF, b),
            "xxhash32": reference.xxh32, "xxhash64": reference.xxh64}
    for algorithm, ref in refs.items():
        t0 = time.perf_counter()
        got = Checksummer(algorithm, block).calculate(data)
        t_dev = time.perf_counter() - t0
        want = np.array([ref(b) for b in pool], dtype=got.dtype)[order]
        if not np.array_equal(got, want):
            raise AssertionError(f"{algorithm} differs from the reference")
        phase(f"checksum[{algorithm}]", t0, bytes=total_bytes,
              block=block, device_s=round(t_dev, 2))


def mesh_phase(devices, n_objects: int, shard_bytes: int) -> None:
    """Sharded encode and degraded decodes on a (dp=2, shard=2) mesh
    over four devices, against the numpy oracle."""
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.gf.numpy_ref import encode_ref
    from ceph_tpu.parallel.mesh import (default_mesh, make_sharded_decoder,
                                        make_sharded_encoder)
    t0 = time.perf_counter()
    matrix = factory(PROFILE).matrix
    mesh = default_mesh(devices, shard=2)
    rng = np.random.default_rng(SEED + 4)
    data = rng.integers(0, 256, (n_objects, K, shard_bytes), np.uint8)
    chunks = make_sharded_encoder(matrix, mesh)(data)
    holders = {s.device for s in chunks.addressable_shards}
    if len(holders) != 4 or any(
            s.data.shape != (n_objects // 2, chunks.shape[1] // 2,
                             shard_bytes)
            for s in chunks.addressable_shards):
        raise AssertionError(f"chunks are not spread over the (2, 2) "
                             f"mesh: {chunks.sharding}")
    want = np.concatenate([data, encode_ref(matrix, data)], axis=1)
    if not np.array_equal(np.asarray(chunks)[:, :K + M], want):
        raise AssertionError("sharded encode differs from encode_ref")
    check_decodes(
        lambda lost, surv: make_sharded_decoder(matrix, lost, surv,
                                                mesh)(chunks), want)
    phase("mesh", t0, objects=n_objects, shard_bytes=shard_bytes,
          mesh=dict(mesh.shape), devices=len(holders))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run the sharded mesh path and nothing else")
    args = ap.parse_args()

    import jax
    t_start = time.perf_counter()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(f"chip_smoke: need {args.chips} tpu device(s), jax found "
              f"{len(devices)} x {devices[0].platform}", file=sys.stderr)
        return 1
    enable_persistent_compile_cache()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print("device: " + json.dumps(device), flush=True)

    if args.chips == 4:
        mesh_phase(devices[:4], 16, SHARD_BYTES)
    else:
        codec_phase(32, SHARD_BYTES)
        assert_device_did_the_work(served_phase(16, OBJECT_BYTES))
        placement_phase(100_000, 1_000, 10_000, 1_000)
        checksum_phase(256 << 20, 4096, 1024)
    print(f"total: {time.perf_counter() - t_start:.2f} s", flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
