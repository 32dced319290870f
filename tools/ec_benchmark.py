"""EC benchmark CLI — flag-compatible recreation of the reference's tool.

Mirrors src/test/erasure-code/ceph_erasure_code_benchmark.cc
(ErasureCodeBench::{setup,run,encode,decode}; CLI: --plugin --parameter
k=.. m=.. --size --iterations --workload encode|decode --erasures),
extended with a TPU batching knob (--batch) since the unit of work here
is a batch of objects, not one buffer.

Examples:
  python tools/ec_benchmark.py --plugin tpu_rs -P k=8 -P m=3 \
      --size $((4*1024*1024)) --batch 64 --iterations 8 --workload encode
  python tools/ec_benchmark.py -P k=8 -P m=3 --workload decode --erasures 2
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--plugin", "-p", default=None,
                    help="EC plugin name [tpu_rs; -P plugin=... also works]")
    ap.add_argument("--parameter", "-P", action="append", default=[],
                    help="profile key=value (k=8, m=3, technique=reed_sol_van)")
    ap.add_argument("--size", "-s", type=int, default=4 * 1024 * 1024,
                    help="object (stripe) size in bytes [4 MiB]")
    ap.add_argument("--batch", "-b", type=int, default=64,
                    help="objects encoded per device launch")
    ap.add_argument("--iterations", "-i", type=int, default=8)
    ap.add_argument("--workload", "-w", choices=["encode", "decode"],
                    default="encode")
    ap.add_argument("--erasures", "-e", type=int, default=1,
                    help="chunks erased per object for decode")
    ap.add_argument("--stream-tile", type=int, default=0, metavar="BYTES",
                    help="stream host-resident chunks through the device "
                         "in tiles of this many bytes (the >HBM object "
                         "path; plain RS only)")
    ap.add_argument("--json", action="store_true", help="emit one JSON line")
    return ap.parse_args(argv)


def run_bench(plugin: str, profile: dict, size: int, batch: int,
              iterations: int, workload: str, erasures: int,
              stream_tile: int = 0) -> dict:
    """Returns {seconds, gbps, bytes_per_iter, ...}. Timing covers only the
    codec region, like ErasureCodeBench::encode/decode (buffers prepared
    outside the loop, one warmup launch excluded for jit compile)."""
    import jax

    from ceph_tpu.ec import registry
    from ceph_tpu.gf.numpy_ref import decode_matrix
    from ceph_tpu.ops.rs_kernels import make_encoder

    prof = dict(profile)
    if plugin is not None:
        if prof.get("plugin", plugin) != plugin:
            raise SystemExit(f"--plugin {plugin} conflicts with "
                             f"-P plugin={prof['plugin']}")
        prof["plugin"] = plugin
    prof.setdefault("plugin", "tpu_rs")
    plugin = prof["plugin"]
    try:
        coder = registry.factory(prof)
    except ValueError as e:
        raise SystemExit(str(e))
    k, m = coder.k, coder.m
    cs = coder.get_chunk_size(size)

    rng = np.random.default_rng(0)
    data = rng.integers(0, 256, size=(batch, k, cs), dtype=np.uint8)

    from ceph_tpu.ec.rs import ReedSolomon
    if stream_tile and not isinstance(coder, ReedSolomon):
        raise SystemExit("--stream-tile needs a plain RS plugin "
                         "(layered plugins plan their own decode)")
    if isinstance(coder, ReedSolomon):
        # plain-MDS fast path: time the raw device kernel (the measured
        # region of ceph_erasure_code_benchmark — codec math only).
        # Layered / non-MDS plugins (lrc, clay, shec) have their own
        # decode planning and must NOT take this path.
        if workload == "encode":
            mat = coder.matrix
        else:
            if not 0 < erasures <= m:
                raise SystemExit(
                    f"--erasures must be in [1, m={m}], got {erasures}")
            ers = tuple(range(erasures))
            survivors = tuple(range(erasures, erasures + k))
            mat = decode_matrix(coder.matrix, list(ers), k,
                                list(survivors))
        if stream_tile:
            # host-resident path: double-buffered tile streaming (the
            # >HBM object dataflow; ceph_tpu/ops/streaming.py). The
            # full array never lands in HBM — only `depth` tiles — and
            # timing includes host<->device transfers: that IS the
            # workload being measured.
            from ceph_tpu.ops.streaming import StreamingCodec
            sc = StreamingCodec(mat, tile=stream_tile)
            out_buf = sc.encode(data)  # warmup / compile
            t0 = time.perf_counter()
            for _ in range(iterations):
                sc.encode(data, out=out_buf)
            dt = time.perf_counter() - t0
        else:
            fn = make_encoder(mat, bucket_batch=False)
            operand = jax.device_put(data)
            fn(operand).block_until_ready()  # warmup / compile
            t0 = time.perf_counter()
            for _ in range(iterations):
                out = fn(operand)
            out.block_until_ready()
            dt = time.perf_counter() - t0
    else:
        # layered / non-MDS plugins (clay, lrc, shec): time the full
        # plugin path, including their own recovery planning
        if workload == "encode":
            run = lambda: coder.encode_chunks(data)  # noqa: E731
        else:
            if not 0 < erasures <= m:
                raise SystemExit(
                    f"--erasures must be in [1, m={m}], got {erasures}")
            parity = coder.encode_chunks(data)
            full = {i: data[:, i, :] for i in range(k)}
            full.update({k + j: parity[:, j, :] for j in range(m)})
            ers = list(range(erasures))
            try:
                need = coder.minimum_to_decode(
                    ers, [c for c in full if c not in set(ers)])
            except ValueError as e:
                raise SystemExit(str(e))
            have = {c: full[c] for c in need if c not in set(ers)}
            run = lambda: coder.decode_chunks(ers, have)  # noqa: E731
        run()  # warmup / compile
        t0 = time.perf_counter()
        for _ in range(iterations):
            run()
        dt = time.perf_counter() - t0

    payload = batch * k * cs  # bytes of data processed per iteration
    return {
        "plugin": plugin, "k": k, "m": m, "chunk_size": cs,
        "object_size": size, "batch": batch, "iterations": iterations,
        "workload": workload, "erasures": erasures if workload == "decode" else 0,
        "seconds": dt,
        "bytes_per_iter": payload,
        "gbps": payload * iterations / dt / 1e9,
        "backend": jax.default_backend(),
    }


def main(argv=None) -> None:
    args = parse_args(argv)
    from ceph_tpu.ec.interface import profile_from_string
    try:
        profile = profile_from_string(" ".join(args.parameter))
    except ValueError as e:
        raise SystemExit(f"--parameter: {e}")
    r = run_bench(args.plugin, profile, args.size, args.batch,
                  args.iterations, args.workload, args.erasures,
                  stream_tile=args.stream_tile)
    if args.json:
        print(json.dumps(r))
    else:
        print(f"{r['workload']} {r['plugin']} k={r['k']} m={r['m']}: "
              f"{r['seconds']:.3f}s for "
              f"{r['iterations']}x{r['bytes_per_iter'] / 1e6:.1f} MB "
              f"-> {r['gbps']:.2f} GB/s [{r['backend']}]")


if __name__ == "__main__":
    main()
