#!/usr/bin/env python
"""CRUSH config #5, run IN FULL: 10M placements on a 10k-OSD map.

BASELINE row 5: the 10M figure had only ever been extrapolated from
capped sub-batches; this tool records the real run into CRUSH_10M.json.

The whole 10M-placement loop runs INSIDE one jitted lax.scan
(VectorMapper.scan_rule) with device-generated seeds and an XOR digest
carry: one dispatch and one host round trip instead of a thousand.
The digest data-depends on every placement, so nothing is elided; the
clock stops when the scalar digest lands on the host.

Ref: src/crush/mapper.c crush_do_rule; src/tools/crushtool.cc --test
(the --num-rep batch mapping loop this measures the analog of).

Usage: [SUB=10000] [NB=1000] [WARM_NB=NB] python tools/crush_10m.py

WARM_NB shortens the warm-up dispatch (compile + determinism check run
at WARM_NB steps instead of the full NB) so a CPU-backend run — where
one full pass is ~half an hour — doesn't pay the 10M loop twice.
Determinism is still asserted: the warm-size scan runs twice and must
produce the same digest. WARM_NB=NB (default) keeps the original
behavior of asserting determinism on the full-size scan itself.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np  # noqa: E402

from ceph_tpu.crush.map import build_hierarchy, ec_rule  # noqa: E402
from ceph_tpu.crush.mapper import VectorMapper, full_weights  # noqa: E402

OUT = Path(__file__).resolve().parent.parent / "CRUSH_10M.json"
SUB = int(os.environ.get("SUB", 10_000))       # lanes per scan step
NB = int(os.environ.get("NB", 1_000))          # scan steps per dispatch
WARM_NB = int(os.environ.get("WARM_NB", NB))   # warm/compile scan steps
K, M = 8, 3


def main() -> None:
    import jax
    m = build_hierarchy(10_000, osds_per_host=10, hosts_per_rack=25)
    ec_rule(m, rule_id=1, choose_type=1)
    vm = VectorMapper(m)
    weights = full_weights(10_000)
    backend = jax.default_backend()
    total = SUB * NB
    t0 = time.perf_counter()
    digest_w, _ = vm.scan_rule(1, weights, K + M, 0, SUB, WARM_NB)
    warm_s = time.perf_counter() - t0
    print(f"compile+warm run ({WARM_NB} steps): {warm_s:.1f}s "
          f"(backend={backend}, digest={digest_w})", flush=True)
    digest_w2, _ = vm.scan_rule(1, weights, K + M, 0, SUB, WARM_NB)
    assert digest_w2 == digest_w, "non-deterministic placement"
    t0 = time.perf_counter()
    digest, last = vm.scan_rule(1, weights, K + M, 0, SUB, NB)
    dt = time.perf_counter() - t0
    if WARM_NB == NB:
        assert digest == digest_w, "non-deterministic placement"
    filled = int((np.asarray(last) >= 0).sum(axis=1).min())
    payload = {
        "crush_placements_per_s_10M": round(total / dt, 1),
        "n_placements": total,
        "numrep": K + M,
        "min_filled_last_batch": filled,
        "elapsed_s": round(dt, 2),
        "compile_plus_first_s": round(warm_s, 1),
        "scan_sub": SUB,
        "scan_steps": NB,
        "warm_steps": WARM_NB,
        "digest": digest,
        "backend": backend,
        "n_osds": 10_000,
        "note": "full config #5 run in one device dispatch (lax.scan, "
                "digest-synced); no extrapolation"
                + ("" if WARM_NB == NB else
                   "; elapsed_s includes the full-size scan's own "
                   "compile (warm run used a shorter scan)"),
    }
    OUT.write_text(json.dumps(payload, indent=1) + "\n")
    print(json.dumps(payload), flush=True)


if __name__ == "__main__":
    main()
