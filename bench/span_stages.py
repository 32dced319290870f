"""The program's span log (`ceph_tpu/utils/tracing.py`) as the per-layer
readers take it.

While a profiler session is live every stage span of the served path
that ends is logged with its self time (its duration minus what its
child spans on the same thread covered), on `time.perf_counter`; with
none live nothing but compiles is logged. So in a traced run the log's
spans are those of the traced seconds, and their self times by name over
`run["traced_ops"]` are each stage's busy or waiting time a client op,
summed over every daemon and thread that worked for it: not the op's
critical path. A program without the log has nothing to read.
"""

from __future__ import annotations


def tracing():
    """The program's tracing module where it keeps a span log."""
    try:
        from ceph_tpu.utils import tracing as module
    except ImportError:
        return None
    return module if hasattr(module, "span_log") else None


def self_ms_per_op(run: dict, names: tuple[str, ...]) -> float | None:
    """Self time of the spans called `names`, for each client op that
    completed in the traced seconds."""
    module = tracing()
    if module is None or not run.get("trace") or not run.get("traced_ops"):
        return None
    table = module.stage_table(module.span_log(), run["traced_ops"])
    found = [table[name]["self_ms_per_op"] for name in names if name in table]
    return sum(found) if found else None
