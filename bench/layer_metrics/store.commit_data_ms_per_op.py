"""The part of a client op's store commits that grows with their bytes:
the wall time of the detail spans `store.commit.stage` (the read-modify
copy, `tobytes`, compression), `.pwrite` (allocator, `ftruncate`,
`os.pwrite`, the `o_dsync` fsync) and `.csum` (the host crc32c of the
stored and logical bytes), inside `store.commit` and beside its whole."""

from bench.span_stages import self_ms_per_op

META = {"layer": "store", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("store.commit.stage", "store.commit.pwrite", "store.commit.csum")


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
