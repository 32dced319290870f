"""The part of a client op's store commits that grows with their count:
the wall time of the detail span `store.commit.wal` (the KV batch built,
TinDB's expand, encode, WAL append and flush, memtable apply), inside
`store.commit` and beside its whole."""

from bench.span_stages import self_ms_per_op

META = {"layer": "store", "source": "program_span",
        "moves": "op_p95_ms"}
NAMES = ("store.commit.wal",)


def compute(run: dict) -> float | None:
    return self_ms_per_op(run, NAMES)
