"""What an LRC codeword is, held to its definition: the benchmark's own
statement, in numpy.

Independent of `ceph_tpu/`: nothing of the program's LRC coder is
imported, and no generator is composed or probed here. Only `gf256.py`
(the field and each layer's reed_sol_van), `rs_decode.py`, `crc32c.py`
and `recovered_pool.data_rows` beside it are. The layered code of Ceph's
lrc plugin, profile `k m l`, as the program lays it out (the position
order of the documentation's low-level example, `__DD__DD` at k=4 m=2
l=3):

* (k + m) / l groups of l + 1 positions, n = k + m + groups in all; the
  first position of each group is its local parity;
* the m global parities go to the groups in turn, each to the earliest
  position of its group not yet taken; every other position holds data,
  the object's k data rows in ascending position order;
* the global layer encodes the k data rows into the m global parities
  (ascending positions) under reed_sol_van(k, m); then each group's
  local layer encodes its l other positions, in ascending order, into
  its local parity under reed_sol_van(l, 1).

The code is systematic and every position is a function of the data, so
the object's striped rows fix all n rows: `check` is exact, with no
tolerance. `rebuilt` is a lost row decoded from the other members of its
local group by `rs_decode`, another route to the same bytes.
"""

from __future__ import annotations

import functools

import numpy as np

from bench.reference import crc32c, gf256
from bench.reference.recovered_pool import CRC_SEED, data_rows
from bench.reference.rs_decode import rs_decode


@functools.cache
def layout(k: int, m: int, l: int) -> tuple[tuple[int, ...], tuple]:
    """(data positions, layers): each layer (input positions, parity
    positions), the global layer first, then one a group."""
    if l < 2 or (k + m) % l:
        raise ValueError(f"k+m={k + m} must be a multiple of l={l} >= 2")
    groups = (k + m) // l
    n = k + m + groups
    local = [g * (l + 1) for g in range(groups)]
    free = [[p for p in range(g * (l + 1) + 1, (g + 1) * (l + 1))]
            for g in range(groups)]
    glob = sorted(free[i % groups].pop(0) for i in range(m))
    data = tuple(p for p in range(n) if p not in local and p not in glob)
    layers = [(data, tuple(glob))]
    for c in local:
        layers.append((tuple(p for p in range(c + 1, c + l + 1)), (c,)))
    return data, tuple(layers)


def mapping(k: int, m: int, l: int) -> str:
    """The profile's mapping string: D at the data positions."""
    data, layers = layout(k, m, l)
    n = k + m + len(layers) - 1
    return "".join("D" if p in data else "_" for p in range(n))


def codeword(payload: bytes, k: int, m: int, l: int,
             stripe_unit: int) -> np.ndarray:
    """The (n, row) rows by position of one object."""
    data, layers = layout(k, m, l)
    rows = data_rows(payload, k, stripe_unit)
    out = np.zeros((k + m + len(layers) - 1, rows.shape[1]), np.uint8)
    out[list(data)] = rows
    for ins, outs in layers:
        out[list(outs)] = gf256.rs_encode(
            gf256.reed_sol_van(len(ins), len(outs)), out[list(ins)])
    return out


def crcs(rows: np.ndarray) -> np.ndarray:
    """Ceph's hinfo crc of each row: crc32c, seed 0xFFFFFFFF, no final
    xor."""
    return crc32c.crc32c_rows(CRC_SEED, rows)


def check(payload: bytes, rows: list, k: int, m: int, l: int,
          stripe_unit: int) -> list[int]:
    """The positions whose stored row is missing or is not the
    codeword's row of `payload`."""
    want = codeword(payload, k, m, l, stripe_unit)
    return [p for p in range(len(want))
            if rows[p] is None or not np.array_equal(rows[p], want[p])]


def group_of(lost: int, k: int, m: int, l: int) -> tuple:
    """(input positions, parity position) of the local layer that holds
    `lost`."""
    for ins, outs in layout(k, m, l)[1][1:]:
        if lost in ins or lost in outs:
            return ins, outs[0]
    raise ValueError(f"position {lost} is in no local group")


def rebuilt(rows: list, lost: int, k: int, m: int, l: int) -> np.ndarray:
    """The row of position `lost` decoded from the l other members of
    its local group, as `rows` (by position) holds them."""
    ins, parity = group_of(lost, k, m, l)
    members = list(ins) + [parity]          # the layer's shard order
    present = [i for i, p in enumerate(members) if p != lost]
    stack = np.stack([rows[members[i]] for i in present])
    return rs_decode(gf256.reed_sol_van(l, 1), stack, present,
                     [members.index(lost)])[0]
