"""What an EC pool has to hold once it has rebuilt a lost OSD's shards
onto its new members: the benchmark's own statement, in numpy.

Independent of `ceph_tpu/`; only `gf256.py`, `crc32c.py` and
`rs_decode.py` beside it are imported. Three things are stated here:

* the stripe of an object: its bytes dealt `stripe_unit` bytes to each of
  the k data rows in turn, m parity rows under the coding matrix, and the
  hinfo crc of each of the k+m rows (`ceph_crc32c`, seed 0xFFFFFFFF, no
  final xor). Every object of the pool, whether it was written before the
  failure or while the pool rebuilt, holds exactly these on the PG's
  *new* acting set: row s on the OSD that now acts for slot s;
* which slots a failure re-pointed, from the acting sets before and after
  it, and which of them lost their bytes with the failed OSD (those are
  rebuilt by a decode) as against those CRUSH moved between two live
  OSDs (those are copied);
* the rebuilt rows by another route: `rs_decode` of k rows that the
  failure did not touch, drawn by the caller's generator and not by the
  program's helper choice.
"""

from __future__ import annotations

import numpy as np

from bench.reference import crc32c, gf256
from bench.reference.rs_decode import rs_decode

CRC_SEED = 0xFFFFFFFF


def data_rows(payload: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """(k, row) data rows of one object: `stripe_unit` bytes to each row
    in turn."""
    flat = np.frombuffer(payload, np.uint8)
    return flat.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1)


def stripe(payload: bytes, matrix: np.ndarray, stripe_unit: int
           ) -> tuple[np.ndarray, np.ndarray]:
    """The (k+m, row) rows and their (k+m,) hinfo crcs."""
    m, k = matrix.shape
    data = data_rows(payload, k, stripe_unit)
    rows = np.concatenate([data, gf256.rs_encode(matrix, data)])
    return rows, crc32c.crc32c_rows(CRC_SEED, rows)


def stripes(payloads: list, k: int, m: int, stripe_unit: int) -> list:
    """One stripe a payload, under the program's reed_sol_van matrix."""
    matrix = gf256.reed_sol_van(k, m)
    return [stripe(p, matrix, stripe_unit) for p in payloads]


def repointed(old: list, new: list, failed: int) -> list[dict]:
    """The slots of one PG whose OSD changed, each `{slot, old, new,
    lost}`: `lost` where the old holder is the failed OSD (nothing to
    copy from: the row is rebuilt), not where CRUSH moved a slot from one
    live OSD to another."""
    return [{"slot": s, "old": int(a), "new": int(b),
             "lost": int(a) == int(failed)}
            for s, (a, b) in enumerate(zip(old, new)) if int(a) != int(b)]


def holes(acting: list, n_osds: int) -> list[int]:
    """Slots of an acting set that name no OSD."""
    return [s for s, o in enumerate(acting) if not 0 <= int(o) < n_osds]


def rebuilt_rows(matrix: np.ndarray, rows: list, decode_from: list[int],
                 slots: list[int]) -> np.ndarray:
    """The rows of `slots` decoded from the k stored rows of the slots
    `decode_from` (the caller draws them, from slots the failure did not
    touch). `rows` is the object's k+m stored rows by slot. Returns
    (len(slots), row)."""
    return rs_decode(matrix, np.stack([rows[s] for s in decode_from]),
                     decode_from, slots)
