"""Plain CRC-32C (Castagnoli, reflected polynomial 0x82F63B78), the
benchmark's own copy, independent of `ceph_tpu/`.

`crc32c_rows` follows Ceph's `ceph_crc32c(seed, data)` convention: the
raw register update from `seed`, no final inversion. The byte loop runs
over 4 KiB segments of every row at once and folds the segments with the
register's shift-through-zero-bytes operator, so a few hundred 512 KiB
rows take about a second in numpy.
"""

from __future__ import annotations

import functools

import numpy as np

_POLY = 0x82F63B78
_SEGMENT = 4096


@functools.cache
def _table() -> np.ndarray:
    t = np.arange(256, dtype=np.uint32)
    for _ in range(8):
        t = np.where(t & 1, (t >> 1) ^ np.uint32(_POLY), t >> 1)
    return t.astype(np.uint32)


def _update(reg: np.ndarray, columns: np.ndarray) -> np.ndarray:
    """Advance the registers `reg` (N,) through `columns` (L, N): byte
    `columns[i]` goes into every register at step i."""
    t = _table()
    for col in columns:
        reg = (reg >> np.uint32(8)) ^ t[(reg ^ col) & np.uint32(0xFF)]
    return reg


@functools.cache
def _shift_tables(nbytes: int) -> np.ndarray:
    """(4, 256) tables of the linear operator 'advance a register
    through `nbytes` zero bytes', one table per register byte."""
    basis = np.uint32(1) << np.arange(32, dtype=np.uint32)
    shifted = _update(basis, np.zeros((nbytes, 32), np.uint32))
    tables = np.zeros((4, 256), np.uint32)
    for byte in range(4):
        for bit in range(8):
            on = (np.arange(256) >> bit) & 1 == 1
            tables[byte, on] ^= shifted[8 * byte + bit]
    return tables


def _shift(reg: np.ndarray, nbytes: int) -> np.ndarray:
    t = _shift_tables(nbytes)
    return (t[0][reg & 0xFF] ^ t[1][(reg >> 8) & 0xFF]
            ^ t[2][(reg >> 16) & 0xFF] ^ t[3][reg >> 24])


def crc32c_rows(seed: int, rows: np.ndarray) -> np.ndarray:
    """Raw-register crc32c of each row of a (R, L) uint8 array, every
    row started from `seed`. Returns (R,) uint32."""
    rows = np.ascontiguousarray(rows, np.uint8)
    n_rows, length = rows.shape
    seg = _SEGMENT if length % _SEGMENT == 0 and length else max(length, 1)
    n_seg = max(length // seg, 1)
    if length == 0:
        return np.full(n_rows, seed & 0xFFFFFFFF, np.uint32)
    # (seg, R * n_seg): step i feeds byte i of every segment
    columns = np.ascontiguousarray(
        rows.reshape(n_rows * n_seg, seg).T).astype(np.uint32)
    partial = _update(np.zeros(n_rows * n_seg, np.uint32),
                      columns).reshape(n_rows, n_seg)
    reg = np.full(n_rows, seed & 0xFFFFFFFF, np.uint32)
    for s in range(n_seg):
        # crc(seed, A + B) = shift(crc(seed, A), len(B)) ^ crc(0, B)
        reg = _shift(reg, seg) ^ partial[:, s]
    return reg
