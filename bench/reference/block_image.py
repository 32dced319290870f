"""A plain block device, the benchmark's own: the reference of the
`rbd_bench` driver. numpy only; imports nothing of `ceph_tpu/`.

The image is `n_objects` objects of `object_bytes`, cut into blocks of
`block_bytes`. Its history is a fill (one payload an object) and then
writes of one block each, every one a dict with `object`, `offset` (in
the object), `payload` and `cut` (the block is
`payloads[payload][cut:cut + block_bytes]`), `start`, `end` (the
client's clock round the call) and `ok`.

The ordering rule. Acknowledged writes are applied in the order of their
acknowledgement (`end`), so the image holds, in every block, the write to
it that was acknowledged last. Where two writes to one block were in
flight at the same time the device may have applied them in either order:
a write W is *admissible* as the block's last content unless another
acknowledged write to the block was issued after W was acknowledged
(`start > W.end`): that one follows W whatever the device did. The write
acknowledged last is always admissible; a block with more than one
admissible write is a *racing* block, and what is read back from it has
to be one of them. A write that was not acknowledged is not applied:
whether it landed cannot be known, and the run that holds one is not
correct by its count of failed ops.
"""

from __future__ import annotations

import collections

import numpy as np


def filled(payloads: list[bytes], fill: list[int]) -> np.ndarray:
    """(n_objects, object_bytes) uint8: object j holds payload fill[j]."""
    return np.stack([np.frombuffer(payloads[p], np.uint8) for p in fill])


def block_of(payloads: list[bytes], write: dict, block_bytes: int
             ) -> np.ndarray:
    cut = write["cut"]
    return np.frombuffer(payloads[write["payload"]], np.uint8,
                         block_bytes, cut)


def replay(image: np.ndarray, payloads: list[bytes], history: list[dict],
           block_bytes: int) -> dict:
    """Apply the acknowledged writes of `history` to `image`, in place, in
    the order of their acknowledgement. Returns {(object, offset): [the
    other admissible blocks]} for the racing blocks."""
    acked = sorted((w for w in history if w["ok"]), key=lambda w: w["end"])
    by_block = collections.defaultdict(list)
    for w in acked:
        image[w["object"], w["offset"]:w["offset"] + block_bytes] = \
            block_of(payloads, w, block_bytes)
        by_block[w["object"], w["offset"]].append(w)
    racing = {}
    for where, writes in by_block.items():
        if len(writes) < 2:
            continue
        # admissible: nobody was issued after this one's acknowledgement
        issued_last = max(w["start"] for w in writes)
        others = [w for w in writes[:-1] if w["end"] >= issued_last]
        if others:
            racing[where] = [block_of(payloads, w, block_bytes)
                             for w in others]
    return racing


def settle(image: np.ndarray, racing: dict, read_back: np.ndarray,
           block_bytes: int) -> int:
    """Where a racing block reads back as one of its other admissible
    writes, the device applied that one last: take it into `image`, so
    that what is stored is held to what was read. Returns how many blocks
    were so settled."""
    settled = 0
    for (obj, off), others in racing.items():
        got = read_back[obj, off:off + block_bytes]
        if any(np.array_equal(got, other) for other in others) \
                and not np.array_equal(got, image[obj, off:off + block_bytes]):
            image[obj, off:off + block_bytes] = got
            settled += 1
    return settled


def blocks_differing(image: np.ndarray, other: np.ndarray,
                     block_bytes: int) -> int:
    """How many blocks of `other` are not `image`'s."""
    if image.shape != other.shape:
        return image.size // block_bytes
    return int((image.reshape(-1, block_bytes)
                != other.reshape(-1, block_bytes)).any(axis=1).sum())
