"""LRC — layered locally-repairable erasure code.

Rebuild of the reference's lrc plugin (ref: src/erasure-code/lrc/
ErasureCodeLrc.{h,cc} + ErasureCodePluginLrc.cc): a stack of sub-codes
("layers") over one set of global chunk positions, so a single lost
chunk is repaired from its small local group instead of k chunks — the
repair-I/O-proportional-to-l property that is the whole point of LRC.

Profile forms (both reference-compatible):

  * low-level:  mapping="__DD__DD"
                layers='[[ "_cDD_cDD", "" ], [ "cDDD____", "" ],
                         [ "____cDDD", "" ]]'
    Each position is one chunk. In `mapping`, 'D' marks the k data
    positions. Each layer is an MDS sub-code over a subset of positions:
    'D' = input to that layer, 'c' = parity written by that layer,
    '_' = not in the layer. Layers encode in order, so a later layer can
    consume an earlier layer's parity as input (the doc example's local
    groups cover the global parities).

  * k/m/l:      k=4 m=2 l=3
    Expanded to mapping/layers exactly like the reference's parse_kml:
    (k+m) must divide by l; chunks sit in (k+m)/l groups of l+1 positions
    (1 local parity + l data/global chunks); the m global parities are
    distributed round-robin across groups, earliest slots first — this
    reproduces the documented expansion of k=4 m=2 l=3.

Chunk ids are mapping POSITIONS (the reference's convention), so data
lives at the 'D' positions, not at ids 0..k-1.

Layer coders default to the RS plugin (plugin=tpu_rs), i.e. the same
batched GF kernels; any registered plugin works via the layer's profile
string, mirroring the reference wrapping jerasure per layer.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Mapping, Sequence

import numpy as np

from .interface import ErasureCode, profile_from_string
from .registry import register


@dataclass
class _Layer:
    d_pos: tuple[int, ...]   # global positions of the layer's data, in order
    c_pos: tuple[int, ...]   # global positions of the layer's parity
    coder: ErasureCode

    @property
    def positions(self) -> frozenset[int]:
        return frozenset(self.d_pos) | frozenset(self.c_pos)

    @property
    def k(self) -> int:
        return len(self.d_pos)

    def local_id(self, pos: int) -> int:
        """Map a global position to this layer's coder chunk id."""
        if pos in self.d_pos:
            return self.d_pos.index(pos)
        return self.k + self.c_pos.index(pos)


def _expand_kml(k: int, m: int, l: int) -> tuple[str, list[list[str]]]:
    """k/m/l -> (mapping, layers), the reference parse_kml expansion."""
    if l < 2:
        raise ValueError(f"lrc l={l}: local groups need at least 2 chunks")
    if (k + m) % l:
        raise ValueError(f"lrc k+m={k + m} must be a multiple of l={l}")
    groups = (k + m) // l
    n = k + m + groups
    # slot layout: each group is [local parity, l data/global slots]
    kind = ["D"] * n  # overwritten below for parity slots
    for g in range(groups):
        kind[g * (l + 1)] = "local"
    free = [i for i in range(n) if kind[i] == "D"]
    # distribute the m global parities round-robin across groups,
    # earliest free slot of each group first
    by_group: list[list[int]] = [[] for _ in range(groups)]
    for pos in free:
        by_group[pos // (l + 1)].append(pos)
    taken: list[int] = []
    for i in range(m):
        g = i % groups
        taken.append(by_group[g].pop(0))
    for pos in taken:
        kind[pos] = "global"
    mapping = "".join("D" if c == "D" else "_" for c in kind)
    global_layer = "".join(
        {"D": "D", "global": "c", "local": "_"}[c] for c in kind)
    layers = [[global_layer, ""]]
    for g in range(groups):
        lo, hi = g * (l + 1), (g + 1) * (l + 1)
        chars = []
        for i in range(n):
            if not lo <= i < hi:
                chars.append("_")
            elif kind[i] == "local":
                chars.append("c")
            else:
                chars.append("D")
        layers.append(["".join(chars), ""])
    return mapping, layers


@register("lrc")
@register("tpu_lrc")
class Lrc(ErasureCode):
    """Layered code; chunk ids are mapping positions."""

    def init(self, profile: Mapping[str, str]) -> None:
        from .registry import factory
        if "mapping" in profile:
            mapping = profile["mapping"]
            raw_layers = profile.get("layers", "[]")
            layer_specs = (json.loads(raw_layers)
                           if isinstance(raw_layers, str) else raw_layers)
            if not layer_specs:
                raise ValueError("lrc: mapping given but no layers")
        else:
            k = int(profile.get("k", 4))
            m = int(profile.get("m", 2))
            l = int(profile.get("l", 3))
            mapping, layer_specs = _expand_kml(k, m, l)
        self.mapping = mapping
        n = len(mapping)
        self.k = mapping.count("D")
        self.m = n - self.k
        if self.k == 0:
            raise ValueError("lrc mapping has no data positions")
        self.data_positions = tuple(i for i, c in enumerate(mapping)
                                    if c == "D")
        self.layers: list[_Layer] = []
        covered: set[int] = set(self.data_positions)
        written: set[int] = set(self.data_positions)
        for spec in layer_specs:
            if len(spec) != 2:
                raise ValueError(f"lrc layer spec must be "
                                 f"[mapping, profile], got {spec!r}")
            lmap, lprof_s = spec
            if len(lmap) != n:
                raise ValueError(f"lrc layer mapping {lmap!r} length "
                                 f"{len(lmap)} != {n}")
            d_pos = tuple(i for i, c in enumerate(lmap) if c == "D")
            c_pos = tuple(i for i, c in enumerate(lmap) if c == "c")
            bad = [c for c in lmap if c not in "Dc_"]
            if bad:
                raise ValueError(f"lrc layer mapping char {bad[0]!r} "
                                 f"not in 'Dc_'")
            if not d_pos or not c_pos:
                raise ValueError(f"lrc layer {lmap!r} needs >=1 'D' and 'c'")
            unwritten = [p for p in d_pos if p not in written]
            if unwritten:
                # a layer may only consume data positions or parities an
                # EARLIER layer wrote; otherwise it encodes over
                # still-zero buffers and decode silently diverges
                raise ValueError(
                    f"lrc layer {lmap!r} reads positions {unwritten} that "
                    f"no earlier layer writes (layer order matters)")
            lprof = profile_from_string(lprof_s) if isinstance(
                lprof_s, str) and lprof_s else dict(lprof_s or {})
            lprof.setdefault("plugin", "tpu_rs")
            lprof["k"] = str(len(d_pos))
            lprof["m"] = str(len(c_pos))
            self.layers.append(_Layer(d_pos, c_pos, factory(lprof)))
            covered |= set(c_pos)
            written |= set(c_pos)
        if covered != set(range(n)):
            raise ValueError(
                f"lrc: positions {sorted(set(range(n)) - covered)} are "
                f"neither data nor written by any layer")

    # -- geometry overrides (chunk ids are positions) ----------------------

    def get_chunk_count(self) -> int:
        return len(self.mapping)

    def get_chunk_mapping(self) -> list[int]:
        return list(self.data_positions) + [
            i for i, c in enumerate(self.mapping) if c != "D"]

    # -- encode ------------------------------------------------------------

    def encode(self, want_to_encode: Sequence[int],
               data: bytes | np.ndarray) -> dict[int, np.ndarray]:
        # base-class pad/split/encode_chunks flow, then relabel chunk ids
        # from the dense (0..k-1 data, k.. coding) order to positions
        n = self.get_chunk_count()
        bad = [i for i in want_to_encode if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), "
                             f"got {sorted(bad)}")
        dense = super().encode(range(self.get_chunk_count()), data)
        coding_positions = [i for i in range(self.get_chunk_count())
                            if i not in set(self.data_positions)]
        by_pos = {p: dense[i] for i, p in enumerate(self.data_positions)}
        by_pos.update({p: dense[self.k + j]
                       for j, p in enumerate(coding_positions)})
        return {i: by_pos[i] for i in want_to_encode}

    def encode_chunks(self, data: np.ndarray) -> np.ndarray:
        """(B, k, L) data -> (B, m, L) parity, parity ordered by ascending
        position (the non-D positions)."""
        b, k, cs = data.shape
        n = self.get_chunk_count()
        full = np.zeros((b, n, cs), dtype=np.uint8)
        full[:, list(self.data_positions), :] = data
        for layer in self.layers:
            parity = np.asarray(layer.coder.encode_chunks(
                full[:, list(layer.d_pos), :]))
            full[:, list(layer.c_pos), :] = parity
        coding_positions = [i for i in range(n) if i not in
                            set(self.data_positions)]
        return full[:, coding_positions, :]

    def encode_matrix(self):
        """The layers composed on the host, in layer order: each
        position's generator row over the k data rows, a layer's parity
        rows its matrix times the rows of its inputs — what
        `encode_chunks` computes, as one (m, k) matrix (parity rows by
        ascending position). None where a layer's coder has no matrix
        of its own."""
        if "_encode_matrix" not in self.__dict__:
            from ..gf.numpy_ref import gf_matmul
            gen = {p: np.eye(self.k, dtype=np.uint8)[i]
                   for i, p in enumerate(self.data_positions)}
            composed = None
            for layer in self.layers:
                mat = layer.coder.encode_matrix()
                if mat is None:
                    break
                rows = gf_matmul(np.asarray(mat, np.uint8),
                                 np.stack([gen[p] for p in layer.d_pos]))
                gen.update(zip(layer.c_pos, rows))
            else:
                composed = np.stack(
                    [gen[p] for p in range(self.get_chunk_count())
                     if p not in set(self.data_positions)])
            self._encode_matrix = composed
        return self._encode_matrix

    # -- repair planning ---------------------------------------------------

    def _repair_plan(self, want: set[int], avail: set[int],
                     costs: Mapping[int, int] | None = None):
        """Sequence of (layer, missing_positions) repairs, preferring
        small (local) layers so repair reads stay proportional to l.
        `costs` biases which k chunks each repair reads (ref:
        minimum_to_decode_with_cost). Returns (plan, reads, known) or
        raises if unreconstructible."""
        known = set(avail)
        plan: list[tuple[_Layer, list[int]]] = []
        reads: set[int] = set()
        cost = (lambda p: costs.get(p, 0)) if costs else (lambda p: 0)
        order = sorted(self.layers, key=lambda la: la.k)
        while want - known:
            progressed = False
            for layer in order:
                missing = [p for p in layer.positions if p not in known]
                if not missing:
                    continue
                have = [p for p in layer.positions if p in known]
                if len(have) < layer.k:
                    continue
                plan.append((layer, missing))
                # the layer reads k of its known chunks; prefer ones some
                # earlier repair already reads, then cheapest, then lowest
                use = sorted(have, key=lambda p: (p not in reads,
                                                  cost(p), p))[:layer.k]
                reads |= {p for p in use if p in avail}
                known |= set(missing)
                progressed = True
                break
            if not progressed:
                raise ValueError(
                    f"lrc: cannot reconstruct {sorted(want - known)} "
                    f"from {sorted(avail)}")
        return plan, reads, known

    def minimum_to_decode(self, want_to_read: Sequence[int],
                          available: Sequence[int]) -> set[int]:
        n = self.get_chunk_count()
        want = set(want_to_read)
        avail = set(available)
        bad = [i for i in want | avail if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), "
                             f"got {sorted(bad)}")
        direct = want & avail
        if want <= avail:
            return direct
        _, reads, _ = self._repair_plan(want - avail, avail)
        return direct | reads

    def minimum_to_decode_with_cost(self, want_to_read: Sequence[int],
                                    available: Mapping[int, int]) -> set[int]:
        """Layer-aware: the MDS default's 'k cheapest chunks' can be an
        undecodable set for a layered code, so plan repairs structurally
        and use cost only to break ties among a layer's inputs."""
        n = self.get_chunk_count()
        want = set(want_to_read)
        avail = set(available)
        bad = [i for i in want | avail if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), "
                             f"got {sorted(bad)}")
        direct = want & avail
        if want <= avail:
            return direct
        _, reads, _ = self._repair_plan(want - avail, avail, costs=available)
        return direct | reads

    # -- decode ------------------------------------------------------------

    def decode_chunks(self, want_to_read: Sequence[int],
                      chunks: Mapping[int, np.ndarray]) -> dict[int, np.ndarray]:
        want = set(want_to_read)
        known: dict[int, np.ndarray] = {p: np.asarray(v, np.uint8)
                                        for p, v in chunks.items()}
        plan, _, _ = self._repair_plan(want - set(known), set(known))
        for layer, missing in plan:
            local_have = {layer.local_id(p): known[p]
                          for p in layer.positions if p in known}
            rec = layer.coder.decode(
                [layer.local_id(p) for p in missing], local_have)
            for p in missing:
                known[p] = rec[layer.local_id(p)]
        return {p: known[p] for p in want}

    # -- device fast path --------------------------------------------------

    def repair_matrix(self, erasures: tuple[int, ...],
                      survivors: tuple[int, ...]):
        """A plan that stays inside one layer: that layer's decode rows
        over the k of its members the plan reads, 0 for every other
        survivor — no probe, and a key equal across coder instances.
        None for a laddered plan (the probed default), or a layer coder
        without a matrix."""
        from ..gf.numpy_ref import decode_matrix
        try:
            plan, reads, _ = self._repair_plan(set(erasures),
                                               set(survivors))
        except ValueError:
            return None
        if len(plan) != 1:
            return None
        layer = plan[0][0]
        mat = layer.coder.encode_matrix()
        if mat is None:
            return None
        use = sorted(reads)
        rows = decode_matrix(np.asarray(mat, np.uint8),
                             [layer.local_id(p) for p in erasures],
                             layer.k, [layer.local_id(p) for p in use])
        R = np.zeros((len(erasures), len(survivors)), np.uint8)
        for j, p in enumerate(use):
            R[:, survivors.index(p)] = rows[:, j]
        return R

    def decode_concat(self, chunks: Mapping[int, np.ndarray],
                      object_size: int | None = None) -> np.ndarray:
        rec = self.decode(list(self.data_positions), chunks)
        out = np.concatenate([rec[p] for p in self.data_positions], axis=-1)
        if object_size is not None:
            out = out[..., :object_size]
        return out
