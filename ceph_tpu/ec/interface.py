"""The erasure-code contract, batched-array edition.

Semantic mirror of the reference's plugin contract
(ref: src/erasure-code/ErasureCodeInterface.h — init, chunk geometry,
minimum_to_decode, encode/decode over shard-keyed buffers; and
src/erasure-code/ErasureCode.{h,cc} for the default padding/split/concat
behaviors), re-shaped for a TPU framework: the unit of work is a BATCH of
objects, chunks are uint8 arrays of shape (batch, L), and the hot paths
lower to the static-matrix kernels in ceph_tpu.ops.rs_kernels.

A profile is a {str: str} dict exactly like ErasureCodeProfile, so
reference profile strings (k=8 m=3 plugin=tpu technique=reed_sol_van)
round-trip unchanged.
"""

from __future__ import annotations

import abc
from typing import Mapping, Sequence

import numpy as np

# TPU lane width; also satisfies every CPU SIMD alignment the reference
# cares about (jerasure wants chunks aligned to w*packetsize; BlueStore
# to csum blocks). All chunk sizes are multiples of this.
CHUNK_ALIGNMENT = 128

ErasureCodeProfile = dict  # {str: str}


class ErasureCode(abc.ABC):
    """Base class: geometry + padding/split/concat defaults.

    Subclasses set self.k, self.m after init() and implement the chunk
    codecs. All byte-level layout rules (padding to stripe width, chunk
    order) live here so every codec shares one bit-exact object<->chunk
    mapping (ref: ErasureCode::encode prep + ECUtil stripe math).
    """

    k: int
    m: int

    # True when encode/decode act independently on every byte position
    # of a chunk (all matrix codes). Vector codes that couple bytes
    # across a chunk's sub-chunk axis (clay) set this False; callers
    # like the RMW write path then fall back to whole-object windows.
    positionwise: bool = True

    # True for the codecs that keep a numpy oracle behind the profile
    # key impl=ref (clay, shec): tests compare the device path with it.
    has_ref_oracle: bool = False

    def __init__(self, profile: Mapping[str, str] | None = None):
        self.profile: ErasureCodeProfile = dict(profile or {})
        impl = self.profile.get("impl")
        if impl is not None and not (impl == "ref" and self.has_ref_oracle):
            # profiles arrive from outside the program
            raise ValueError(
                f"impl={impl!r}: the GF(2^8) lowering is no longer "
                f"selectable (ops/rs_kernels picks it from the matrix); "
                f"the key's one value left is impl=ref, the numpy oracle "
                f"of the clay and shec plugins")
        self.ref_oracle = impl == "ref"
        if profile is not None:
            self.init(self.profile)

    # -- lifecycle ---------------------------------------------------------

    @abc.abstractmethod
    def init(self, profile: Mapping[str, str]) -> None:
        """Parse/validate the profile; set k, m; build matrices."""

    # -- geometry ----------------------------------------------------------

    def get_chunk_count(self) -> int:
        return self.k + self.m

    def get_data_chunk_count(self) -> int:
        return self.k

    def get_coding_chunk_count(self) -> int:
        return self.m

    def get_chunk_mapping(self) -> list[int]:
        """Shard-id permutation; identity unless a subclass remaps."""
        return list(range(self.get_chunk_count()))

    def get_chunk_size(self, stripe_width: int) -> int:
        """Bytes per chunk for an object of `stripe_width` logical bytes,
        padded so chunk_size is CHUNK_ALIGNMENT-aligned."""
        align = self.k * CHUNK_ALIGNMENT
        padded = -(-stripe_width // align) * align
        return padded // self.k

    # -- device fast path --------------------------------------------------

    def batch_decoder(self, erasures: Sequence[int],
                      survivors: Sequence[int]):
        """Optional device fast path: a jitted fn mapping a survivor
        stack (B, H, L) uint8 (rows in `survivors` order, H =
        len(survivors)) to the rebuilt chunks (B, len(erasures), L) in
        `erasures` order, suitable for fusing into larger jitted
        pipelines (recovery CRC+decode+CRC in one launch). How many
        rows are consumed is codec-specific (RS: the first k; LRC: all
        — the local plan may need fewer than k rows total; Clay: all d
        helpers, repair planes selected on device). Returns None when
        the codec has no static-matrix form for this pattern; callers
        must then use decode_chunks."""
        if not getattr(self, "positionwise", True):
            return None          # byte positions couple (clay
        #                          overrides with its sub-chunk plan)
        if self.ref_oracle:
            return None          # numpy oracle: no device path
        erasures = tuple(int(e) for e in erasures)
        survivors = tuple(int(s) for s in survivors)
        cache = self.__dict__.setdefault("_bd_cache", {})
        fn = cache.get((erasures, survivors))
        if fn is None:
            from ..ops.rs_kernels import make_encoder
            from .linearize import derive_repair_matrix
            R = self.repair_matrix(erasures, survivors)
            for seed in range(3 if R is None else 0):
                try:    # a random probe matrix is singular ~0.4% of
                    #     the time even when the helpers suffice
                    R = derive_repair_matrix(
                        self, erasures, survivors, seed=seed)
                    break
                except ValueError:
                    continue
            fn = make_encoder(R) if R is not None else False
            cache[(erasures, survivors)] = fn
            if R is not None:
                self.__dict__.setdefault("_bd_keys", {})[
                    (erasures, survivors)] = (
                        "lin", R.tobytes(), R.shape)
        return fn or None

    def repair_matrix(self, erasures: tuple[int, ...],
                      survivors: tuple[int, ...]):
        """Optional static repair: the (len(erasures), len(survivors))
        GF(2^8) matrix that rebuilds `erasures` from the rows of
        `survivors` in that order, known on the host, as
        `encode_matrix` serves the write. None by default:
        `batch_decoder` then derives one by probing `encode_chunks`."""
        return None

    # -- parity-delta fast path (partial-stripe RMW) -----------------------

    def delta_matrix(self, touched: Sequence[int]):
        """(m, len(touched)) GF matrix D with parity_delta =
        D (GF@) data_delta byte-wise, or None when the codec has no
        static scalar form (vector codes, bitmatrix techniques) —
        callers then use parity_delta's generic XOR-linear path.
        `touched` names DENSE data rows (encode_chunks order).
        Cached per instance; derivation is probe-verified."""
        if not getattr(self, "positionwise", True):
            return None
        touched = tuple(int(t) for t in touched)
        cache = self.__dict__.setdefault("_dm_cache", {})
        if touched not in cache:
            from .linearize import derive_delta_matrix
            try:
                cache[touched] = derive_delta_matrix(self, touched)
            except ValueError:
                cache[touched] = None
        return cache[touched]

    def delta_program_key(self, touched: Sequence[int]):
        """Hashable identity of the fused delta-encode program, EQUAL
        across coder instances with the same geometry — the
        process-wide RMW program cache key (same sharing contract as
        decode_program_key: identical HLO compiles ONCE per process,
        not once per PG per daemon). None when there is no static
        form (callers cache the generic path per coder instance)."""
        touched = tuple(int(t) for t in touched)
        D = self.delta_matrix(touched)
        if D is None:
            return None
        return ("delta", D.tobytes(), D.shape)

    def parity_delta(self, touched: Sequence[int],
                     deltas: np.ndarray) -> np.ndarray:
        """(B, len(touched), L) data-shard deltas (new ^ old, DENSE
        row order per `touched`) -> (B, m, L) parity deltas: XOR each
        into its parity shard and the stripe re-encodes to the new
        bytes. Correct for EVERY additive (XOR-linear) code — all GF
        codes here, Clay included (whose sub-chunk coupling only
        requires L to be the FULL chunk length; positionwise callers
        may pass any sub-window). Uses the static delta matrix when
        one exists, else encodes the zero-padded delta through
        encode_chunks (linearity: encode(new^old) = parity(new) ^
        parity(old))."""
        deltas = np.asarray(deltas, np.uint8)
        touched = tuple(int(t) for t in touched)
        if deltas.ndim != 3 or deltas.shape[1] != len(touched):
            raise ValueError(
                f"deltas must be (B, {len(touched)}, L), "
                f"got {deltas.shape}")
        D = self.delta_matrix(touched)
        if D is not None:
            from ..gf.numpy_ref import gf_matmul
            B, t, L = deltas.shape
            out = np.empty((B, self.m, L), np.uint8)
            for bi in range(B):
                out[bi] = gf_matmul(D, deltas[bi])
            return out
        B, t, L = deltas.shape
        full = np.zeros((B, self.k, L), np.uint8)
        for ti, tr in enumerate(touched):
            full[:, tr, :] = deltas[:, ti, :]
        return np.asarray(self.encode_chunks(full))

    def encode_matrix(self):
        """Optional static encode over whole rows: the (m, k) GF(2^8)
        matrix whose product with the k data rows is the m parity rows,
        both in DENSE order (`encode_chunks`' order), byte-wise. The
        served write fuses it with the rows' crcs into one launch. None
        for a code without one: RS has its coding matrix, LRC its
        layers composed; a vector code has `vector_encode_matrix`."""
        return None

    def vector_encode_matrix(self):
        """Optional static encode of a vector code: (D, P), where P is
        the sub-chunk count and D the (m*P, k*P) GF(2^8) matrix whose
        product with the k data rows viewed as (k*P, L/P) sub-chunks is
        the m parity rows viewed the same way. The served write fuses
        it with the rows' crcs into one launch. None for a code without
        one (a matrix code has `encode_matrix`)."""
        return None

    def range_batch_decoder(self, erasures: Sequence[int],
                            survivors: Sequence[int]):
        """Optional sub-chunk fast path: a jitted fn mapping the
        helpers' PLANNED BYTE RANGES — stacked (B, H, rl) uint8 where
        rl = row_bytes(shard_len) of the repair plan — to the rebuilt
        full chunks (B, len(erasures), shard_len). Only codecs whose
        repair touches a strict sub-range of each helper (Clay/MSR)
        provide one; None means the planner ships full rows and
        batch_decoder applies."""
        return None

    def range_decode_program_key(self, erasures: Sequence[int],
                                 survivors: Sequence[int]):
        """Process-wide program identity for range_batch_decoder
        (same sharing contract as decode_program_key)."""
        return None

    def decode_program_key(self, erasures: Sequence[int],
                           survivors: Sequence[int]):
        """Hashable identity of batch_decoder's compiled program, EQUAL
        across coder instances with the same geometry — the process-wide
        recovery program cache key (a per-backend cache recompiles the
        identical HLO once per PG per daemon; the write path learned
        this in round 8). None when there is no static form (callers
        fall back to caching per coder instance)."""
        erasures = tuple(int(e) for e in erasures)
        survivors = tuple(int(s) for s in survivors)
        if self.batch_decoder(erasures, survivors) is None:
            return None
        return self.__dict__.get("_bd_keys", {}).get(
            (erasures, survivors))

    # -- availability ------------------------------------------------------

    def minimum_to_decode(self, want_to_read: Sequence[int],
                          available: Sequence[int]) -> set[int]:
        """Smallest chunk set from `available` able to produce `want_to_read`.

        MDS default: any k available chunks (prefer wanted ones, then data
        chunks — they're free to 'decode'). Locally-repairable codecs
        override (LRC: the local group; Clay: sub-chunk ranges).
        """
        avail = set(available)
        want = set(want_to_read)
        n = self.get_chunk_count()
        bad = [i for i in want | avail if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), got {sorted(bad)}")
        if want - avail:
            need = want & avail
            rest = sorted(avail - want)
            need.update(rest[:max(0, self.k - len(need))])
            if len(need) < self.k:
                raise ValueError(
                    f"cannot decode {sorted(want)} from {sorted(avail)}: "
                    f"only {len(avail)} chunks available, need {self.k}")
            return need
        return want

    def minimum_to_decode_with_cost(self, want_to_read: Sequence[int],
                                    available: Mapping[int, int]) -> set[int]:
        """Like minimum_to_decode but with per-chunk read costs; default
        picks the k cheapest (ref: ErasureCodeInterface minimum_to_decode_with_cost)."""
        want = set(want_to_read)
        avail = set(available)
        n = self.get_chunk_count()
        bad = [i for i in want | avail if not 0 <= i < n]
        if bad:
            raise ValueError(f"chunk ids must be in [0, {n}), got {sorted(bad)}")
        if want - avail:
            ordered = sorted(avail, key=lambda c: (available[c], c))
            need = set(ordered[:self.k])
            if len(need) < self.k:
                raise ValueError("not enough chunks")
            return need
        return want

    # -- byte-level encode/decode -----------------------------------------

    def encode(self, want_to_encode: Sequence[int],
               data: bytes | np.ndarray) -> dict[int, np.ndarray]:
        """Full-object encode: pad to stripe width, split into k data
        chunks, compute parity, return the requested chunk ids.

        data: bytes or (object_bytes,) uint8, or (batch, object_bytes).
        Returns {chunk_id: (batch, chunk_size) uint8} (batch dim kept).
        """
        n_chunks = self.get_chunk_count()
        bad = [i for i in want_to_encode if not 0 <= i < n_chunks]
        if bad:
            raise ValueError(
                f"chunk ids must be in [0, {n_chunks}), got {sorted(bad)}")
        arr = np.frombuffer(data, dtype=np.uint8) if isinstance(
            data, (bytes, bytearray, memoryview)) else np.asarray(data, np.uint8)
        squeeze = arr.ndim == 1
        if squeeze:
            arr = arr[None, :]
        b, n = arr.shape
        cs = self.get_chunk_size(n)
        padded = np.zeros((b, self.k * cs), dtype=np.uint8)
        padded[:, :n] = arr
        chunks = padded.reshape(b, self.k, cs)
        coded = self.encode_chunks(chunks)  # (b, m, cs)
        full = {i: chunks[:, i, :] for i in range(self.k)}
        full.update({self.k + i: np.asarray(coded)[:, i, :] for i in range(self.m)})
        out = {i: full[i] for i in want_to_encode}
        if squeeze:
            out = {i: v[0] for i, v in out.items()}
        return out

    @abc.abstractmethod
    def encode_chunks(self, data: np.ndarray) -> np.ndarray:
        """(batch, k, L) data chunks -> (batch, m, L) coding chunks."""

    def decode(self, want_to_read: Sequence[int],
               chunks: Mapping[int, np.ndarray]) -> dict[int, np.ndarray]:
        """Reconstruct `want_to_read` chunk ids from available `chunks`.

        Systematic default (ref: ErasureCode::_decode): wanted chunks that
        are already available pass through; the rest go to decode_chunks.
        """
        out: dict[int, np.ndarray] = {}
        missing = []
        for i in want_to_read:
            if i in chunks:
                out[i] = np.asarray(chunks[i])
            else:
                missing.append(i)
        if missing:
            out.update(self.decode_chunks(missing, chunks))
        return out

    @abc.abstractmethod
    def decode_chunks(self, want_to_read: Sequence[int],
                      chunks: Mapping[int, np.ndarray]) -> dict[int, np.ndarray]:
        """Reconstruct the (erased) `want_to_read` ids from `chunks`."""

    def decode_concat(self, chunks: Mapping[int, np.ndarray],
                      object_size: int | None = None) -> np.ndarray:
        """Recover and concatenate the data chunks (ref:
        ErasureCodeInterface::decode_concat), trimming padding if
        object_size is given."""
        rec = self.decode(list(range(self.k)), chunks)
        parts = [rec[i] for i in range(self.k)]
        out = np.concatenate(parts, axis=-1)
        if object_size is not None:
            out = out[..., :object_size]
        return out


def profile_from_string(s: str) -> ErasureCodeProfile:
    """Parse 'k=8 m=3 plugin=tpu technique=reed_sol_van' profile strings."""
    out: ErasureCodeProfile = {}
    for tok in s.split():
        if "=" not in tok:
            raise ValueError(f"bad profile token {tok!r}")
        key, val = tok.split("=", 1)
        out[key] = val
    return out
