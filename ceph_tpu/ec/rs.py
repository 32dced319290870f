"""Reed-Solomon coder — the jerasure/isa plugin equivalent.

Covers the reference's `jerasure` plugin techniques reed_sol_van /
cauchy_orig / cauchy_good (ref: src/erasure-code/jerasure/
ErasureCodeJerasure.cc) and, by the same contract, the `isa` plugin
(ref: src/erasure-code/isa/ErasureCodeIsa.cc — same math, different CPU
backend; here there is only one backend: the TPU kernels).

Encode: parity = C (GF@) data on device with a static matrix.
Decode: invert the surviving k x k submatrix on host (tiny, like
jerasure_matrix_decode does) and run the same static-matrix device kernel
with the decode matrix; decode matrices are cached per erasure pattern.

Two faces of one matrix. `encode_chunks` and `decode_chunks` take host
rows and return host rows: they run the HOST FACE
(`rs_kernels.make_host_encoder`), where the bytes cross the link as
uint32 words both ways and the result's copy to the host is a plain
copy (as uint8 `(B, m, L)` the parity left the device tiled with the
batch in m's place, and de-tiling it on the host was 84 of a 108 ms
call at 32 x 4 MiB: PERF.md, PR 30). A large call (two or more
sub-batches of 8 MiB of rows: `rs_kernels._sub_batch_rows`) crosses the
link as a pipeline of sub-batches of the batch axis, so the device
works and the next rows are laid out while the rows before them are
still being copied in; one object is one launch (PERF.md, PR 35).
`batch_decoder` hands out the DEVICE-RESIDENT program (`make_encoder`)
that the served path composes with its crc programs; its shape, dtype
and HLO are what they were.
"""

from __future__ import annotations

from typing import Mapping, Sequence

import numpy as np

from ..gf.numpy_ref import decode_matrix
from ..ops.rs_kernels import make_encoder, make_host_encoder
from .interface import ErasureCode
from .matrices import coding_matrix
from .registry import register


class ReedSolomon(ErasureCode):
    """MDS Reed-Solomon over GF(2^8), batched on TPU."""

    def init(self, profile: Mapping[str, str]) -> None:
        self.k = int(profile.get("k", 7))
        self.m = int(profile.get("m", 3))
        technique = profile.get("technique", "reed_sol_van")
        self.technique = technique
        if self.k < 1 or self.m < 1 or self.k + self.m > 256:
            raise ValueError(f"bad geometry k={self.k} m={self.m} (w=8)")
        self.matrix = coding_matrix(technique, self.k, self.m)
        self._encode_host = make_host_encoder(self.matrix)
        self._decode_cache: dict[tuple[tuple[int, ...], tuple[int, ...]], tuple] = {}

    def encode_chunks(self, data: np.ndarray) -> np.ndarray:
        return self._encode_host(data)

    def encode_matrix(self):
        return self.matrix

    def delta_matrix(self, touched):
        # exact: the parity-delta matrix IS the coding matrix's
        # touched columns (no probe needed; bit-parity with the probe
        # path is pinned by tests/test_rmw_delta.py)
        touched = tuple(int(t) for t in touched)
        if any(not 0 <= t < self.k for t in touched):
            raise ValueError(f"touched rows must be in [0, {self.k})")
        return np.ascontiguousarray(self.matrix[:, list(touched)])

    def _decoder_for(self, erasures: tuple[int, ...], survivors: tuple[int, ...]):
        key = (erasures, survivors)
        hit = self._decode_cache.get(key)
        if hit is None:
            D = decode_matrix(self.matrix, list(erasures), self.k, list(survivors))
            # the device-resident program and the host face
            hit = (make_encoder(D), make_host_encoder(D))
            self._decode_cache[key] = hit
        return hit

    def batch_decoder(self, erasures: Sequence[int],
                      survivors: Sequence[int]):
        # orders are honored as given (the interface contract: stack
        # rows arrive in `survivors` order, outputs in `erasures`
        # order); only the first k survivors are consumed
        erasures = tuple(erasures)
        survivors = tuple(survivors)[:self.k]
        if len(survivors) < self.k:
            return None
        return self._decoder_for(erasures, survivors)[0]

    def decode_program_key(self, erasures: Sequence[int],
                           survivors: Sequence[int]):
        # the compiled program is a pure function of (coding matrix,
        # erasure/survivor pattern) — every PG backend with the
        # same profile shares one program per pattern
        erasures = tuple(int(e) for e in erasures)
        survivors = tuple(int(s) for s in survivors)[:self.k]
        if len(survivors) < self.k:
            return None
        return ("rs", self.matrix.tobytes(), erasures, survivors)

    def decode_chunks(self, want_to_read: Sequence[int],
                      chunks: Mapping[int, np.ndarray]) -> dict[int, np.ndarray]:
        erasures = tuple(sorted(want_to_read))
        survivors = tuple(sorted(i for i in chunks if i not in set(erasures))[:self.k])
        if len(survivors) < self.k:
            raise ValueError(
                f"need {self.k} chunks to decode, have {len(survivors)}")
        _, host_fn = self._decoder_for(erasures, survivors)
        stack = np.stack([np.asarray(chunks[s], np.uint8) for s in survivors],
                         axis=-2)
        squeeze = stack.ndim == 2
        if squeeze:
            stack = stack[None]
        rec = host_fn(stack)  # (B, E, L)
        if squeeze:
            rec = rec[0]
        return {e: rec[..., i, :] for i, e in enumerate(erasures)}


@register("tpu_rs")
@register("jerasure")  # accept reference profile strings unchanged
def _jerasure_factory(profile: Mapping[str, str]) -> ErasureCode:
    """The jerasure plugin face: matrix techniques go to ReedSolomon,
    bitmatrix/schedule techniques (liberation, blaum_roth, liber8tion)
    to the XOR-schedule coder (ref: ErasureCodeJerasure.cc technique
    dispatch in ErasureCodePluginJerasure::factory)."""
    from .bitmatrix import BITMATRIX_TECHNIQUES, JerasureBitmatrix
    technique = dict(profile).get("technique", "reed_sol_van")
    if technique in BITMATRIX_TECHNIQUES:
        return JerasureBitmatrix(profile)
    return ReedSolomon(profile)


@register("isa")
class IsaReedSolomon(ReedSolomon):
    """The isa plugin's coder (ref: src/erasure-code/isa/ErasureCodeIsa.cc
    ErasureCodeIsaDefault, techniques reed_sol_van / cauchy).

    Distinct from the jerasure plugin: ISA-L's reed_sol_van builds its
    matrix as gf_gen_rs_matrix does (row r = powers of 2^r), which is a
    DIFFERENT byte format from jerasure's column-reduced Vandermonde.
    That construction is not MDS for every geometry, so init() verifies
    decodability for small codes and rejects known-degenerate setups.
    """

    # exhaustive MDS verification is C(k+m, m) tiny matrix inversions;
    # above this budget reed_sol_van is refused rather than trusted.
    _MDS_CHECK_BUDGET = 200_000

    def init(self, profile: Mapping[str, str]) -> None:
        prof = dict(profile)
        technique = prof.get("technique", "reed_sol_van")
        if technique == "reed_sol_van":
            prof["technique"] = "isa_reed_sol_van"
        elif technique == "cauchy":
            prof["technique"] = "isa_cauchy"
        else:
            raise ValueError(f"isa plugin technique must be reed_sol_van or "
                             f"cauchy, got {technique!r}")
        super().init(prof)
        self.technique = technique
        if technique == "reed_sol_van":
            # ISA-L's gf_gen_rs_matrix construction is NOT MDS for every
            # geometry; accepting one would advertise fault tolerance that
            # fails at decode time. Verify exhaustively, or refuse when
            # the pattern space is too large to verify.
            from math import comb

            from .matrices import is_mds
            if comb(self.k + self.m, self.m) > self._MDS_CHECK_BUDGET:
                raise ValueError(
                    f"isa reed_sol_van k={self.k} m={self.m}: MDS property "
                    f"cannot be verified exhaustively at this size and the "
                    f"construction is not guaranteed MDS; use "
                    f"technique=cauchy (always MDS)")
            if not is_mds(self.matrix, self.k):
                raise ValueError(
                    f"isa reed_sol_van matrix is not MDS for k={self.k} "
                    f"m={self.m}; use technique=cauchy")
