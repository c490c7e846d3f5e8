"""Reed-Solomon / Cauchy codecs on the TPU bit-plane kernels.

One codec class covers the matrix techniques of the reference's `jerasure` and
`isa` plugins (ErasureCodeJerasure.cc, ErasureCodeIsa.cc): the technique picks
the coding-matrix family (ceph_tpu.ec.matrices), encode/decode are batched
GF(2^8) matmuls on the MXU (ceph_tpu.ops.gf_bitplane), and decode matrices are
memoized per erasure signature — the TPU analogue of the reference's LRU
decoding-table cache (ErasureCodeIsaTableCache.cc:234-296).

Parameter envelopes mirror the reference:
  * w=8 only (the GF(2^8) field; jerasure also offers w=16/32, which change the
    chunk layout only for non-default techniques — out of scope, rejected);
  * isa vandermonde MDS guard k<=32, m<=4, (m==4 -> k<=21) (ErasureCodeIsa.cc:325-364);
  * jerasure defaults k=7, m=3 (ErasureCodeJerasure.h:89-91).
"""

from __future__ import annotations

import errno
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec import matrices
from ceph_tpu.ec.interface import (
    SIMD_ALIGN,
    DecodeTableCache,
    ErasureCode,
    ErasureCodeError,
    PlanePlan,
    align_up,
    chunk_size_isa_style,
    chunk_size_jerasure_style,
    profile_to_bool,
    profile_to_int,
    profile_to_string,
)
from ceph_tpu.ops import gf_bitplane as bp
from ceph_tpu.ops import gf_pallas as gp
from ceph_tpu.ops.gf import gf_region_matmul, matrix_to_bitmatrix

LARGEST_VECTOR_WORDSIZE = 16  # reference: ErasureCodeJerasure.cc:30


class ErasureCodeRs(ErasureCode):
    """Matrix-technique RS codec; family selects reference-compatible behavior.

    family: "tpu" | "jerasure" | "isa" — controls technique-name namespace,
    defaults, chunk-size rule, and parameter envelope.
    """

    #: reference technique name -> matrix builder key
    TECHNIQUES = {
        "jerasure": {
            "reed_sol_van": "reed_sol_van",
            "reed_sol_r6_op": "reed_sol_r6_op",
            "cauchy_orig": "cauchy_orig",
            "cauchy_good": "cauchy_good",
        },
        "isa": {
            "reed_sol_van": "isa_vandermonde",
            "cauchy": "isa_cauchy",
        },
        # the native namespace exposes every family directly
        "tpu": {name: name for name in matrices.TECHNIQUES},
    }

    #: every technique here reduces to parity = gen @ data applied
    #: byte-column-wise over GF(2^8), so sub-stripe (column window)
    #: re-encoding is exact — the OSD's partial-overwrite fast path
    column_independent = True

    def __init__(self, family: str = "tpu"):
        super().__init__()
        if family not in self.TECHNIQUES:
            raise ErasureCodeError(errno.EINVAL, f"unknown family {family!r}")
        self.family = family
        self.technique = ""
        self.w = 8
        self.per_chunk_alignment = False
        self._gen: np.ndarray | None = None
        self._encode_bits: jnp.ndarray | None = None
        self._encode_packed: jnp.ndarray | None = None
        #: decode tables and plane plans, per erasure signature
        self._decode_cache = DecodeTableCache()
        #: plane plans built (the EncodeService counts cache hits by it)
        self.plans_built = 0

    # -- profile ------------------------------------------------------------

    def parse(self, profile) -> None:
        default_technique = "reed_sol_van" if self.family != "tpu" else "isa_cauchy"
        self.k = profile_to_int(profile, "k", 7)
        self.m = profile_to_int(profile, "m", 3)
        self.w = profile_to_int(profile, "w", 8)
        self.technique = profile_to_string(profile, "technique", default_technique)
        self.per_chunk_alignment = profile_to_bool(
            profile, "jerasure-per-chunk-alignment", False
        )
        # packetsize only exists for jerasure's bitmatrix (cauchy) techniques
        self.packetsize = (
            profile_to_int(profile, "packetsize", 2048)
            if self.family == "jerasure"
            else 1
        )
        techniques = self.TECHNIQUES[self.family]
        if self.technique not in techniques:
            raise ErasureCodeError(
                errno.EINVAL,
                f"technique={self.technique} is not a valid {self.family} "
                f"technique (know {sorted(techniques)})",
            )
        matrix_key = techniques[self.technique]
        if matrix_key == "reed_sol_r6_op":
            # RAID6 is m=2 by construction; the reference coerces m rather
            # than rejecting (ErasureCodeJerasure.cc:238-252 erases profile m),
            # so coerce BEFORE the geometry checks below run
            self.m = 2
            profile["m"] = "2"
        self.sanity_check_k_m()
        if self.w != 8:
            raise ErasureCodeError(
                errno.EINVAL, f"w={self.w} not supported (GF(2^8) only)"
            )
        if self.k + self.m > 256:
            raise ErasureCodeError(errno.EINVAL, "k+m must be <= 256 for w=8")
        if matrix_key == "isa_vandermonde":
            # MDS safety envelope, ErasureCodeIsa.cc:325-364
            if self.k > 32 or self.m > 4 or (self.m == 4 and self.k > 21):
                raise ErasureCodeError(
                    errno.EINVAL,
                    "isa reed_sol_van is only MDS for k<=32, m<=4 "
                    "(k<=21 when m=4)",
                )
        self._matrix_key = matrix_key
        self._parse_mapping(profile)

    def prepare(self) -> None:
        parity = matrices.build_parity_matrix(self._matrix_key, self.k, self.m)
        if self.family == "isa" and self.m == 1:
            # the reference's isa plugin short-circuits m==1 to region XOR for
            # BOTH matrix types (isa_encode/isa_decode, ErasureCodeIsa.cc:125,
            # 196-203), so the code it actually implements is the all-ones row
            parity = np.ones_like(parity)
        # the XOR fast path is only valid when the parity row really is XOR
        self._xor_ok = self.m == 1 and bool(np.all(parity == 1))
        self._gen = np.concatenate([np.eye(self.k, dtype=np.uint8), parity])
        bits = matrix_to_bitmatrix(parity)
        self._encode_bits = jnp.asarray(bits, dtype=jnp.int8)
        self._encode_packed = jnp.asarray(gp.pack_matrix(bits))
        self._decode_cache.clear()

    # -- geometry -----------------------------------------------------------

    def get_chunk_size(self, object_size: int) -> int:
        if self.family == "jerasure":
            # bitmatrix (cauchy) techniques fold packetsize into the alignment
            # (ErasureCodeJerasureCauchy::get_alignment, .cc:279-293); the
            # matrix techniques use the plain word alignment (.cc:174-184)
            cauchy = self.technique.startswith("cauchy")
            if self.per_chunk_alignment:
                if cauchy:
                    alignment = align_up(
                        self.w * self.packetsize, LARGEST_VECTOR_WORDSIZE
                    )
                else:
                    alignment = self.w * LARGEST_VECTOR_WORDSIZE
            else:
                packet = self.packetsize if cauchy else 1
                alignment = self.k * self.w * packet * 4
                if (self.w * packet * 4) % LARGEST_VECTOR_WORDSIZE:
                    alignment = self.k * self.w * packet * LARGEST_VECTOR_WORDSIZE
            return chunk_size_jerasure_style(
                self.k, object_size, alignment, self.per_chunk_alignment
            )
        if self.family == "isa":
            return chunk_size_isa_style(self.k, object_size, SIMD_ALIGN)
        # native tpu family: lane-width (128 B) aligned chunks so packed
        # stripes land on TPU tile boundaries
        return chunk_size_isa_style(self.k, object_size, 128)

    # -- compute ------------------------------------------------------------

    def encode_array(self, data) -> np.ndarray:
        data = jnp.asarray(data, dtype=jnp.uint8)
        if self._xor_ok:
            return bp.xor_reduce(data)
        return bp.gf_matmul_bitplane(self._encode_bits, data)

    def decode_bitmatrix(self, present: Sequence[int], targets: Sequence[int]):
        """Memoized decode matrices for an erasure signature: a (bitplane,
        packed) pair — the TPU analogue of the reference's LRU decode-table
        cache (ErasureCodeIsaTableCache.cc:234-296)."""
        def build():
            dm = matrices.decode_matrix(
                self._gen, self.k, list(present), list(targets)
            )
            bits_np = matrix_to_bitmatrix(dm)
            # cache HOST arrays: entries may be created while tracing under
            # jit, where a device array would be a leaked tracer; as numpy
            # constants they fold into the compiled program at each use site
            return (bits_np.astype(np.int8), gp.pack_matrix(bits_np))

        key = (tuple(present[: self.k]), tuple(targets))
        return self._decode_cache.get_or(key, build)

    def decode_array(self, present, targets, survivors) -> np.ndarray:
        if len(present) < self.k:
            raise ErasureCodeError(errno.EIO, "not enough survivors")
        survivors = jnp.asarray(survivors, dtype=jnp.uint8)[:, : self.k, :]
        bits, _ = self.decode_bitmatrix(present, targets)
        return bp.gf_matmul_bitplane(bits, survivors)

    def encode_bitmatrix(self):
        """The parity rows' (bitplane, packed) pair, as decode_bitmatrix
        gives a decode's."""
        return self._encode_bits, self._encode_packed

    # -- planar word API: the fused Pallas fast path --------------------------

    def encode_words(self, words) -> jnp.ndarray:
        """Chunk-planar encode: (k, N/4) int32 words -> (m, N/4) parity words.

        The TPU-native entry point — rows are whole chunk columns (many
        objects' chunk j packed end to end), bytes ride 4-per-lane through the
        fused kernel (ceph_tpu.ops.gf_pallas). Falls back to the XLA bit-plane
        path off-TPU so the data path runs identically on CPU meshes.
        """
        words = jnp.asarray(words, dtype=jnp.int32)
        if self._xor_ok:
            return gp.xor_reduce_words(words)
        if gp.available():
            return gp.gf_matmul_packed(self._encode_packed, words)
        return bp.gf_matmul_words(self._encode_bits, words)

    def decode_words(self, present, targets, words) -> jnp.ndarray:
        """Planar decode: words holds the first k survivor chunks (logical ids
        `present`, ascending); returns len(targets) rebuilt chunk rows."""
        if len(present) < self.k:
            raise ErasureCodeError(errno.EIO, "not enough survivors")
        words = jnp.asarray(words, dtype=jnp.int32)[: self.k]
        bits, packed = self.decode_bitmatrix(present, targets)
        if gp.available():
            return gp.gf_matmul_packed(packed, words)
        return bp.gf_matmul_words(bits, words)

    # -- plane API: the EncodeService's batches --------------------------------

    def plan_survivors(self, present) -> tuple:
        """A plan reads the first k survivors and depends on no other."""
        return tuple(present)[: self.k]

    def plane_plan(self, present, targets) -> PlanePlan:
        """The launch that rebuilds logical chunks `targets` from the first
        k of logical chunks `present`: one plane, one GF(2^8) matrix. On
        the TPU the matrix is packed for the fused Pallas kernel (the
        encode's is the one encode_words uses, a decode's comes from
        decode_bitmatrix); elsewhere it is the byte matrix of the numpy
        table path, or None where the parity row is a plain XOR. Encode
        is the plan whose present chunks are the data chunks and whose
        targets are the parity chunks. Memoized per erasure signature."""
        present = self.plan_survivors(present)
        targets = tuple(targets)
        if len(present) < self.k:
            raise ErasureCodeError(errno.EIO, "not enough survivors")

        def build():
            self.plans_built += 1
            pallas = gp.available()
            n = self.get_chunk_count()
            encode = (present == tuple(range(self.k))
                      and targets == tuple(range(self.k, n)))
            if encode and self._xor_ok:
                mat = None
            elif encode:
                mat = self._encode_packed if pallas else self._gen[self.k:]
            elif pallas:
                mat = self.decode_bitmatrix(present, targets)[1]
            else:
                mat = matrices.decode_matrix(
                    self._gen, self.k, list(present), list(targets))
            return PlanePlan(
                inputs=present, targets=targets,
                rows=tuple(range(len(targets))), groups=1, pallas=pallas,
                path="pallas" if pallas else "numpy",
                program=("encode", n) if encode else (
                    "decode", len(targets)),
                args=(mat,))

        return self._decode_cache.get_or(("plan", present, targets), build)

    def planes(self, plan: PlanePlan, words) -> np.ndarray:
        """Run `plan` on (k, 1, W) int32 words whose row j holds chunk
        plan.inputs[j] of every object of a batch, side by side along W.
        Returns (len(plan.targets), 1, W) int32 in host memory."""
        (mat,) = plan.args
        rows = np.asarray(words).reshape(len(plan.inputs), -1)
        if plan.pallas:
            rows = jnp.asarray(rows, dtype=jnp.int32)
            out = np.asarray(gp.xor_reduce_words(rows) if mat is None
                             else gp.gf_matmul_packed(mat, rows))
        else:
            data = rows.view(np.uint8)
            out = (np.bitwise_xor.reduce(data, axis=0)[None] if mat is None
                   else gf_region_matmul(mat, data)).view("<i4")
        return out[:, None]
