"""Fused Pallas GF(2^8) matmul — the flagship erasure-code kernel.

The XLA bit-plane pipeline (ceph_tpu.ops.gf_bitplane) materializes the 8x bit
expansion in HBM, so its throughput is capped by ~30x-amplified HBM traffic.
This kernel keeps the whole expansion in VMEM and, critically, keeps FOUR bytes
packed per int32 lane end to end:

  * data lives as (k, N/4) int32 words (a free reinterpret of the (k, N) uint8
    chunk-planar layout — chunk j is row j, matching the reference's per-chunk
    char* buffers, ErasureCodeInterface.h:290-300);
  * bit-plane b of all 4 packed bytes is extracted with ONE shift + ONE mask:
    (w >> b) & 0x01010101 — 2 VPU ops per 4 bytes per bit instead of the 16x
    cost of per-byte lanes;
  * `pltpu.bitcast` int32->int8 turns each packed plane into 4 int8 sublanes
    for free (byte s of word row j lands in sublane 4j+s, LSB first), so the
    MXU sees ordinary int8 {0,1} operands;
  * the coding matrix is expanded host-side to a (32r, 32k) block matrix
    M[bo*4r+4i+s, bi*4k+4j+s'] = delta(s,s') * bitmat[i*8+bo, j*8+bi] so the
    byte-in-word position s rides through the contraction unchanged;
  * the int32 accumulator's parity bit is exact (contraction width 32k <= 2^8
    of {0,1} values), and the output is re-packed with 8 shift-or ops into
    (r, N/4) int32 words.

Measured on one v5e chip this runs RS(8,3) encode at ~300 GB/s vs ~47 GB/s for
the XLA path — VPU-bound on the plane extraction, with the HBM roofline at
~596 GB/s (1 + m/k traffic ratio) and the MXU roofline at ~193 GB/s*K-pad for
this geometry.

Only {0,1} bit-matrices are accepted (any GF(2^8) coding matrix expands to one
via ceph_tpu.ops.gf.matrix_to_bitmatrix). Decode uses the same kernel with the
inverted-submatrix bit-planes, mirroring how the reference feeds
ec_encode_data with either encode or decode tables (ErasureCodeIsa.cc:121-128,
274-302).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "available",
    "pack_matrix",
    "bytes_to_words",
    "words_to_bytes",
    "gf_matmul_packed",
    "xor_reduce_words",
    "DEFAULT_TILE_WORDS",
]

#: lanes per grid step; chosen from a v5e sweep (see BASELINE.md) — large
#: enough to amortize the (32r, 32k) matmul, small enough to double-buffer.
DEFAULT_TILE_WORDS = 65536


def available() -> bool:
    """True when the default backend can compile Mosaic kernels. A
    backend that fails to start raises here rather than reading as
    "no TPU", which would send the codec down the XLA path in silence."""
    return jax.default_backend() == "tpu"


def pack_matrix(bitmat: np.ndarray) -> np.ndarray:
    """(8r, 8k) {0,1} bit-matrix -> (32r, 32k) packed-lane MXU matrix.

    Row/column order is (bit, item, byte-in-word): index b*4n + 4i + s. The
    identity over s expresses that byte s of an output word only ever depends
    on byte s of the input words.
    """
    r8, k8 = bitmat.shape
    if r8 % 8 or k8 % 8:
        raise ValueError(f"bit-matrix shape {bitmat.shape} must be 8-aligned")
    r, k = r8 // 8, k8 // 8
    bm4 = np.asarray(bitmat, dtype=np.int8).reshape(r, 8, k, 8)
    eye4 = np.eye(4, dtype=np.int8)
    big = (
        bm4.transpose(1, 0, 3, 2)[:, :, None, :, :, None]
        * eye4[None, None, :, None, None, :]
    )  # (bo, i, s, bi, j, s')
    return np.ascontiguousarray(big.reshape(32 * r, 32 * k))


def bytes_to_words(chunks: np.ndarray) -> np.ndarray:
    """(k, N) uint8 -> (k, N/4) int32, little-endian (free host-side view)."""
    chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
    if chunks.shape[-1] % 4:
        raise ValueError("chunk length must be a multiple of 4 bytes")
    return chunks.view("<i4")


def words_to_bytes(words: np.ndarray) -> np.ndarray:
    """(k, N/4) int32 -> (k, N) uint8. Inverse of bytes_to_words."""
    return np.ascontiguousarray(words, dtype="<i4").view(np.uint8)


def _kernel(k: int, r: int):
    def kern(mat_ref, data_ref, out_ref):
        mask = jnp.int32(0x01010101)
        w = data_ref[...]  # (k, tile) int32
        bits = jnp.concatenate(
            [pltpu.bitcast((w >> b) & mask, jnp.int8) for b in range(8)],
            axis=0,
        )  # (32k, tile) int8 {0,1}, rows b*4k + 4j + s
        acc = jax.lax.dot_general(
            mat_ref[...],
            bits,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (32r, tile); parity bit of each lane is the output bit
        packed = pltpu.bitcast((acc & 1).astype(jnp.int8), jnp.int32)  # (8r, tile)
        o = packed[0:r]
        for b in range(1, 8):
            o = o | (packed[b * r : (b + 1) * r] << b)
        out_ref[...] = o

    return kern


@functools.partial(jax.jit, static_argnames=("tile_words", "interpret"))
def gf_matmul_packed(
    packed_mat: jnp.ndarray,
    words: jnp.ndarray,
    *,
    tile_words: int = DEFAULT_TILE_WORDS,
    interpret: bool = False,
) -> jnp.ndarray:
    """(32r, 32k) packed matrix x (k, N4) int32 words -> (r, N4) int32 words."""
    r32, k32 = packed_mat.shape
    r, k = r32 // 32, k32 // 32
    n4 = words.shape[1]
    if words.shape[0] != k:
        raise ValueError(f"words rows {words.shape[0]} != matrix k {k}")
    tile = min(tile_words, max(128, -(-n4 // 128) * 128))
    grid = (pl.cdiv(n4, tile),)
    # block indices stay int32 under jax_enable_x64 (which CRUSH turns on):
    # Mosaic refuses an index map that returns a 64-bit literal 0
    def whole(i):
        return jnp.int32(0), jnp.int32(0)

    def column(i):
        return jnp.int32(0), i

    return pl.pallas_call(
        _kernel(k, r),
        grid=grid,
        in_specs=[
            pl.BlockSpec((r32, k32), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((k, tile), column, memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((r, tile), column, memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((r, n4), jnp.int32),
        interpret=interpret,
    )(packed_mat, words)


@jax.jit
def xor_reduce_words(words: jnp.ndarray) -> jnp.ndarray:
    """m=1 fast path on packed words: (k, N4) int32 -> (1, N4) XOR.

    Mirrors the reference ISA plugin's m==1 region-XOR short-circuit
    (ErasureCodeIsa.cc:121-128, xor_op.cc) — XOR commutes with the packing.
    """
    return jax.lax.reduce(
        words, jnp.int32(0), jax.lax.bitwise_xor, dimensions=(0,)
    )[None, :]
