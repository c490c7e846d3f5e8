"""Bit-plane GF(2^8) matmul — the TPU-native formulation of erasure coding.

Multiplication by a constant in GF(2^8) is linear over GF(2), so an (r x k)
GF(2^8) coding matrix expands to an (8r x 8k) {0,1} matrix and a batched
encode/decode becomes ONE integer matmul followed by a parity (mod 2) reduction:

    bytes (B, k, L)  --unpack-->  bits (B, 8k, L)   [int8, {0,1}]
    bits_out = (M8 @ bits) & 1                      [MXU matmul, int32 accum]
    bytes_out (B, r, L)  <--pack--  bits_out

This is the same linear-algebra fact jerasure's bitmatrix "schedule" codecs
exploit with XOR schedules on CPUs (reference: jerasure plugin technique
cauchy_good, /root/reference/src/erasure-code/jerasure/ErasureCodeJerasure.cc,
prepare_schedule) — but instead of a sparse XOR schedule, the TPU wants the
dense formulation so the systolic array (MXU) does 8k-wide dot products at
int8 throughput. Exactness: entries are {0,1}, accumulation is int32, and the
contraction width is 8k <= 2048 in practice, so there is no rounding anywhere.

Everything here is jittable JAX; the numpy oracle lives in ceph_tpu.ops.gf and
tests assert bit-exact equality.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ops.gf import matrix_to_bitmatrix

__all__ = [
    "bitplane_matrix",
    "unpack_bits",
    "pack_bits",
    "gf_matmul_bitplane",
    "gf_matmul_words",
    "xor_reduce",
]


def bitplane_matrix(mat: np.ndarray) -> jnp.ndarray:
    """Expand an (r x c) GF(2^8) matrix to its (8r x 8c) GF(2) form as int8.

    Host-side, done once per (technique, k, m, erasure-signature) and cached by
    the codec layer — the analogue of the reference's decode-table cache
    (ErasureCodeIsaTableCache.cc).
    """
    return jnp.asarray(matrix_to_bitmatrix(mat), dtype=jnp.int8)


def unpack_bits(x: jnp.ndarray) -> jnp.ndarray:
    """(..., n, L) uint8 -> (..., 8n, L) int8 bits, LSB-first within each byte.

    Row n*8+b holds bit b of chunk-row n, matching the bit order of
    ceph_tpu.ops.gf.bytes_to_bits / mul_bitmatrix.
    """
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[..., :, None, :] >> shifts[None, :, None]) & jnp.uint8(1)
    return bits.reshape(*x.shape[:-2], x.shape[-2] * 8, x.shape[-1]).astype(jnp.int8)


def pack_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(..., 8n, L) {0,1} int -> (..., n, L) uint8. Inverse of unpack_bits."""
    n8, length = bits.shape[-2], bits.shape[-1]
    b = bits.reshape(*bits.shape[:-2], n8 // 8, 8, length).astype(jnp.int32)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))[None, :, None]
    return (b * weights).sum(axis=-2).astype(jnp.uint8)


@jax.jit
def gf_matmul_bitplane(bitmat: jnp.ndarray, data: jnp.ndarray) -> jnp.ndarray:
    """Batched GF(2^8) matmul: (8r, 8k) bit-matrix x (B, k, L) bytes -> (B, r, L).

    The contraction runs on the MXU as int8 x int8 -> int32 over BOTH the chunk
    axis and the bit axis at once (a multi-dimensional dot_general), so the
    unpacked bits keep their natural (B, k, 8, L) layout — merging k and the
    bit axis into one dimension would force a tiled-layout relayout copy of the
    8x-expanded bits array, which measured ~20% slower end-to-end on v5e. The
    mod-2 reduction and byte re-pack stay in the (8r, B, L) result layout until
    a single final small transpose.
    """
    batch, k, length = data.shape
    r8 = bitmat.shape[0]
    mat3 = bitmat.reshape(r8, k, 8)  # column j*8+b -> (chunk j, bit b)
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (
        (data[:, :, None, :] >> shifts[None, None, :, None]) & jnp.uint8(1)
    ).astype(jnp.int8)  # (B, k, 8, L)
    acc = jax.lax.dot_general(
        mat3,
        bits,
        dimension_numbers=(((1, 2), (1, 2)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (8r, B, L)
    acc = (acc & 1).reshape(r8 // 8, 8, batch, length)
    weights = (jnp.int32(1) << jnp.arange(8, dtype=jnp.int32))[:, None, None]
    out = (acc * weights[None]).sum(axis=1).astype(jnp.uint8)  # (r, B, L)
    return jnp.moveaxis(out, 1, 0)


def gf_matmul_words(bitmat: jnp.ndarray, words: jnp.ndarray) -> jnp.ndarray:
    """(8r, 8k) bit-matrix x (k, N4) int32 words -> (r, N4) int32 words:
    the packed-word layout of ceph_tpu.ops.gf_pallas.gf_matmul_packed on
    the XLA bit-plane path, for backends without the Pallas kernel
    (bit-exact, slower)."""
    bytes_ = jax.lax.bitcast_convert_type(words, jnp.uint8)  # (k, N4, 4)
    flat = bytes_.reshape(words.shape[0], -1)
    out = gf_matmul_bitplane(bitmat, flat[None])[0]
    return jax.lax.bitcast_convert_type(
        out.reshape(out.shape[0], -1, 4), jnp.int32
    )


@jax.jit
def xor_reduce(data: jnp.ndarray) -> jnp.ndarray:
    """m=1 fast path: parity chunk = XOR of the k data chunks.

    Mirrors the reference ISA plugin's short-circuit for a single parity
    (region XOR, ErasureCodeIsa.cc:121-128 / xor_op.cc) — no bit expansion.
    data: (B, k, L) uint8 -> (B, 1, L) uint8.
    """
    return jax.lax.reduce(
        data, jnp.uint8(0), jax.lax.bitwise_xor, dimensions=(data.ndim - 2,)
    )[..., None, :]
