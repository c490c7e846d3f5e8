"""Tests for the fused packed-lane Pallas kernel (ceph_tpu.ops.gf_pallas).

Runs on the CPU mesh via Pallas interpret mode; bit-exactness is asserted
against the numpy GF(2^8) oracle (ceph_tpu.ops.gf). The compile for a v5e
chip is tests/test_chip_compile.py; the run on one is chip_smoke.py.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ceph_tpu.ops import gf
from ceph_tpu.ops import gf_pallas as gp
from ceph_tpu.ec.registry import factory


def ref_gf_matmul(mat, data):
    """Numpy oracle: (r, k) GF matrix x (k, N) bytes -> (r, N)."""
    return gf.gf_matmul(mat, data)


def test_pack_matrix_structure():
    rng = np.random.default_rng(0)
    r, k = 3, 5
    bitmat = (rng.random((8 * r, 8 * k)) < 0.4).astype(np.int8)
    big = gp.pack_matrix(bitmat)
    assert big.shape == (32 * r, 32 * k)
    want = np.zeros_like(big)
    for i in range(r):
        for bo in range(8):
            for j in range(k):
                for bi in range(8):
                    for s in range(4):
                        want[bo * 4 * r + 4 * i + s, bi * 4 * k + 4 * j + s] = (
                            bitmat[i * 8 + bo, j * 8 + bi]
                        )
    assert np.array_equal(big, want)


def test_bytes_words_roundtrip():
    rng = np.random.default_rng(1)
    chunks = rng.integers(0, 256, (4, 256), np.uint8)
    words = gp.bytes_to_words(chunks)
    assert words.shape == (4, 64) and words.dtype == np.int32
    assert np.array_equal(gp.words_to_bytes(words), chunks)
    # device-side bitcast agrees with the host view (little-endian on both)
    dev = jax.lax.bitcast_convert_type(jnp.asarray(words), jnp.uint8)
    assert np.array_equal(np.asarray(dev).reshape(4, 256), chunks)


@pytest.mark.parametrize("k,r", [(4, 2), (8, 3), (6, 4)])
def test_packed_matmul_vs_oracle(k, r):
    rng = np.random.default_rng(2)
    mat = rng.integers(0, 256, (r, k), np.uint8)
    bitmat = gf.matrix_to_bitmatrix(mat)
    data = rng.integers(0, 256, (k, 512), np.uint8)
    want = ref_gf_matmul(mat, data)
    got = gp.gf_matmul_packed(
        jnp.asarray(gp.pack_matrix(bitmat)),
        jnp.asarray(gp.bytes_to_words(data)),
        interpret=True,
    )
    assert np.array_equal(gp.words_to_bytes(np.asarray(got)), want)


def test_xor_reduce_words():
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (5, 128), np.uint8)
    got = gp.xor_reduce_words(jnp.asarray(gp.bytes_to_words(data)))
    want = data[0]
    for row in data[1:]:
        want = want ^ row
    assert np.array_equal(gp.words_to_bytes(np.asarray(got))[0], want)


def test_codec_words_path_matches_array_path():
    """encode_words/decode_words (XLA fallback on CPU) == (B,k,L) array path."""
    ec = factory("isa", {"k": "8", "m": "3", "technique": "cauchy"})
    rng = np.random.default_rng(4)
    data = rng.integers(0, 256, (8, 1024), np.uint8)
    parity_arr = np.asarray(ec.encode_array(data[None]))[0]
    parity_words = np.asarray(ec.encode_words(gp.bytes_to_words(data)))
    assert np.array_equal(gp.words_to_bytes(parity_words), parity_arr)

    # degraded: lose chunks 0, 5, 9 -> decode targets 0 and 5 from survivors
    full = np.concatenate([data, parity_arr], axis=0)
    present = [i for i in range(11) if i not in (0, 5, 9)]
    survivors = full[present[:8]]
    got = ec.decode_words([p for p in present][:8], [0, 5],
                          gp.bytes_to_words(survivors))
    assert np.array_equal(gp.words_to_bytes(np.asarray(got)), full[[0, 5]])


def test_codec_words_xor_fast_path():
    ec = factory("isa", {"k": "4", "m": "1"})
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (4, 512), np.uint8)
    parity = gp.words_to_bytes(np.asarray(ec.encode_words(gp.bytes_to_words(data))))
    assert np.array_equal(parity[0], data[0] ^ data[1] ^ data[2] ^ data[3])
