"""ceph_crc32c's implementations against each other: the load-time choice
of ceph_tpu/native/crc32c.c (the CRC32 instruction on x86-64 with SSE4.2),
its exported slicing-by-8 fallback, and the numpy loop, on the same bytes.

Lengths cover the single stream (0-64), both sides of each three-stream
block boundary, and buffers of many blocks; start offsets 0-7 put every
alignment of the head under the instruction path.
"""

import platform
import random
import shutil

import pytest

from ceph_tpu.common import crc

#: per-stream block lengths of the three-stream loop in crc32c.c
BLOCKS = (256, 8192)
LENGTHS = sorted(
    set(range(65))
    | {n for b in BLOCKS for n in (b - 1, b, b + 1,
                                   3 * b - 1, 3 * b, 3 * b + 1)}
    | {65536, (1 << 20) + 7, 4 << 20}
)
SEEDS = (0, 0xFFFFFFFF, 0xDEADBEEF)
#: above this the numpy loop takes seconds: it checks one offset and seed
NUMPY_ALL_UP_TO = 65536


def _sctp8(seed, data):
    return crc._native_crc(crc._LIB.ceph_crc32c_sctp8, seed, data, None)


@pytest.mark.parametrize("n", LENGTHS)
def test_crc32c_implementations_agree(n):
    if crc._LIB is None:
        pytest.skip("no C compiler: only the numpy loop exists")
    rng = random.Random(n)
    # a bytearray, so each slice is a writable view handed over by
    # address, at its own alignment, and not copied to aligned bytes
    backing = bytearray(rng.randbytes(n + 8))
    for off in range(8):
        view = memoryview(backing)[off:off + n]
        for seed in SEEDS:
            want = _sctp8(seed, view)
            assert crc.ceph_crc32c(seed, view) == want, (n, off, seed)
            if n <= NUMPY_ALL_UP_TO or (off, seed) == (0, SEEDS[1]):
                assert crc._numpy_crc(seed, view, None) == want, (n, off, seed)
    # chaining: crc(AB) == crc(crc(A), B) at every kind of split point
    data = bytes(backing[:n])
    whole = crc.ceph_crc32c(0xFFFFFFFF, data)
    cuts = {0, 1, n // 3, n // 2, n - 1, n} | {
        c for b in BLOCKS for c in (b, 3 * b, 3 * b + 5)
    }
    for cut in sorted(c for c in cuts if 0 <= c <= n):
        head = crc.ceph_crc32c(0xFFFFFFFF, data[:cut])
        assert crc.ceph_crc32c(head, data[cut:]) == whole, (n, cut)
        assert _sctp8(_sctp8(0xFFFFFFFF, data[:cut]), data[cut:]) == whole


def _has_sse42() -> bool:
    try:
        with open("/proc/cpuinfo") as f:
            return " sse4_2" in f.read()
    except OSError:
        return False


def test_crc32c_native_engaged():
    """A library that fails to build must fail here, not quietly move every
    frame onto the numpy loop, about a thousand times slower."""
    if shutil.which("cc") or shutil.which("gcc") or shutil.which("g++"):
        assert crc._NATIVE is not None
    if platform.machine() == "x86_64" and _has_sse42():
        assert crc.impl() == "sse4.2"
    # the CRC-32C check value (init -1, final xor -1) on every path
    check = b"123456789"
    assert crc.ceph_crc32c(0xFFFFFFFF, check) ^ 0xFFFFFFFF == 0xE3069283
    assert crc._numpy_crc(0xFFFFFFFF, check, None) ^ 0xFFFFFFFF == 0xE3069283
    if crc._LIB is not None:
        assert _sctp8(0xFFFFFFFF, check) ^ 0xFFFFFFFF == 0xE3069283
    # `perf dump` says which one checksums every frame
    from ceph_tpu.msg.messenger import Messenger

    perf = Messenger("client.crc").perf.dump()
    assert perf["crc_hw"] == int(crc.impl() == "sse4.2")
