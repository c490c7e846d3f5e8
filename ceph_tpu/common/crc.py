"""ceph_crc32c — the Castagnoli CRC the reference uses everywhere.

Re-expresses the reference's src/include/crc32c.h (`ceph_crc32c(seed, data,
len)`): CRC-32C (polynomial 0x1EDC6F41, reflected 0x82F63B78),
bitwise-reflected in/out, NO final inversion — callers seed with -1
themselves (e.g. the EC deep-scrub shard hashes, ECBackend.cc:2482
`bufferhash(-1)`, and ECUtil::HashInfo's cumulative shard hashes).

The bytes are checksummed in C (ceph_tpu/native/crc32c.c), which picks its
implementation once, when it loads: the x86-64 CRC32 instruction over three
interleaved streams where the CPU has SSE4.2, else table slicing-by-8.
`impl()` names the one in use. Without a compiler the byte loop runs over a
numpy view with the same slicing-by-8 tables, about a thousand times slower.
Parity of all three, and with the compiled reference sctp_crc32.c, is pinned
in tests/test_crc32c.py and tests/test_scrub.py.
"""

from __future__ import annotations

import ctypes

import numpy as np

_POLY = 0x82F63B78


def _make_table() -> np.ndarray:
    table = np.zeros((8, 256), dtype=np.uint32)
    for n in range(256):
        c = n
        for _ in range(8):
            c = (c >> 1) ^ _POLY if c & 1 else c >> 1
        table[0, n] = c
    for k in range(1, 8):
        for n in range(256):
            c = table[k - 1, n]
            table[k, n] = table[0, c & 0xFF] ^ (c >> 8)
    return table


_TABLE = _make_table()


def _load_native():
    """The C library (ceph_tpu/native/crc32c.c) via ctypes: the frame
    checksum and shard hashes are per-byte hot paths that a Python loop
    turns into the daemon's top CPU sink. None when it cannot be built;
    callers then take the numpy loop (same bits; parity pinned in tests)."""
    try:
        import os

        from ceph_tpu.native.build import build_shared

        so = build_shared(
            "crc32c",
            os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__)
                )),
                "native", "crc32c.c",
            ),
        )
        if so is None:
            return None
        lib = ctypes.CDLL(so)
        for fn in (lib.ceph_crc32c, lib.ceph_crc32c_sctp8):
            fn.restype = ctypes.c_uint32
            fn.argtypes = [ctypes.c_uint32, ctypes.c_char_p, ctypes.c_size_t]
        lib.ceph_crc32c_impl.restype = ctypes.c_char_p
        lib.ceph_crc32c_impl.argtypes = []
        return lib
    # cephlint: disable=error-taxonomy (native-impl probe: any failure falls back to the python crc)
    except Exception:
        return None


_LIB = _load_native()
#: the load-time choice of ceph_tpu/native/crc32c.c, or None (numpy)
_NATIVE = _LIB.ceph_crc32c if _LIB is not None else None


def impl() -> str:
    """Which implementation checksums: "sse4.2" (the CRC32 instruction),
    "sctp8" (C slicing-by-8) or "numpy" (no library could be built)."""
    if _LIB is None:
        return "numpy"
    return _LIB.ceph_crc32c_impl().decode()


def ceph_crc32c(
    seed: int, data: bytes | np.ndarray, length: int | None = None
) -> int:
    """crc32c(seed, data[:length]) with ceph's conventions (no final xor).

    `length` checksums a prefix without materializing the slice — the wire
    read path hands the whole `payload+crc` buffer straight here. Chaining
    is supported the way the reference's bufferlist crc is: the return
    value is the raw register state, so crc(AB) == crc32c(crc32c(seed, A),
    B) — Frame.encode_parts exploits that to checksum a segment list
    without joining it first.
    """
    if _NATIVE is not None:
        return _native_crc(_NATIVE, seed, data, length)
    return _numpy_crc(seed, data, length)


def _native_crc(fn, seed: int, data, length: int | None) -> int:
    """Hand `data` to the C function `fn` without a copy where it can be
    had: bytes directly, writable contiguous memoryviews by address."""
    if isinstance(data, memoryview) and data.contiguous:
        # the shm-ring receive path checksums loaned views; handing
        # the buffer address over directly keeps it zero-copy
        n = data.nbytes if length is None else min(length, data.nbytes)
        try:
            buf = (ctypes.c_char * data.nbytes).from_buffer(data)
            return int(fn(seed & 0xFFFFFFFF, buf, n))
        except TypeError:
            pass  # read-only exporter: fall through to the copy
    raw = data if isinstance(data, bytes) else bytes(data)
    n = len(raw) if length is None else min(length, len(raw))
    return int(fn(seed & 0xFFFFFFFF, raw, n))


def _numpy_crc(seed: int, data, length: int | None) -> int:
    """The fallback byte loop: slicing-by-8 over a numpy view."""
    crc = np.uint32(seed & 0xFFFFFFFF)
    buf = np.frombuffer(
        data if isinstance(data, (bytes, bytearray, memoryview))
        else bytes(data),
        dtype=np.uint8,
    )
    if length is not None:
        buf = buf[:length]
    t = _TABLE
    n8 = len(buf) // 8 * 8
    if n8:
        words = buf[:n8].reshape(-1, 8)
        for row in words:
            crc = np.uint32(
                t[7, (crc ^ row[0]) & np.uint32(0xFF)]
                ^ t[6, ((crc >> np.uint32(8)) ^ row[1]) & np.uint32(0xFF)]
                ^ t[5, ((crc >> np.uint32(16)) ^ row[2]) & np.uint32(0xFF)]
                ^ t[4, ((crc >> np.uint32(24)) ^ row[3]) & np.uint32(0xFF)]
                ^ t[3, row[4]] ^ t[2, row[5]] ^ t[1, row[6]] ^ t[0, row[7]]
            )
    for b in buf[n8:]:
        crc = np.uint32(t[0, (crc ^ b) & np.uint32(0xFF)] ^ (crc >> np.uint32(8)))
    return int(crc)
