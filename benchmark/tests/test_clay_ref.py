"""The CLAY reference (clay_ref.py) on its own properties, against the
program's codec, and the CLAY cell's three metric readers on spans and a
device trace worked by hand. Tag values are strings, as the tracer's
Jaeger export writes them."""

import itertools
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import gf_ref, harness
from benchmark.clay_ref import ClayRef

REF = {}


def ref(k, m, d) -> ClayRef:
    if (k, m, d) not in REF:
        REF[(k, m, d)] = ClayRef(k, m, d)
    return REF[(k, m, d)]


def payload(seed: int, size: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, np.uint8).tobytes()


def test_geometry_of_8_4_11():
    r = ref(8, 4, 11)
    assert (r.q, r.t, r.nu, r.planes, r.nodes) == (4, 3, 0, 64, 12)
    assert r.chunk_size(4096) == 2048
    assert r.chunk_size(4 << 20) == 524288
    # m planes' worth of equations, each over all coupled symbols
    assert r.h.shape == (4 * 64, 12 * 64)


def test_pair_code_is_the_isa_cauchy_2_2_code():
    assert gf_ref.cauchy_parity(2, 2).tolist() == [
        [gf_ref.inv(2), gf_ref.inv(3)], [gf_ref.inv(3), gf_ref.inv(2)]]


def test_encode_then_decode_is_the_identity():
    r = ref(8, 4, 11)
    data = payload(1, 5 * 8 * 2048 + 3)
    full = r.encode(data)
    assert full.shape == (12, 5 * 2048 + 2048)
    assert full[:8].reshape(-1)[: len(data)].tobytes() == data
    # H C = 0 on every byte column of every plane
    c = full.reshape(12 * 64, -1)
    assert not gf_ref.matmul(r.h, c).any()
    chunks = {i: full[i] for i in range(12)}
    lost = [0, 3, 8, 11]
    got = r.rebuild({i: a for i, a in chunks.items() if i not in lost}, lost)
    assert np.array_equal(got, full[lost])


#: a seeded sample of the 495 ways to keep 8 of 12, and the kept sets a
#: read with one OSD down gathers
KEEP = sorted({tuple(sorted(np.random.default_rng(25).choice(
    12, 8, replace=False).tolist())) for _ in range(6)} | {
    tuple(i for i in range(12) if i != lost)[:8] for lost in (0, 6)})


@pytest.mark.parametrize("kept", KEEP, ids=lambda k: "-".join(map(str, k)))
def test_any_8_of_12_rebuild_the_object(kept):
    r = ref(8, 4, 11)
    full = r.encode(payload(2, 8 * 4096))
    lost = [i for i in range(12) if i not in kept]
    got = r.rebuild({i: full[i] for i in kept}, lost)
    assert np.array_equal(got, full[lost])


def _program(k, m, d):
    from ceph_tpu.ec.registry import factory

    return factory("clay", {"k": str(k), "m": str(m), "d": str(d),
                            "scalar_mds": "isa", "technique": "cauchy"})


@pytest.mark.parametrize("kmd,size", [((4, 2, 5), 4 * 256),
                                      ((4, 2, 5), 3000),
                                      ((8, 4, 11), 8 * 2048),
                                      ((8, 4, 11), 40_000)])
def test_agrees_with_the_programs_codec(kmd, size):
    """The program's ErasureCodeClay encodes, decodes and repairs (its
    numpy MSR repair from d helpers' sub-chunks) as the reference."""
    ec, r = _program(*kmd), ref(*kmd)
    k, m, d = kmd
    data = payload(size, size)
    want = r.encode(data)
    got = ec.encode(range(k + m), data)
    assert [got[i] for i in range(k + m)] == [w.tobytes() for w in want]
    for lost in ([0], [k], [1, k + m - 1]):
        avail = {i: want[i].tobytes() for i in range(k + m) if i not in lost}
        out = ec.decode(set(lost), avail)
        assert [out[i] for i in lost] == [want[i].tobytes() for i in lost]
    lost = 1
    minimum = ec.minimum_to_decode({lost}, set(range(k + m)) - {lost})
    sc = want.shape[1] // r.planes
    partial = {c: b"".join(want[c][o * sc:(o + n) * sc].tobytes()
                           for o, n in runs) for c, runs in minimum.items()}
    out = ec.decode({lost}, partial, chunk_size=want.shape[1])
    assert out[lost] == want[lost].tobytes()


@pytest.mark.parametrize("lost", list(itertools.combinations(range(6), 2)))
def test_4_2_5_two_lost(lost):
    r = ref(4, 2, 5)
    full = r.encode(payload(9, 4 * 256 * 2))
    kept = {i: full[i] for i in range(6) if i not in lost}
    assert np.array_equal(r.rebuild(kept, lost), full[list(lost)])


# -- the CLAY cell's readers --------------------------------------------------


def _span(sid, name, start, end, trace="t", **tags):
    return {"trace_id": trace, "span_id": sid, "parent_id": None,
            "name": name, "service": "osd.1", "start": start,
            "duration": end - start,
            "tags": {k: str(v) for k, v in tags.items()}}


def read(metric, ctx):
    return harness.load_module("metrics", metric).read(ctx)


#: tracer time t lies at t - 999.5 on the device trace
PROFILED = (1000.0, 1010.0)
WINDOW = [0.5, 10.5]


def _batch(sid, name, start, dev_start, dev_s, end, batch, path="clay"):
    return _span(sid, name, start, end, path=path, batch=batch, planes=64,
                 groups=3, device_start=dev_start, device_s=dev_s)


#: two CLAY decodes (legs [1.5, 1.9] and [3.5, 3.7] on the device trace),
#: a CLAY encode and an RS decode
LAUNCHES = [
    _batch("d1", "decode_batch", 1000.9, 1001.0, 0.4, 1001.5, 3),
    _batch("d2", "decode_batch", 1002.8, 1003.0, 0.2, 1003.3, 1),
    _batch("e", "encode_batch", 1004.0, 1004.1, 0.1, 1004.2, 2),
    _batch("r", "decode_batch", 1005.0, 1005.1, 0.1, 1005.4, 8,
           path="pallas"),
]

K, ALIGN = 8, 2048
#: two reads that each rebuilt one chunk, one healthy read, one write
READS = [
    _span("a", "op_submit", 1001.0, 1002.0, op="read", object="r00001"),
    _span("b", "op_submit", 1003.0, 1004.0, op="read", object="r00002"),
    _span("c", "op_submit", 1003.0, 1004.0, op="read", object="r00003"),
    _span("w", "op_submit", 1003.0, 1004.0, op="write", object="r00001"),
]
SIZES = {"r00001": 4096, "r00002": 1 << 20, "r00003": 1 << 20}
ERASED = {"r00001": 1, "r00002": 1, "r00003": 0}


def _ctx(spans, devices=None):
    return SimpleNamespace(
        spans=spans, profiled=PROFILED, erased=ERASED, sizes=SIZES, k=K,
        align=ALIGN, peaks={"hbm_bytes_per_s": 819e9},
        trace={"window": WINDOW, "devices": devices or {}, "host": []})


def test_clay_launch_loop_ms_is_the_mean_clay_span():
    # (0.6 + 0.5 + 0.2) / 3 s: the RS launch is not CLAY's
    assert read("clay_launch_loop_ms", _ctx(LAUNCHES)) == pytest.approx(
        1e3 * 1.3 / 3)
    assert read("clay_launch_loop_ms", _ctx(LAUNCHES[3:])) is None


def test_clay_objects_per_launch_is_the_mean_batch_tag():
    assert read("clay_objects_per_launch", _ctx(LAUNCHES)) == (
        pytest.approx(2.0))
    assert read("clay_objects_per_launch", _ctx([])) is None


def test_clay_roofline_over_the_busy_time_in_clay_decode_legs():
    devices = {"/device:TPU:0": [
        # 0.1 s inside the first leg (overlapping ops count once) and
        # 0.05 s in the second; the encode's and the RS leg's ops, and one
        # outside every leg, do not count
        ["fusion", 1.6, 0.1], ["gf_matmul_packed", 1.65, 0.05],
        ["while", 3.6, 0.05], ["gf_matmul_packed", 4.65, 0.05],
        ["gf_matmul_packed", 5.7, 0.05], ["copy", 8.0, 1.0]]}
    moved = (K + 1) * (2048 + 131072)
    want = 100.0 * moved / 819e9 / 0.15
    got = read("clay_roofline", _ctx(LAUNCHES + READS, devices))
    assert got == pytest.approx(want)
    # silent with no device ops, no CLAY decode leg, or no decoded read
    assert read("clay_roofline", _ctx(LAUNCHES + READS)) is None
    assert read("clay_roofline", _ctx(LAUNCHES[2:] + READS,
                                      devices)) is None
    assert read("clay_roofline", _ctx(LAUNCHES, devices)) is None
