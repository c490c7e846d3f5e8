"""The RS codecs' plane API, the EncodeService's one batch path: a plan
per erasure signature, a batch of objects packed side by side into one
(k, 1, bucket) launch, each object's rebuilt chunks unpacked. Every
object must equal both the codec's byte API and the GF(2^8) oracle."""

import asyncio

import numpy as np
import pytest

from ceph_tpu.common.tracer import Tracer
from ceph_tpu.ec.registry import factory
from ceph_tpu.ops import gf
from ceph_tpu.osd.encode_service import EncodeService
from tests.test_trace_layers import spans_named, traced_config

PROFILES = {
    "isa83-cauchy": ("isa", {"k": "8", "m": "3", "technique": "cauchy"}),
    "tpu42": ("tpu", {"k": "4", "m": "2"}),
    # m=1: the parity row is a plain XOR
    "isa41-xor": ("isa", {"k": "4", "m": "1", "technique": "cauchy"}),
}

#: logical chunks lost per case; () is the encode plan
LOST = {
    "isa83-cauchy": [(), (3,), (0, 9), (1, 6, 10)],
    "tpu42": [(), (5,), (0, 2)],
    "isa41-xor": [(), (2,)],
}

CASES = [(name, lost) for name in PROFILES for lost in LOST[name]]


def _batch(codec, sizes, seed):
    """Objects of `sizes` bytes, and the k + m chunks of each from the
    GF(2^8) oracle (logical order)."""
    rng = np.random.default_rng(seed)
    k = codec.k
    objs, full = [], []
    for size in sizes:
        data = rng.integers(0, 256, size, np.uint8).tobytes()
        bs = codec.get_chunk_size(size)
        padded = np.zeros(k * bs, dtype=np.uint8)
        padded[:size] = np.frombuffer(data, dtype=np.uint8)
        objs.append(data)
        full.append(gf.gf_matmul(codec._gen, padded.reshape(k, bs)))
    return objs, full


@pytest.mark.parametrize(
    "name,lost", CASES,
    ids=[f"{n}-{'encode' if not lo else 'lost' + '-'.join(map(str, lo))}"
         for n, lo in CASES])
def test_rs_plane_batches_match_byte_api_and_oracle(name, lost):
    plugin, profile = PROFILES[name]
    codec = factory(plugin, dict(profile))
    k, n = codec.k, codec.get_chunk_count()
    if lost:
        present, targets = [i for i in range(n) if i not in lost], lost
    else:
        present, targets = range(k), range(k, n)
    plan = codec.plane_plan(present, targets)
    assert plan.inputs == tuple(present)[:k]
    assert plan.targets == tuple(targets)
    assert (plan.path, plan.groups) == ("numpy", 1)
    assert codec.plane_plan(present, targets) is plan  # memoized
    # one chunk-size mix, and one whose two objects fill 256 words exactly
    for sizes, exact in (([100, 3 * 4096 + 7, 20000, k * 64], False),
                         ([k * 512, k * 512], True)):
        objs, full = _batch(codec, sizes, seed=len(sizes) + k)
        words, widths = codec.pack_planes(
            [[f[i].tobytes() for i in plan.inputs] for f in full])
        assert words.shape == (k, 1, codec.plane_bucket(sum(widths)))
        assert (words.shape[-1] == sum(widths)) == exact
        out = codec.planes(plan, words)
        assert isinstance(out, np.ndarray)
        got = codec.unpack_planes(out, widths)
        for data, f, rows in zip(objs, full, got):
            assert np.array_equal(rows, f[list(targets)])
            if not lost:
                want = codec.encode(range(n), data)
                for t, row in zip(targets, rows):
                    assert row.tobytes() == want[codec.chunk_index(t)]
            else:
                have = {codec.chunk_index(i): f[i].tobytes()
                        for i in present}
                want = codec.decode(
                    {codec.chunk_index(t) for t in targets}, have)
                for t, row in zip(targets, rows):
                    assert row.tobytes() == want[codec.chunk_index(t)]


def test_numpy_launch_pads_to_the_bucket():
    """Off the chip a batch pads to the same power-of-two bucket (in
    words) as on it, so the launch shapes and their widths agree."""
    codec = factory("tpu", {"k": "4", "m": "2"})
    tr = Tracer("osd.3", config=traced_config())
    svc = EncodeService(window=0.001, tracer=tr)
    data = bytes(range(256)) * 20  # 5120 B: 1280 B chunks, 320 words

    async def main():
        root = tr.start("op")
        tok = tr.use(root)
        try:
            return await svc.encode(codec, data)
        finally:
            tr.release(tok)
            root.finish()

    assert asyncio.run(main()) == codec.encode(range(6), data)
    (b,) = spans_named(tr, "encode_batch")
    tags = b["tags"]
    assert (tags["path"], tags["width"], tags["planes"]) == ("numpy", 512, 1)
    assert tags["object_bytes"] == 4 * 1280
    assert tags["launch_bytes"] == 4 * 4 * 512
    assert svc._seen_shapes == {("encode", "numpy", 4, 6, 512)}
