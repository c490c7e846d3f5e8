"""CLAY's layered decode as one device program (ec/clay.py, plane_plan
and planes), batched by the EncodeService, bit for bit against the plain
reference (benchmark/clay_ref.py), which solves the code's defining
equations and follows no decode schedule. On the CPU the program's GF(2^8)
matmuls take the XLA bit-plane path."""

import asyncio
import functools
import itertools

import numpy as np
import pytest

import jax

from benchmark.clay_ref import ClayRef
from ceph_tpu.common.tracer import Tracer
from ceph_tpu.ec import clay
from ceph_tpu.ec.registry import factory
from tests.test_trace_layers import spans_named, traced_config


def make(k, m, d):
    return factory("clay", {"k": str(k), "m": str(m), "d": str(d),
                            "scalar_mds": "isa", "technique": "cauchy"})


@functools.lru_cache(maxsize=None)
def ref(k, m, d) -> ClayRef:
    return ClayRef(k, m, d)


def payload(seed: int, size: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, size, np.uint8).tobytes()


@pytest.mark.parametrize("kmd,size", [
    ((8, 4, 11), 1),
    ((8, 4, 11), 8 * 2048),
    ((8, 4, 11), 3 * 8 * 2048 - 5),
    ((8, 4, 11), 100_000),
    ((4, 2, 5), 3000),
    # nu = 1: a virtual zero node shortens the grid
    ((5, 2, 6), 7000),
], ids=["8411-1B", "8411-16K", "8411-48K", "8411-100K", "425", "526-nu1"])
def test_encode_matches_reference(kmd, size):
    ec, r = make(*kmd), ref(*kmd)
    data = payload(size, size)
    assert r.chunk_size(size) == ec.get_chunk_size(size)
    got = ec.encode(range(ec.get_chunk_count()), data)
    want = r.encode(data)
    assert [got[i] for i in range(len(want))] == [w.tobytes() for w in want]


@pytest.mark.parametrize("n_lost", [1, 2, 3, 4])
def test_every_signature_rebuilds_the_reference_chunks(n_lost):
    """Every erasure signature of n_lost chunks of CLAY(8,4,11), with
    sub-chunks of 32 bytes (chunks of 2 KiB), two objects in each launch:
    the rebuilt chunks are the reference's encoding of the objects."""
    ec, r = make(8, 4, 11), ref(8, 4, 11)
    full = np.stack([r.encode(payload(s, 8 * 2048)) for s in (1, 2)])
    for lost in itertools.combinations(range(12), n_lost):
        present = [i for i in range(12) if i not in lost]
        out = ec.decode_array(present, list(lost), full[:, present])
        assert np.array_equal(out, full[:, list(lost)]), lost


@pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])
def test_mixed_size_batch_of_five_in_one_launch(x64):
    """Five objects of four chunk sizes side by side in one program run,
    encode and a two-chunk rebuild, each object equal to the reference;
    also under jax_enable_x64, as a process serving CRUSH runs."""
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", x64)
    try:
        ec, r = make(8, 4, 11), ref(8, 4, 11)
        sizes = [4096, 16384, 49152 - 300, 16384 + 1, 160_000]
        full = [r.encode(payload(7 + i, s)) for i, s in enumerate(sizes)]
        assert len({f.shape[1] for f in full}) == 4
        plan = ec.plane_plan(range(8), range(8, 12))
        words, widths = ec.pack_planes([list(f[:8]) for f in full])
        assert words.shape == (8, 64, 256)
        got = ec.unpack_planes(ec.planes(plan, words), widths)
        for f, g in zip(full, got):
            assert np.array_equal(g, f[8:])
        lost = [2, 9]
        present = [i for i in range(12) if i not in lost][:8]
        plan = ec.plane_plan(present, lost)
        assert plan.inputs == tuple(present)
        words, widths = ec.pack_planes([list(f[present]) for f in full])
        got = ec.unpack_planes(ec.planes(plan, words), widths)
        for f, g in zip(full, got):
            assert np.array_equal(g, f[lost])
    finally:
        jax.config.update("jax_enable_x64", was)


@pytest.mark.parametrize(
    "lost", [c for n in (1, 2) for c in itertools.combinations(range(6), n)],
    ids=lambda c: "-".join(map(str, c)))
def test_4_2_5_every_signature_against_the_reference_solve(lost):
    """CLAY(4,2,5) exhaustively: the program's rebuild equals both the
    reference's own solve for the signature and the encoded chunks."""
    ec, r = make(4, 2, 5), ref(4, 2, 5)
    full = r.encode(payload(42, 4 * 3 * 256))
    chunks = {i: full[i] for i in range(6) if i not in lost}
    want = r.rebuild(chunks, lost)
    assert np.array_equal(want, full[list(lost)])
    present = sorted(chunks)
    got = ec.decode_array(present, list(lost), full[None, present])[0]
    assert np.array_equal(got, want)


def test_one_program_serves_every_signature():
    """The erasure signature is data and the program returns every lost
    node: rebuilding another chunk, two chunks, or encoding, at the same
    plane width, compiles nothing new."""
    ec, r = make(8, 4, 11), ref(8, 4, 11)
    data = payload(3, 8 * 4096)
    full = r.encode(data)[None]
    sizes = []
    for lost in ([3], [6], [1, 5]):
        present = [i for i in range(12) if i not in lost][:8]
        out = ec.decode_array(present, lost, full[:, present])
        assert np.array_equal(out, full[:, lost])
        sizes.append(clay._layered_planes._cache_size())
    got = ec.encode(range(12), data)
    assert [got[i] for i in range(12)] == [f.tobytes() for f in full[0]]
    sizes.append(clay._layered_planes._cache_size())
    assert len(set(sizes)) == 1
    assert ec.plane_plan(
        [i for i in range(12) if i != 3][:8], [3]).groups == 3
    with pytest.raises(clay.ErasureCodeError):
        ec.plane_plan(range(1, 9), [1])


def test_encode_service_batches_clay_per_signature():
    """Six concurrent CLAY objects of three sizes go out in one encode
    launch, and their degraded reads in one launch per erasure
    signature; every result equals the per-object path's bytes. The
    launch spans carry path clay, the planes and the groups run, and the
    service counts the plans it built and found cached."""
    from ceph_tpu.osd.encode_service import EncodeService

    codec = make(8, 4, 11)
    sizes = [4096, 4096, 40_000, 40_000, 150_000, 150_000]
    payloads = [payload(100 + i, s) for i, s in enumerate(sizes)]
    tr = Tracer("osd.3", config=traced_config())
    svc = EncodeService(window=0.05, tracer=tr)

    async def traced(coro_fn):
        root = tr.start("op")
        tok = tr.use(root)
        try:
            return await coro_fn()
        finally:
            tr.release(tok)
            root.finish()

    async def main():
        encoded = await asyncio.gather(*(
            traced(lambda p=p: svc.encode(codec, p)) for p in payloads))
        assert (svc.launches, svc.objects) == (1, 6)
        for p, got in zip(payloads, encoded):
            assert got == codec.encode(range(12), p)
        # osd.2's and osd.5's data chunks lost: two signatures
        reads = []
        for j, enc in enumerate(encoded):
            lost = 2 if j % 2 else 5
            have = {i: enc[i] for i in range(12) if i != lost}
            present = dict(sorted(have.items())[:8])
            reads.append((lost, present))
        outs = await asyncio.gather(*(
            traced(lambda pr=pr: svc.decode(codec, set(range(8)), pr))
            for _lost, pr in reads))
        assert (svc.launches, svc.objects) == (3, 12)
        for (lost, present), enc, out in zip(reads, encoded, outs):
            assert out == codec.decode(set(range(8)), present)
            assert out[lost] == enc[lost]
        return encoded

    asyncio.run(main())
    batches = spans_named(tr, "encode_batch") + spans_named(
        tr, "decode_batch")
    assert len(batches) == 3
    for b in batches:
        assert b["tags"]["path"] == "clay"
        assert b["tags"]["planes"] == 64
        assert b["tags"]["launch_bytes"] >= b["tags"]["object_bytes"]
    assert sorted(b["tags"]["groups"] for b in batches) == [1, 3, 3]
    assert sorted(b["tags"]["batch"] for b in batches) == [3, 3, 6]
    perf = svc.perf.dump()
    assert perf["plane_plans_built"] == 3
    assert perf["plane_plans_cached"] == 0
