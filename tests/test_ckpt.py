"""Checkpoint store unit tier: deterministic layout (manifest, chunk
table, EC-stripe alignment, striper naming), pytree path round-trips,
the sharding byte-run math restore's partial reads are built on, the
chunk content fingerprints + incremental diff the dedup fast path keys
on, and the gc retention selector. Everything here is pure — no
cluster, no IO, no sleeps."""

import numpy as np
import pytest

from ceph_tpu.ckpt import layout
from ceph_tpu.ckpt.gc import select_retained
from ceph_tpu.common.crc import ceph_crc32c
from ceph_tpu.parallel.sharding import device_slices, slice_byte_runs
from ceph_tpu.rados.striper import object_name


def _tree():
    rng = np.random.default_rng(7)
    return {
        "params": {
            "w": rng.standard_normal((8, 16)).astype(np.float32),
            "b": rng.standard_normal((16,)).astype(np.float32),
        },
        "opt": [
            rng.integers(0, 100, (4, 4), dtype=np.int32),
            np.float64(0.125),
        ],
        "step": np.int64(42),
    }


# -- naming + alignment -------------------------------------------------------


def test_chunk_objects_use_striper_naming():
    assert layout.chunk_object_name("ck", "abcd", 0) == "ck@abcd." + "0" * 16
    assert (
        layout.chunk_object_name("ck", "abcd", 26)
        == object_name("ck@abcd", 26)
        == "ck@abcd.000000000000001a"
    )
    assert layout.manifest_object("ck", "abcd") == "ck@abcd.manifest"
    assert layout.head_object("ck") == "ck.ckpt-head"


def test_pool_alignment_ec_full_stripe_vs_replicated():
    from ceph_tpu.crush.types import CrushMap
    from ceph_tpu.osd import OSDMap, PgPool
    from ceph_tpu.osd.types import TYPE_ERASURE, TYPE_REPLICATED

    m = OSDMap(crush=CrushMap())
    m.pools[1] = PgPool(pg_num=8, size=3, type=TYPE_REPLICATED, crush_rule=1)
    m.pools[2] = PgPool(pg_num=8, size=4, type=TYPE_ERASURE, crush_rule=0)
    m.pools[2].erasure_code_profile = "k2m2"
    m.erasure_code_profiles["k2m2"] = {"plugin": "tpu", "k": "2", "m": "2"}
    assert layout.pool_alignment(m, 1) == layout.MIN_ALIGN
    # full EC stripe: k * stripe_unit (default 64KiB)
    assert layout.pool_alignment(m, 2) == 2 * (1 << 16)
    # explicit stripe_unit in the profile is honored
    m.erasure_code_profiles["k2m2"]["stripe_unit"] = 8192
    assert layout.pool_alignment(m, 2) == 2 * 8192


def test_chunk_bytes_rounds_up_to_alignment():
    assert layout.chunk_bytes(1 << 20, 4096) == 1 << 20
    assert layout.chunk_bytes((1 << 20) + 1, 4096) == (1 << 20) + 4096
    assert layout.chunk_bytes(1, 131072) == 131072


# -- manifest determinism -----------------------------------------------------


def test_manifest_is_deterministic_and_chunked_exactly():
    recs = layout.flatten_tree(_tree())
    m1 = layout.build_manifest("ck", "sid1", recs, chunk_size=256)
    m2 = layout.build_manifest(
        "ck", "sid1", layout.flatten_tree(_tree()), chunk_size=256
    )
    assert layout.encode_manifest(m1) == layout.encode_manifest(m2)

    # array offsets are contiguous in flatten order
    off = 0
    for a in m1["arrays"]:
        assert a["offset"] == off
        assert a["nbytes"] == int(
            np.dtype(a["dtype"]).itemsize * np.prod(a["shape"], dtype=np.int64)
        )
        off += a["nbytes"]
    assert off == m1["stream_bytes"]

    # chunk table covers the stream exactly; only the tail is short
    chunks = m1["chunks"]
    assert [c["offset"] for c in chunks] == [
        i * 256 for i in range(len(chunks))
    ]
    assert all(c["length"] == 256 for c in chunks[:-1])
    assert sum(c["length"] for c in chunks) == m1["stream_bytes"]
    assert [c["object"] for c in chunks] == [
        layout.chunk_object_name("ck", "sid1", i) for i in range(len(chunks))
    ]

    # a different save_id renames every object but changes no geometry
    m3 = layout.build_manifest("ck", "sid2", recs, chunk_size=256)
    assert [c["offset"] for c in m3["chunks"]] == [
        c["offset"] for c in chunks
    ]
    assert all("sid2" in c["object"] for c in m3["chunks"])


def test_manifest_decode_rejects_unknown_format():
    recs = layout.flatten_tree({"a": np.zeros(3, np.uint8)})
    m = layout.build_manifest("x", "s", recs, chunk_size=4096)
    raw = layout.encode_manifest(m)
    assert layout.decode_manifest(raw)["save_id"] == "s"
    with pytest.raises(ValueError):
        layout.decode_manifest(raw.replace(b'"format": 1', b'"format": 9'))


def test_flatten_unflatten_round_trip():
    tree = _tree()
    recs = layout.flatten_tree(tree)
    rebuilt = layout.unflatten([(r["path"], r["leaf"]) for r in recs])
    assert set(rebuilt) == {"params", "opt", "step"}
    assert np.array_equal(rebuilt["params"]["w"], tree["params"]["w"])
    assert np.array_equal(rebuilt["opt"][0], tree["opt"][0])
    assert rebuilt["opt"][1] == tree["opt"][1]
    assert rebuilt["step"] == tree["step"]
    # single-leaf tree round-trips to the bare leaf
    solo = layout.flatten_tree(np.arange(5))
    assert np.array_equal(
        layout.unflatten([(solo[0]["path"], solo[0]["leaf"])]), np.arange(5)
    )


@pytest.mark.parametrize(
    "dtype", ["float32", "int64", "uint8", "bfloat16", "float8_e4m3fn"]
)
def test_manifest_dtype_spelling_round_trips(dtype):
    """bfloat16 and the float8 types are ml_dtypes extensions whose numpy
    string is an opaque void ('<V2'): the manifest must name them, or a
    restore hands back raw bytes that JAX refuses."""
    import jax.numpy as jnp

    arr = np.arange(12, dtype=np.float32).astype(jnp.dtype(dtype))
    (rec,) = layout.flatten_tree({"w": arr})
    spelled = rec["dtype"]
    assert "V" not in spelled
    back = np.frombuffer(arr.tobytes(), dtype=layout.parse_dtype(spelled))
    assert back.dtype == arr.dtype
    assert np.array_equal(back.view(np.uint8), arr.view(np.uint8))


# -- chunk fingerprints + incremental diff ------------------------------------


def test_chunk_fingerprint_composition_and_determinism():
    payload = b"the same bytes" * 100
    fp = layout.chunk_fingerprint(payload)
    assert fp == layout.chunk_fingerprint(bytes(payload))
    assert len(fp) == 24 and int(fp, 16) >= 0
    # the tail 8 hex chars ARE the put's crc32c (computed once, reused)
    assert int(fp[16:], 16) == ceph_crc32c(0xFFFFFFFF, payload)
    # a single flipped byte moves both hash families
    other = layout.chunk_fingerprint(payload[:-1] + b"X")
    assert other[:16] != fp[:16] and other[16:] != fp[16:]


def _manifests_for_diff(chunk=256):
    rng = np.random.default_rng(11)
    base = rng.integers(0, 256, 4 * chunk, np.uint8)
    changed = base.copy()
    changed[2 * chunk:3 * chunk] ^= 1  # exactly chunk index 2 differs
    prev = layout.build_manifest(
        "ck", "old", layout.flatten_tree({"w": base}), chunk_size=chunk
    )
    cur = layout.build_manifest(
        "ck", "new", layout.flatten_tree({"w": changed}), chunk_size=chunk
    )
    for m, arr in ((prev, base), (cur, changed)):
        raw = arr.tobytes()
        for c in m["chunks"]:
            payload = raw[c["offset"]:c["offset"] + c["length"]]
            c["hash"] = layout.chunk_fingerprint(payload)
            c["crc"] = int(c["hash"][16:], 16)
            c["stored"] = c["length"]
    return prev, cur


def test_diff_chunks_marks_only_unchanged_and_retargets_objects():
    prev, cur = _manifests_for_diff()
    assert layout.diff_chunks(cur, prev) == 3
    for i, c in enumerate(cur["chunks"]):
        if i == 2:
            assert not c.get("reused")
            assert "new" in c["object"]
        else:
            # reused entries point INTO the previous save, fields ride
            assert c["reused"]
            assert c["object"] == prev["chunks"][i]["object"]
            assert c["crc"] == prev["chunks"][i]["crc"]
    stats = layout.manifest_dedup(cur)
    assert stats["chunks"] == 4
    assert stats["chunks_owned"] == 1
    assert stats["chunks_referenced"] == 3
    assert stats["dedup_ratio"] == 0.75
    # no parent -> nothing reused; hashless parent chunks never match
    _, fresh = _manifests_for_diff()
    assert layout.diff_chunks(fresh, None) == 0
    stale = {"chunks": [dict(c, hash=None) for c in prev["chunks"]]}
    assert layout.diff_chunks(fresh, stale) == 0


def test_diff_chunks_is_transitive_through_reused_entries():
    """A reused entry in the parent already names the ORIGINAL owner,
    so a grandchild referencing it lands on the oldest save's object —
    gc reachability then only has one level to chase."""
    prev, cur = _manifests_for_diff()
    layout.diff_chunks(cur, prev)
    grand = {
        "chunks": [dict(c, reused=False) for c in cur["chunks"]],
    }
    # rebuild a third manifest with identical content to `cur`
    third = {"chunks": [
        dict(c, object=c["object"].replace("new", "v3"), reused=False)
        for c in grand["chunks"]
    ]}
    assert layout.diff_chunks(third, cur) == 4  # all content matches
    for i, c in enumerate(third["chunks"]):
        if i == 2:
            assert c["object"] == cur["chunks"][2]["object"]  # owner: new
        else:
            assert c["object"] == prev["chunks"][i]["object"]  # owner: old


# -- gc retention selection ---------------------------------------------------


def test_select_retained_keep_last_and_every_nth():
    hist = [f"s{i}" for i in range(10)]
    assert select_retained(hist, keep_last=1) == ["s9"]
    assert select_retained(hist, keep_last=3) == ["s7", "s8", "s9"]
    # every 3rd from the first commit, plus the newest window
    assert select_retained(hist, keep_last=2, keep_every_nth=3) == [
        "s0", "s3", "s6", "s8", "s9"
    ]
    # HEAD is always retained, whatever the knobs say
    assert select_retained(hist, keep_last=0) == ["s9"]
    assert select_retained([], keep_last=5) == []
    # order is commit order (oldest first), stable under both policies
    assert select_retained(hist, keep_last=10, keep_every_nth=2) == hist


# -- shard byte-run math ------------------------------------------------------


def test_slice_byte_runs_row_block_is_one_run():
    # rows [2,4) of an (8, 4) float32 array: one contiguous run
    idx = (slice(2, 4), slice(None))
    assert slice_byte_runs((8, 4), 4, idx) == [(2 * 16, 2 * 16)]
    # the whole array coalesces to a single run too
    assert slice_byte_runs((8, 4), 4, (slice(None), slice(None))) == [
        (0, 128)
    ]


def test_slice_byte_runs_column_block_strides():
    # columns [0,2) of (4, 4) uint8: one 2-byte run per row, stride 4
    runs = slice_byte_runs((4, 4), 1, (slice(None), slice(0, 2)))
    assert runs == [(r * 4, 2) for r in range(4)]
    # adjacent rows merge when the inner slice spans the full row
    runs = slice_byte_runs((4, 4), 1, (slice(1, 3), slice(None)))
    assert runs == [(4, 8)]


def test_slice_byte_runs_cover_shard_exactly():
    rng = np.random.default_rng(3)
    arr = rng.integers(0, 256, (6, 8, 10), dtype=np.uint8)
    stream = arr.tobytes()
    for idx in [
        (slice(0, 3), slice(None), slice(None)),
        (slice(2, 4), slice(0, 4), slice(None)),
        (slice(5, 6), slice(4, 8), slice(5, 10)),
    ]:
        runs = slice_byte_runs(arr.shape, 1, idx)
        got = b"".join(stream[o:o + n] for o, n in runs)
        assert got == arr[idx].tobytes(), idx
        # runs are sorted, non-overlapping, non-adjacent (max coalescing)
        for (o1, n1), (o2, _) in zip(runs, runs[1:]):
            assert o1 + n1 < o2


def test_slice_byte_runs_rejects_strided_shards():
    with pytest.raises(ValueError):
        slice_byte_runs((8,), 1, (slice(0, 8, 2),))


def test_device_slices_respects_mesh_and_degrades_missing_axes():
    import jax
    from jax.sharding import Mesh, PartitionSpec as P

    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs.reshape(4, 2), ("stripe", "model"))
    idx = device_slices((8, 6), P("stripe", None), mesh)
    # 4 unique row slabs, each replicated across the 2 model devices
    slabs = {
        tuple(sl.indices(d) for sl, d in zip(i, (8, 6)))
        for i in idx.values()
    }
    assert len(idx) == 8 and len(slabs) == 4
    # spec axes absent from the mesh degrade to replication
    idx2 = device_slices((8, 6), P("data", None), mesh)
    assert all(
        i == (slice(0, 8), slice(0, 6))
        or tuple(sl.indices(d) for sl, d in zip(i, (8, 6)))
        == ((0, 8, 1), (0, 6, 1))
        for i in idx2.values()
    )
