"""The CLAY cell's objects against the plain reference, on the served path.

    python3 -m benchmark.tests.clay_shards [--seed N]

Boots clay8411-degraded-read's cluster, preloads its 128 objects through
the OSDs' EncodeService, and compares:

- every one of the 12 shards of every object, read straight from the
  stores, with clay_ref's encoding of the object;
- for every object of which the mix's down OSD holds a data shard, the
  chunk an OSD's EncodeService rebuilds from the 8 shards a primary's read
  gathers (the first 8 others, as minimum_to_decode picks them) with
  clay_ref's solve for the same signature;
- after the OSD is stopped and marked down, every object read back
  through the client with its payload.

Prints one JSON line of counts and exits non-zero where any differs. It
runs where the cell runs: on the chip, the launches go through the
device program.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "clay8411-degraded-read"


def side_by_side(ref, chunks: list[dict]) -> dict:
    """One chunk per id of many objects' chunks, each object's planes
    laid side by side: the reference's equations hold column by column."""
    S = ref.planes
    return {c: np.concatenate(
        [np.frombuffer(ch[c], np.uint8).reshape(S, -1) for ch in chunks],
        axis=1).reshape(-1) for c in chunks[0]}


def split(ref, row, widths: list[int]) -> list[bytes]:
    out, off = [], 0
    planes = row.reshape(ref.planes, -1)
    for w in widths:
        out.append(planes[:, off: off + w].tobytes())
        off += w
    return out


async def check(seed: int, smaller: dict | None = None) -> dict:
    from benchmark import gf_ref, harness
    from benchmark.clay_ref import ClayRef
    from benchmark.cluster import EC_POOL, LocalCluster
    from benchmark.traffic import Traffic

    cell = harness.resolve(harness.load_spec(), CELL)
    dep, mix = cell.config["deployment"], {**cell.mix, **(smaller or {})}
    prof = dep["profile"]
    ref = ClayRef(int(prof["k"]), int(prof["m"]), int(prof["d"]))
    k, n = ref.k, ref.k + ref.m
    traffic = Traffic(mix, seed)
    # every shape compiled before the cluster boots, as a run does
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.osd.encode_service import EncodeService

    profile = {key: str(v) for key, v in prof.items()}
    await harness.warm_shapes(EncodeService(), factory(
        profile.pop("plugin"), profile), mix, int(dep["chunk_alignment"]))
    cluster = LocalCluster(dep)
    io = await cluster.start()
    counts = {"objects": len(traffic.read_names), "shards_compared": 0,
              "shards_differ": 0, "rebuilt_compared": 0,
              "rebuilt_differ": 0, "reads_differ": 0}
    try:
        async def put(name):
            await io.write_full(name, traffic.read_payload(name))

        await harness.pump(put, traffic.read_names, traffic.in_flight)
        stored = {name: cluster.stored_shards(name)
                  for name in traffic.read_names}
        # the reference's encoding of every object, all in one solve
        data = {}
        for name in traffic.read_names:
            d = gf_ref.data_chunks(traffic.read_payload(name), k,
                                   32 * ref.planes)
            data[name] = {i: d[i].tobytes() for i in range(k)}
        names = traffic.read_names
        widths = [len(data[x][0]) // ref.planes for x in names]
        parity = ref.rebuild(side_by_side(ref, [data[x] for x in names]),
                             range(k, n))
        want = {x: dict(data[x]) for x in names}
        for i in range(ref.m):
            for x, b in zip(names, split(ref, parity[i], widths)):
                want[x][k + i] = b
        for x in names:
            for pos in range(n):
                counts["shards_compared"] += 1
                if stored[x].get(pos) != want[x][pos]:
                    counts["shards_differ"] += 1
        # the served decode of each object that loses a data shard
        victim = int(mix["down_osds"][0])
        osd = next(o for i, o in cluster.osds.items() if i != victim)
        codec = osd.codec(EC_POOL)
        groups: dict = {}
        for x in names:
            _pool, _ps, acting, _p = cluster.rados.objecter._calc_acting(
                EC_POOL, x)
            lost = acting.index(victim) if victim in acting else None
            if lost is None or lost >= k:
                continue
            present = [p for p in range(n) if p != lost][:k]
            groups.setdefault((lost, tuple(present)), []).append(x)
        for (lost, present), xs in groups.items():
            served = await asyncio.gather(*(
                osd.encode_service.decode(
                    codec, set(range(k)), {p: want[x][p] for p in present})
                for x in xs))
            row = ref.rebuild(side_by_side(
                ref, [{p: want[x][p] for p in present} for x in xs]),
                [lost])[0]
            ws = [len(want[x][0]) // ref.planes for x in xs]
            for x, got, expect in zip(xs, served, split(ref, row, ws)):
                counts["rebuilt_compared"] += 1
                if got[lost] != expect or expect != want[x][lost]:
                    counts["rebuilt_differ"] += 1
        await cluster.mark_down(victim)

        async def back(name):
            got = await io.read(name)
            if got != traffic.read_payload(name):
                counts["reads_differ"] += 1

        await harness.pump(back, names, traffic.in_flight)
    finally:
        await cluster.stop()
    return counts


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=2**31 + 11)
    ap.add_argument("--small", action="store_true",
                    help="16 objects of 4-128 KiB, for a run on the CPU")
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_enable_x64", True)
    smaller = ({"sizes": {"min": 4096, "max": 131072, "points": 32},
                "read_set": {"objects": 16}} if args.small else None)
    counts = asyncio.run(check(args.seed, smaller))
    ok = (counts["shards_differ"] == counts["rebuilt_differ"]
          == counts["reads_differ"] == 0 and counts["rebuilt_compared"] > 0)
    print(json.dumps(dict(counts, ok=ok)), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
