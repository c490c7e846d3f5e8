"""Checkpoint operator CLI (the rados/orbax-tool role for ceph_tpu.ckpt).

    python tools/ckpt_tool.py --mon-host 127.0.0.1:6789 --pool 2 <cmd>

Commands:

    save <name> --npz file.npz        save the arrays of an .npz as one
                                      checkpoint (keys become the pytree)
    restore <name> [--npz out.npz]    restore HEAD (or --save-id) and
                                      optionally write it back to .npz
    ls <name>                         committed HEAD + every save present
                                      (aborted saves show committed=false;
                                      per-save dedup ratio and owned-vs-
                                      referenced chunk counts ride along)
    verify <name> [--save-id ID]      fetch + crc-check every chunk
    gc <name> [--keep-last N]         retention + reachability collection
              [--keep-every-nth N]    (chunks any retained manifest
                                      references stay live)
    bench [--mb N] [--arrays K]       save/restore throughput, one JSON
          [--async] [--incremental]   line (GB/s both directions); --async
                                      adds blocking-vs-wall for a
                                      backgrounded second save,
                                      --incremental adds the second-save
                                      dedup ratio

Output is JSON per command, like tools/ceph.py."""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


async def _store(args):
    from ceph_tpu.common.config import Config
    from ceph_tpu.ckpt import CkptStore
    from ceph_tpu.mon import MonMap
    from ceph_tpu.rados.client import Rados

    addrs = []
    for hostport in args.mon_host.split(","):
        host, _, port = hostport.rpartition(":")
        addrs.append((host or "127.0.0.1", int(port)))
    rados = Rados(args.name_id, MonMap(addrs=addrs), config=Config())
    await rados.connect()
    return rados, CkptStore(rados.io_ctx(args.pool), args.ckpt_name)


def _tree_from_npz(path: str) -> dict:
    import numpy as np

    with np.load(path) as npz:
        return {k: np.asarray(npz[k]) for k in npz.files}


def _tree_to_npz(path: str, tree) -> None:
    import numpy as np

    import jax

    flat = {}
    for p, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(e, "key", getattr(e, "idx", e)))
                       for e in p) or "value"
        flat[key] = np.asarray(leaf)
    np.savez(path, **flat)


async def _amain(args) -> int:
    if args.command == "bench":
        result = await _bench(args)
        print(json.dumps(result, sort_keys=True))
        return 0
    rados, store = await _store(args)
    try:
        if args.command == "save":
            tree = _tree_from_npz(args.npz)
            save_id = await store.save(tree)
            result = {"save_id": save_id, "perf": store.perf_dump()}
        elif args.command == "restore":
            tree = await store.restore(save_id=args.save_id)
            if args.npz:
                _tree_to_npz(args.npz, tree)
            result = {
                "restored": sorted(
                    str(k) for k in (tree if isinstance(tree, dict)
                                     else {"value": tree})
                ),
                "perf": store.perf_dump(),
            }
        elif args.command == "ls":
            result = await store.ls()
        elif args.command == "verify":
            result = await store.verify(args.save_id)
        elif args.command == "gc":
            result = await store.gc(
                keep_last=args.keep_last,
                keep_every_nth=args.keep_every_nth,
            )
        else:
            raise SystemExit(f"unknown command {args.command!r}")
        print(json.dumps(result, indent=2, sort_keys=True))
        return 0
    finally:
        await rados.shutdown()


async def _bench(args) -> dict:
    """Save/restore GB/s against an in-process cluster (no external
    daemons needed): the `bench` subcommand."""
    import numpy as np

    from tests.test_cluster_live import Cluster, EC_POOL, REP_POOL
    from ceph_tpu.ckpt import CkptStore
    from ceph_tpu.rados.client import Rados

    pool = EC_POOL if args.pool_kind == "ec" else REP_POOL
    cluster = Cluster()
    await cluster.start()
    rados = Rados("client.ckptbench", cluster.monmap, config=cluster.cfg)
    await rados.connect()
    await cluster.create_pools(rados)
    try:
        rng = np.random.default_rng(0)
        per = (args.mb * (1 << 20)) // max(args.arrays, 1)
        tree = {
            f"w{i}": rng.integers(0, 256, per, np.uint8)
            for i in range(args.arrays)
        }
        store = CkptStore(rados.io_ctx(pool), "bench-ckpt")
        total = args.arrays * per
        t0 = time.perf_counter()
        await store.save(tree)
        t_save = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = await store.restore()
        t_restore = time.perf_counter() - t0
        assert all(
            np.array_equal(back[k], tree[k]) for k in tree
        ), "restore mismatch"
        result = {
            "bench": "ckpt",
            "pool": args.pool_kind,
            "bytes": total,
            "save_s": round(t_save, 6),
            "restore_s": round(t_restore, 6),
            "save_gbps": round(total / t_save / 1e9, 4),
            "restore_gbps": round(total / t_restore / 1e9, 4),
            "chunks": store.perf.dump()["save_chunks"],
        }

        def mutate():
            """Touch ONE of the K arrays: the unchanged-majority
            second save the async/incremental numbers are defined on."""
            tree["w0"] = rng.integers(0, 256, per, np.uint8)

        if args.bench_incremental or args.bench_async:
            # second save, synchronous: the blocking-time baseline AND
            # the dedup measurement (only changed chunks upload)
            before = dict(store.perf.dump())
            mutate()
            t0 = time.perf_counter()
            sid = await store.save(tree)
            t_second = time.perf_counter() - t0
            after = store.perf.dump()
            reused = after["save_chunks_reused"] - before["save_chunks_reused"]
            uploaded = after["save_chunks"] - before["save_chunks"]
            result.update({
                "second_save_s": round(t_second, 6),
                "chunks_reused": reused,
                "chunks_uploaded": uploaded,
                "dedup_ratio": round(
                    reused / max(reused + uploaded, 1), 4
                ),
            })
            back = await store.restore(save_id=sid)
            assert all(
                np.array_equal(back[k], tree[k]) for k in tree
            ), "incremental restore mismatch"
        if args.bench_async:
            # third save, backgrounded: blocking time (the train-
            # visible stall) vs the persist wall time
            mutate()
            t0 = time.perf_counter()
            ps = await store.save_async(tree)
            block_s = time.perf_counter() - t0
            await ps.wait()
            result.update({
                "block_s": round(block_s, 6),
                "wall_s": round(ps.wall_s, 6),
                "blocking_speedup": round(
                    result.get("second_save_s", t_save) / max(block_s, 1e-9), 2
                ),
            })
            back = await store.restore()
            assert all(
                np.array_equal(back[k], tree[k]) for k in tree
            ), "async restore mismatch"
        return result
    finally:
        await rados.shutdown()
        await cluster.stop()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="ckpt_tool")
    ap.add_argument("--mon-host", default="127.0.0.1:6789")
    ap.add_argument("--pool", type=int, default=1)
    ap.add_argument("--name", dest="name_id", default="client.ckpt")
    ap.add_argument("--npz", default="")
    ap.add_argument("--save-id", default=None)
    ap.add_argument("--mb", type=int, default=16)
    ap.add_argument("--arrays", type=int, default=4)
    ap.add_argument("--pool-kind", choices=("rep", "ec"), default="ec")
    ap.add_argument("--keep-last", type=int, default=None)
    ap.add_argument("--keep-every-nth", type=int, default=None)
    ap.add_argument("--async", dest="bench_async", action="store_true",
                    help="bench: blocking-vs-wall of a save_async "
                    "second save")
    ap.add_argument("--incremental", dest="bench_incremental",
                    action="store_true",
                    help="bench: dedup ratio of an unchanged-majority "
                    "second save")
    ap.add_argument("command",
                    choices=("save", "restore", "ls", "verify", "gc",
                             "bench"))
    ap.add_argument("ckpt_name", nargs="?", default="ckpt")
    args = ap.parse_args(argv)
    return asyncio.run(_amain(args))


if __name__ == "__main__":
    sys.exit(main())
