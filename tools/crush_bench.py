"""crush_bench — the BASELINE CRUSH benchmark, reproducibly.

Measures BASELINE.md config 5 ("crushtool --test: straw2 mapping of 1M PGs
over a 10k-OSD map") on both implementations:

  * the reference C mapper, single thread, via the test oracle shim's
    `benchrun` command (only when /root/reference and gcc are available);
  * this framework's vectorized JAX mapper on the default device.

Prints one JSON line per measurement, plus the ratio. The JAX output is
validated bit-exact against the C oracle on a prefix before timing.

    python tools/crush_bench.py [--pgs 1000000] [--osds 10000] [--replicas 3]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def build_map(n_osds: int, osds_per_host: int = 50):
    from ceph_tpu.crush import builder as cb
    from ceph_tpu.crush.types import BucketAlg, CrushMap, Tunables

    cmap = CrushMap(tunables=Tunables.jewel())
    host_ids, host_w = [], []
    osd = 0
    n_hosts = n_osds // osds_per_host
    for h in range(n_hosts):
        items = list(range(osd, osd + osds_per_host))
        osd += osds_per_host
        b = cb.make_bucket(
            cmap, -(h + 2), BucketAlg.STRAW2, 1, items, [0x10000] * osds_per_host
        )
        host_ids.append(b.id)
        host_w.append(b.weight)
    cb.make_bucket(cmap, -1, BucketAlg.STRAW2, 10, host_ids, host_w)
    cb.make_simple_rule(cmap, 0, -1, 1, "firstn", 0)
    return cmap


def bench_c(cmap, n_pgs: int, replicas: int, weight) -> float | None:
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests"))
    try:
        from crush_oracle import build_shim, map_to_protocol
    except ImportError:
        return None
    shim = build_shim()
    if shim is None:
        return None
    wtxt = " ".join(str(w) for w in weight)
    text = (
        map_to_protocol(cmap)
        + f"\nbenchrun 0 0 {n_pgs} {replicas} {len(weight)} {wtxt}\n"
    )
    t0 = time.perf_counter()
    proc = subprocess.run(
        [shim], input=text, capture_output=True, text=True, check=True
    )
    wall = time.perf_counter() - t0
    # prefer the shim's self-timed mapping loop (excludes spawn + map parse);
    # an elapsed that rounds to 0 (e.g. --pgs 0) falls back to wall clock
    for line in proc.stdout.splitlines():
        if line.startswith("elapsed "):
            parsed = float(line.split()[1])
            if parsed > 0:
                return parsed
    return wall


def bench_c_mt(cmap, n_pgs: int, replicas: int, weight,
               threads: int | None = None) -> tuple[float, int] | None:
    """The honest CPU comparator: the reference's thread-pool mapping
    (ParallelPGMapper, src/osd/OSDMapMapping.h:18) — every hardware
    thread running the same crush_do_rule loop over a shard of x."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tests"))
    try:
        from crush_oracle import build_shim, map_to_protocol
    except ImportError:
        return None
    shim = build_shim()
    if shim is None:
        return None
    threads = threads or (os.cpu_count() or 1)
    wtxt = " ".join(str(w) for w in weight)
    text = (
        map_to_protocol(cmap)
        + f"\nbenchrunmt {threads} 0 0 {n_pgs} {replicas} "
        + f"{len(weight)} {wtxt}\n"
    )
    proc = subprocess.run(
        [shim], input=text, capture_output=True, text=True, check=True
    )
    for line in proc.stdout.splitlines():
        if line.startswith("elapsed "):
            parsed = float(line.split()[1])
            if parsed > 0:
                return parsed, threads
    return None


def validate(cmap, compiled, jax_out, replicas, weight, n_check: int):
    from crush_oracle import build_shim, oracle_do_rule

    from ceph_tpu.crush.types import CRUSH_ITEM_NONE

    if build_shim() is None:
        return None
    want = oracle_do_rule(cmap, 0, range(n_check), weight, replicas)
    want_arr = np.full((n_check, jax_out.shape[1]), -1, dtype=np.int64)
    for i, row in enumerate(want):
        want_arr[i, : len(row)] = row
    got = np.where(jax_out[:n_check] == CRUSH_ITEM_NONE, -1, jax_out[:n_check])
    bad = np.nonzero((got != want_arr).any(axis=1))[0]
    if bad.size:
        x = int(bad[0])
        raise SystemExit(
            f"MISMATCH vs reference C at x={x}: "
            f"got {got[x].tolist()} want {want_arr[x].tolist()}"
        )
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--pgs", type=int, default=1_000_000)
    ap.add_argument("--osds", type=int, default=10_000)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--skip-c", action="store_true")
    ap.add_argument("--repeats", type=int, default=3,
                    help="TPU timing repeats (chip is shared; best-of wins)")
    ap.add_argument("--validate", type=int, default=-1,
                    help="PGs to check bit-exact vs the C oracle "
                    "(-1 = all of --pgs)")
    args = ap.parse_args(argv)

    from ceph_tpu.crush import jax_mapper as jm

    cmap = build_map(args.osds)
    weight = [0x10000] * args.osds
    compiled = jm.compile_map(cmap)
    xs = np.arange(args.pgs)

    jm.map_rule(compiled, 0, xs[: jm.DEFAULT_CHUNK], weight, args.replicas)  # warm the compile cache
    jax_s = float("inf")
    for _ in range(max(args.repeats, 1)):
        t0 = time.perf_counter()
        out = jm.map_rule(compiled, 0, xs, weight, args.replicas)
        jax_s = min(jax_s, time.perf_counter() - t0)
    print(json.dumps({
        "metric": "crush_straw2_mappings_per_s_tpu",
        "value": round(args.pgs / jax_s, 1),
        "unit": "mappings/s",
        "pgs": args.pgs, "osds": args.osds,
    }))

    c_s = None if args.skip_c else bench_c(cmap, args.pgs, args.replicas, weight)
    if c_s is not None:
        print(json.dumps({
            "metric": "crush_straw2_mappings_per_s_reference_c",
            "value": round(args.pgs / c_s, 1),
            "unit": "mappings/s",
        }))
        print(json.dumps({"metric": "crush_vs_reference_c",
                          "value": round(c_s / jax_s, 3), "unit": "x"}))
        mt = bench_c_mt(cmap, args.pgs, args.replicas, weight)
        if mt is not None:
            mt_s, threads = mt
            print(json.dumps({
                "metric": "crush_straw2_mappings_per_s_reference_c_mt",
                "value": round(args.pgs / mt_s, 1),
                "unit": "mappings/s", "threads": threads,
            }))
            print(json.dumps({
                "metric": "crush_vs_reference_c_mt",
                "value": round(mt_s / jax_s, 3), "unit": "x",
            }))
        n_check = args.pgs if args.validate < 0 else min(args.validate, args.pgs)
        checked = validate(cmap, compiled, out, args.replicas, weight, n_check)
        if checked:
            print(json.dumps({"metric": "bit_exact_vs_c",
                              "value": n_check, "unit": "mappings"}))
    return 0


if __name__ == "__main__":
    from ceph_tpu.chip import use_compile_cache

    use_compile_cache()
    sys.exit(main())
