#!/usr/bin/env python3
"""chip_smoke — drive the system's main path once on one TPU chip.

One process owns the chip and runs five phases through the entry points a
user calls, at the sizes users run:

  1. device   the chip JAX reports; no CPU fallback
  2. kernel   RS(8,3) encode_words/decode_words on 256 MiB (bench.py's
              launch), checked against the numpy GF(2^8) oracle
  3. served   mons + 12 OSDService daemons + a Rados client over TCP; an
              RS(8,3) plugin=tpu pool takes >= 256 MiB of 4 KiB-4 MiB
              objects, 32 writes in flight; everything is read back, then
              read again degraded after one OSD stops
  4. crush    1,048,576 PGs x 3 replicas over a 10k-OSD straw2 map,
              checked against the scalar mapper
  5. ckpt     a 256 MiB pytree of device arrays saved through CkptStore
              into the EC pool and restored onto the chip

Each phase prints one JSON report line (wall and compile seconds, bytes
moved, the path each kernel took); any failed check exits non-zero. The
last line is {"ok": true, "device": {"platform", "kind", "count"}}.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # phase 3 on the 2x2 mesh, against
                                        # the one-chip kernel in-process

Data comes from --seed. The compile cache lives where
JAX_COMPILATION_CACHE_DIR says, else in <repo>/.jax_cache.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import math
import multiprocessing
import os
import sys
import time
from concurrent.futures import ProcessPoolExecutor

K, M = 8, 3
#: int32 words per row of the kernel phase: 8 rows x 32 MiB = 256 MiB
KERNEL_WORDS = 8 * 1024 * 1024
#: word columns of the kernel phase checked on the host against the oracle
ORACLE_COLUMNS = 1 << 16
N_MONS = 3
N_OSDS = 12
EC_POOL = 1
#: the pool's CRUSH rule: choose indep over type 0, failure domain osd
EC_RULE = 2
PG_NUM = 32
SERVED_BYTES = 256 << 20
MIN_OBJECT, MAX_OBJECT = 4 << 10, 4 << 20
IN_FLIGHT = 32
CRUSH_PGS = 1 << 20
CRUSH_OSDS = 10_000
CRUSH_REPLICAS = 3
CRUSH_CHECKED = 10_000
CKPT_BYTES = 256 << 20
#: libtpu's bounds for a process that owns chip 0 alone; set before JAX
#: starts, so a host with four chips shows this process one
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}


class SmokeFailure(RuntimeError):
    pass


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def report(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache), and that cache's
    hits and misses, from JAX's own monitoring events."""

    SECONDS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.SECONDS:
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def phase(self, name: str, fn, *args):
        """Run one phase and print its report: fn's fields plus wall and
        compile seconds and the cache traffic it caused."""
        t0 = time.perf_counter()
        s0, h0, m0 = self.seconds, self.hits, self.misses
        fields = fn(*args)
        report(
            phase=name,
            wall_s=time.perf_counter() - t0,
            compile_s=self.seconds - s0,
            cache_hits=self.hits - h0,
            cache_misses=self.misses - m0,
            **fields,
        )


class XlaPathSpy:
    """Counts calls into the XLA bit-plane GF path: the codec's off-chip
    fallback and the kernel the mesh path shard_maps. Installed before
    any phase, so a cached trace cannot hide a call."""

    def __init__(self):
        from ceph_tpu.ops import gf_bitplane as bp

        self.calls = 0
        inner = bp.gf_matmul_bitplane

        def counted(*args, **kwargs):
            self.calls += 1
            return inner(*args, **kwargs)

        bp.gf_matmul_bitplane = counted


class CodecTransfers:
    """Host<->device bytes of the served path's EC launches: EncodeService
    hands the codec's planar API host arrays and fetches every result."""

    def __init__(self):
        import numpy as np

        from ceph_tpu.ec.rs import ErasureCodeRs

        self.h2d = self.d2h = 0
        for name in ("encode_words", "decode_words"):
            inner = getattr(ErasureCodeRs, name)

            def counted(codec, *args, _inner=inner):
                out = _inner(codec, *args)
                if isinstance(args[-1], np.ndarray):
                    self.h2d += args[-1].nbytes
                    self.d2h += out.nbytes
                return out

            setattr(ErasureCodeRs, name, counted)

    def since(self, start: tuple[int, int]) -> dict:
        return {"h2d_bytes": self.h2d - start[0],
                "d2h_bytes": self.d2h - start[1]}

    def mark(self) -> tuple[int, int]:
        return self.h2d, self.d2h


def pallas_lowered(fn, *args) -> bool:
    """True when fn lowers to a Mosaic kernel (not the XLA path, not the
    interpreter)."""
    import jax

    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


# -- phase 1 ------------------------------------------------------------------


def phase_device(want: int) -> dict:
    import jax

    devs = jax.devices()
    check(
        devs[0].platform == "tpu",
        f"no TPU: JAX found platform {devs[0].platform!r}",
    )
    check(len(devs) == want, f"want {want} chip(s), JAX sees {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


# -- phase 2 ------------------------------------------------------------------


def phase_kernel(seed: int, words_per_row: int = KERNEL_WORDS,
                 oracle_columns: int = ORACLE_COLUMNS) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ceph_tpu.ec import matrices
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.ops import gf_pallas as gp
    from ceph_tpu.ops.gf import gf_region_matmul

    check(gp.available(), "gf_pallas.available() is false on the chip")
    ec = factory("isa", {"k": str(K), "m": str(M), "technique": "cauchy"})
    present, lost = list(range(3, K + M)), [0, 1, 2]

    def encode(w):
        return ec.encode_words(w)

    def decode(s):
        return ec.decode_words(present, lost, s)

    words = jax.lax.bitcast_convert_type(
        jax.random.bits(jax.random.key(seed), (K, words_per_row), jnp.uint32),
        jnp.int32,
    )
    check(pallas_lowered(encode, words), "encode did not lower to Pallas")
    t0 = time.perf_counter()
    parity = encode(words).block_until_ready()
    first_encode_s = time.perf_counter() - t0
    survivors = jnp.concatenate([words[3:], parity])
    check(pallas_lowered(decode, survivors), "decode did not lower to Pallas")
    rebuilt = decode(survivors).block_until_ready()
    t0 = time.perf_counter()
    encode(words).block_until_ready()
    warm_encode_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    decode(survivors).block_until_ready()
    warm_decode_s = time.perf_counter() - t0

    # every rebuilt column, on the device; a seeded sample on the host
    # against the numpy oracle
    check(bool(jnp.array_equal(rebuilt, words[:3])),
          "rebuilt chunks differ from the lost data")
    cols = np.sort(np.random.default_rng(seed).choice(
        words_per_row, min(oracle_columns, words_per_row), replace=False))
    idx = jnp.asarray(cols)

    def host_bytes(a):
        return np.ascontiguousarray(np.asarray(a[:, idx])).view(np.uint8)

    data_b, parity_b, rebuilt_b = (
        host_bytes(words), host_bytes(parity), host_bytes(rebuilt))
    check(np.array_equal(parity_b, gf_region_matmul(ec._gen[K:], data_b)),
          "parity differs from the numpy oracle")
    dm = matrices.decode_matrix(ec._gen, K, present, lost)
    surv_b = np.concatenate([data_b[3:], parity_b])
    check(np.array_equal(gf_region_matmul(dm, surv_b), rebuilt_b),
          "rebuilt chunks differ from the numpy oracle")
    check(np.array_equal(rebuilt_b, data_b[:3]),
          "sampled rebuilt chunks differ from the data")
    data_bytes = K * words_per_row * 4
    d2h = data_b.nbytes + parity_b.nbytes + rebuilt_b.nbytes
    for a in (words, parity, survivors, rebuilt):
        a.delete()
    return {
        "data_bytes": data_bytes,
        "h2d_bytes": 0,
        "d2h_bytes": d2h,
        "oracle_columns": int(cols.size),
        "first_encode_s": first_encode_s,
        "warm_encode_s": warm_encode_s,
        "warm_decode_s": warm_decode_s,
        "path": {"encode": "pallas", "decode": "pallas"},
    }


# -- the served cluster (phases 3 and 5) ----------------------------------------


class LocalCluster:
    """Mons + OSDService daemons + one Rados client in this process, over
    TCP, with an RS(8,3) plugin=tpu pool whose failure domain is osd."""

    def __init__(self, n_osds: int = N_OSDS, pg_num: int = PG_NUM):
        self.n_osds = n_osds
        self.pg_num = pg_num
        self.mons: list = []
        self.osds: dict = {}
        self.rados = None

    async def start(self):
        from ceph_tpu.common.config import Config
        from ceph_tpu.crush import builder as cb
        from ceph_tpu.crush.types import RuleOp, RuleStep
        from ceph_tpu.mon import MonMap, Monitor
        from ceph_tpu.osd.daemon import OSDService
        from ceph_tpu.rados.client import Rados
        from ceph_tpu.vstart import initial_osdmap

        base = initial_osdmap(self.n_osds)
        # what Ceph's add_simple_rule writes for an erasure pool whose
        # crush-failure-domain is osd (CrushWrapper.cc)
        cb.make_rule(base.crush, EC_RULE, [
            RuleStep(RuleOp.SET_CHOOSELEAF_TRIES, 5),
            RuleStep(RuleOp.SET_CHOOSE_TRIES, 100),
            RuleStep(RuleOp.TAKE, -1),
            RuleStep(RuleOp.CHOOSE_INDEP, 0, 0),
            RuleStep(RuleOp.EMIT),
        ], rule_type=3, max_size=K + M)
        self.cfg = Config()
        self.monmap = MonMap(addrs=[("127.0.0.1", 0)] * N_MONS)
        self.mons = [Monitor(r, self.monmap, base, config=self.cfg)
                     for r in range(N_MONS)]
        for m in self.mons:
            await m.bind()
        for m in self.mons:
            m.go()
        for i in range(self.n_osds):
            osd = OSDService(i, self.monmap, config=self.cfg)
            await osd.start()
            self.osds[i] = osd
        await wait_for(
            lambda: all(not self.leader_map().is_down(i)
                        for i in range(self.n_osds)),
            120, "every OSD to boot")
        self.rados = Rados("client.smoke", self.monmap, config=self.cfg)
        await self.rados.connect()
        await self.rados.mon_command(
            "osd erasure-code-profile set",
            {"name": "rs83",
             "profile": {"plugin": "tpu", "k": str(K), "m": str(M)}},
        )
        await self.rados.mon_command(
            "osd pool create",
            {"pool_id": EC_POOL, "crush_rule": EC_RULE,
             "erasure_code_profile": "rs83", "pg_num": self.pg_num},
        )
        client_map = self.rados.objecter
        await wait_for(
            lambda: EC_POOL in client_map.osdmap.pools and all(
                not client_map.osdmap.is_down(i)
                for i in range(self.n_osds)),
            120, "the client to see the pool and every OSD")
        return self.rados.io_ctx(EC_POOL)

    async def stop(self):
        if self.rados is not None:
            await self.rados.shutdown()
        for osd in self.osds.values():
            if not osd._stopped:
                await osd.stop()
        for m in self.mons:
            await m.stop()

    def leader_map(self):
        leader = next((m for m in self.mons if m.is_leader), None)
        check(leader is not None, "the monitors have no leader")
        return leader.osdmap


async def pump(op, names, lanes: int) -> None:
    """Run op over names with `lanes` calls in flight."""
    it = iter(names)

    async def lane():
        for name in it:
            await op(name)

    await asyncio.gather(*(lane() for _ in range(lanes)))


async def wait_for(pred, timeout: float, what: str) -> None:
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while not pred():
        check(loop.time() < end, f"timed out waiting for {what}")
        await asyncio.sleep(0.05)


def object_sizes(rng, total: int) -> list[int]:
    """Log-uniform object sizes in [MIN_OBJECT, MAX_OBJECT] adding up to
    at least `total` bytes (BASELINE's 4 KiB-4 MiB stripe range)."""
    lo, hi = math.log(MIN_OBJECT), math.log(MAX_OBJECT)
    sizes: list[int] = []
    while sum(sizes) < total:
        sizes.append(int(math.exp(rng.uniform(lo, hi))))
    return sizes


# -- phase 3 ------------------------------------------------------------------


async def served_ec(seed: int, total: int, n_osds: int,
                    lanes: int) -> dict:
    import numpy as np

    rng = np.random.default_rng(seed)
    sizes = object_sizes(rng, total)
    blob = rng.bytes(sum(sizes))
    payloads, off = {}, 0
    for i, size in enumerate(sizes):
        payloads[f"smoke-{i:05d}"] = blob[off: off + size]
        off += size
    del blob
    names = list(payloads)
    cluster = LocalCluster(n_osds)
    try:
        io = await cluster.start()

        async def write(name):
            await io.write_full(name, payloads[name])

        async def read(name):
            got = await io.read(name)
            check(got == payloads[name], f"{name}: read back wrong bytes")

        t0 = time.perf_counter()
        await pump(write, names, lanes)
        write_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        await pump(read, names, lanes)
        read_s = time.perf_counter() - t0

        acting = cluster.leader_map().pool_mappings(EC_POOL)
        victim = int(rng.integers(n_osds))
        check(bool((acting == victim).any()), f"osd.{victim} holds no shards")
        await cluster.osds[victim].stop()
        await cluster.rados.mon_command("osd down", {"osd": victim})
        await wait_for(
            lambda: cluster.rados.objecter.osdmap.is_down(victim), 60,
            f"the client to see osd.{victim} down")
        t0 = time.perf_counter()
        await pump(read, names, lanes)
        degraded_read_s = time.perf_counter() - t0

        services = [o.encode_service for o in cluster.osds.values()]
        shapes = set().union(*(s._seen_shapes for s in services))
        launches = sum(s.launches for s in services)
        objects = sum(s.objects for s in services)
        mesh_launches = sum(s.mesh_launches for s in services)
        check(any(op == "decode" for op, *_ in shapes),
              "the degraded reads decoded nothing")
        check(objects > launches,
              f"no batching: {objects} objects in {launches} launches")
        return {
            "objects": len(names),
            "bytes_written": sum(sizes),
            "bytes_read": 2 * sum(sizes),
            "osds": n_osds,
            "in_flight": lanes,
            "victim": victim,
            "launches": launches,
            "launched_objects": objects,
            "mesh_launches": mesh_launches,
            "paths": sorted({path for _op, path, *_ in shapes}),
            "shapes": len(shapes),
            "write_s": write_s,
            "read_s": read_s,
            "degraded_read_s": degraded_read_s,
        }
    finally:
        await cluster.stop()


def phase_served(seed: int, spy: XlaPathSpy, moved: CodecTransfers,
                 total: int = SERVED_BYTES, n_osds: int = N_OSDS,
                 lanes: int = IN_FLIGHT) -> dict:
    calls, start = spy.calls, moved.mark()
    out = asyncio.run(asyncio.wait_for(
        served_ec(seed, total, n_osds, lanes), 900))
    check(out["paths"] == ["pallas"],
          f"served EC ran {out['paths']}, not only the Pallas kernel")
    check(spy.calls == calls, "the XLA bit-plane path ran")
    return {**out, **moved.since(start)}


# -- phase 3 on four chips -----------------------------------------------------


class MeshCapture:
    """Records every mesh launch of the EncodeService (inputs, outputs and
    the devices the sharded output spans) so the one-chip kernel can
    recompute it afterwards."""

    def __init__(self):
        from ceph_tpu.parallel import sharding

        self.launches: list[tuple] = []
        self.spans: list[int] = []
        enc, dec = sharding.mesh_encode_planar, sharding.mesh_decode_planar
        s_enc, s_dec = sharding.sharded_encode, sharding.sharded_decode

        def mesh_encode(ec, planes, mesh):
            out = enc(ec, planes, mesh)
            self.launches.append((ec, None, None, planes.copy(), out))
            return out

        def mesh_decode(ec, present, targets, planes, mesh):
            out = dec(ec, present, targets, planes, mesh)
            self.launches.append(
                (ec, list(present), list(targets), planes.copy(), out))
            return out

        def spanned(fn):
            def run(*args):
                out = fn(*args)
                self.spans.append(
                    len({s.device for s in out.addressable_shards}))
                return out
            return run

        sharding.mesh_encode_planar = mesh_encode
        sharding.mesh_decode_planar = mesh_decode
        sharding.sharded_encode = spanned(s_enc)
        sharding.sharded_decode = spanned(s_dec)

    def compare_one_chip(self) -> int:
        """Recompute every captured launch with the Pallas kernel on one
        chip; returns the bytes compared."""
        import jax
        import numpy as np

        compared = 0
        chip0 = jax.devices()[0]
        for ec, present, targets, planes, out in self.launches:
            words = jax.device_put(planes.view(np.int32), chip0)
            if present is None:
                want = ec.encode_words(words)
            else:
                want = ec.decode_words(present, targets, words)
            check(want.devices() == {chip0}, "one-chip run left chip 0")
            got = np.asarray(want).view(np.uint8)
            check(np.array_equal(got, out),
                  "mesh output differs from the one-chip kernel")
            compared += out.nbytes
        return compared


def phase_served_four(seed: int, spy: XlaPathSpy, moved: CodecTransfers,
                      total: int = SERVED_BYTES, n_osds: int = N_OSDS,
                      lanes: int = IN_FLIGHT) -> dict:
    import jax

    from ceph_tpu.ops import gf_pallas as gp
    from ceph_tpu.parallel import sharding

    capture = MeshCapture()
    calls, start = spy.calls, moved.mark()
    out = asyncio.run(asyncio.wait_for(
        served_ec(seed, total, n_osds, lanes), 900))
    single = moved.since(start)
    check(out["mesh_launches"] > 0, "no launch went through the mesh")
    check(capture.spans and min(capture.spans) == 4,
          f"mesh outputs span {sorted(set(capture.spans))} devices, not 4")
    check(gp.available(), "gf_pallas.available() is false on the chip")
    compared = capture.compare_one_chip()
    mesh = sharding.ec_mesh(4)
    probe = sharding.shard_batch(
        jax.numpy.zeros((2, K, 512), jax.numpy.uint8), mesh)
    from ceph_tpu.ec.registry import factory

    ec = factory("tpu", {"k": str(K), "m": str(M)})
    mesh_pallas = "tpu_custom_call" in sharding._sharded_matmul(mesh).lower(
        ec._encode_bits, probe).as_text()
    out.update({
        "mesh_shape": dict(mesh.shape),
        "mesh_output_devices": sorted(set(capture.spans)),
        "mesh_compared_launches": len(capture.launches),
        "mesh_compared_bytes": compared,
        "mesh_kernel": "pallas" if mesh_pallas else "xla-bitplane",
        "xla_bitplane_calls": spy.calls - calls,
        "h2d_bytes": single["h2d_bytes"] + sum(
            c[3].nbytes for c in capture.launches),
        "d2h_bytes": single["d2h_bytes"] + sum(
            c[4].nbytes for c in capture.launches),
    })
    return out


# -- phase 4 ------------------------------------------------------------------


def scalar_rows(job) -> list[list[int]]:
    """The scalar mapper's rows for x in [lo, hi): a worker process of
    phase 4, kept off the chip."""
    n_osds, replicas, lo, hi = job
    os.environ["JAX_PLATFORMS"] = "cpu"
    from ceph_tpu.crush import mapper
    from tools.crush_bench import build_map

    cmap = build_map(n_osds)
    weight = [0x10000] * n_osds
    return [mapper.do_rule(cmap, 0, x, weight, replicas)
            for x in range(lo, hi)]


def phase_crush(pgs: int = CRUSH_PGS, n_osds: int = CRUSH_OSDS,
                replicas: int = CRUSH_REPLICAS,
                checked: int = CRUSH_CHECKED) -> dict:
    import numpy as np

    from ceph_tpu.crush import jax_mapper as jm
    from tools.crush_bench import build_map

    cmap = build_map(n_osds)
    weight = [0x10000] * n_osds
    compiled = jm.compile_map(cmap)
    xs = np.arange(pgs)
    t0 = time.perf_counter()
    out = jm.map_rule(compiled, 0, xs, weight, replicas)
    first_map_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    again = jm.map_rule(compiled, 0, xs, weight, replicas)
    warm_map_s = time.perf_counter() - t0
    check(out.shape == (pgs, replicas), f"mapping shape {out.shape}")
    check(np.array_equal(out, again), "two runs of the mapper disagree")
    check(bool(((out >= 0) & (out < n_osds)).all()),
          "a PG mapped to no OSD or outside the map")
    check(all(len(set(row)) == replicas for row in out[:checked].tolist()),
          "a PG mapped twice to one OSD")
    t0 = time.perf_counter()
    workers = max(1, min(8, os.cpu_count() or 1))
    bounds = [checked * i // workers for i in range(workers + 1)]
    ctx = multiprocessing.get_context("spawn")
    with ProcessPoolExecutor(workers, mp_context=ctx) as pool:
        rows = pool.map(scalar_rows, [
            (n_osds, replicas, lo, hi)
            for lo, hi in zip(bounds, bounds[1:])])
        want = [row for part in rows for row in part]
    for x, row in enumerate(want):
        check(out[x].tolist() == row,
              f"x={x}: device {out[x].tolist()} != scalar {row}")
    scalar_s = time.perf_counter() - t0
    return {
        "pgs": pgs,
        "osds": n_osds,
        "replicas": replicas,
        "chunk": jm._pick_chunk(pgs),
        "checked_vs_scalar": checked,
        "scalar_workers": workers,
        "d2h_bytes": 2 * out.nbytes,
        "first_map_s": first_map_s,
        "warm_map_s": warm_map_s,
        "scalar_check_s": scalar_s,
        "path": "jax_mapper",
    }


# -- phase 5 ------------------------------------------------------------------


def seeded_tree(seed: int, total: int) -> dict:
    """Device arrays of a model's shapes: float32 and bfloat16 matrices,
    half the bytes each."""
    import jax
    import jax.numpy as jnp

    keys = iter(jax.random.split(jax.random.key(seed), 64))
    tree, left, i = {}, total, 0
    while left > 0:
        dtype = jnp.float32 if i % 2 == 0 else jnp.bfloat16
        rows = max(1, min(4096, left // (2048 * jnp.dtype(dtype).itemsize)))
        tree[f"layer{i:02d}"] = {
            "w": jax.random.normal(next(keys), (rows, 2048), dtype)}
        left -= rows * 2048 * jnp.dtype(dtype).itemsize
        i += 1
    return tree


async def ckpt_round_trip(seed: int, total: int, n_osds: int) -> dict:
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ckpt import CkptStore
    from ceph_tpu.coord.mesh import fleet_mesh

    tree = seeded_tree(seed, total)
    leaves = jax.tree_util.tree_leaves(tree)
    nbytes = sum(a.nbytes for a in leaves)
    cluster = LocalCluster(n_osds)
    try:
        io = await cluster.start()
        store = CkptStore(io, "smoke")
        t0 = time.perf_counter()
        save_id = await store.save(tree)
        save_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        back = await store.restore(mesh=fleet_mesh(1), save_id=save_id)
        restore_s = time.perf_counter() - t0
        chip0 = jax.devices()[0]
        got = jax.tree_util.tree_leaves(back)
        check(len(got) == len(leaves), "restore lost arrays")
        for a, b in zip(leaves, got):
            check(b.devices() == {chip0}, "restored array is not on the chip")
            check(a.dtype == b.dtype and a.shape == b.shape,
                  f"restored {b.dtype}{b.shape} != saved {a.dtype}{a.shape}")
            bits = jnp.uint32 if a.dtype.itemsize == 4 else jnp.uint16
            check(bool(jnp.array_equal(
                jax.lax.bitcast_convert_type(a, bits),
                jax.lax.bitcast_convert_type(b, bits))),
                "restored bits differ from the saved ones")
        launches = sum(o.encode_service.launches
                       for o in cluster.osds.values())
        return {
            "arrays": len(leaves),
            "bytes": nbytes,
            "tree_d2h_bytes": nbytes,
            "tree_h2d_bytes": nbytes,
            "save_s": save_s,
            "restore_s": restore_s,
            "ec_launches": launches,
            "paths": sorted({s[1] for o in cluster.osds.values()
                             for s in o.encode_service._seen_shapes}),
        }
    finally:
        await cluster.stop()


def phase_ckpt(seed: int, moved: CodecTransfers, total: int = CKPT_BYTES,
               n_osds: int = N_OSDS) -> dict:
    start = moved.mark()
    out = asyncio.run(asyncio.wait_for(
        ckpt_round_trip(seed, total, n_osds), 900))
    check(out["paths"] == ["pallas"],
          f"checkpoint EC ran {out['paths']}, not only the Pallas kernel")
    ec = moved.since(start)
    return {**out, "ec_h2d_bytes": ec["h2d_bytes"],
            "ec_d2h_bytes": ec["d2h_bytes"]}


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only phase 3 on the 2x2 mesh of four chips, "
                    "compared with the one-chip kernel")
    args = ap.parse_args(argv)
    if not args.four_chips:
        os.environ.update(ONE_CHIP_ENV)

    import jax

    # CRUSH needs exact 64-bit integers: switch once, before any kernel
    # traces, so every phase runs under the dtype rules an OSD serves with
    jax.config.update("jax_enable_x64", True)
    from ceph_tpu.chip import use_compile_cache

    cache = use_compile_cache()
    meter = CompileMeter()
    spy = XlaPathSpy()
    moved = CodecTransfers()
    want = 4 if args.four_chips else 1
    device = phase_device(want)
    report(phase="device", compile_cache=cache, **device)
    if args.four_chips:
        meter.phase("served_four_chips", phase_served_four, args.seed, spy,
                    moved)
    else:
        meter.phase("kernel", phase_kernel, args.seed)
        check(spy.calls == 0, "the XLA bit-plane path ran")
        meter.phase("served", phase_served, args.seed, spy, moved)
        meter.phase("crush", phase_crush)
        meter.phase("ckpt", phase_ckpt, args.seed, moved)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
