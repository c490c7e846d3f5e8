"""EncodeService: coalesce concurrent per-object EC work into planar
TPU launches.

SURVEY §7 calls packing "stripes from many concurrent objects" into one
launch the real performance design problem: a 4 KiB object write encodes
512 B chunks — far too small to feed the MXU — but N concurrent writes
stacked end-to-end along the chunk axis are one wide (k, N·chunk/4) planar
`encode_words` call on the fused Pallas kernel (ceph_tpu.ops.gf_pallas).
The reference's analogue is ECBackend's op pipelining (start_rmw batches
in-flight ops, ECBackend.cc:1830) feeding ISA-L's wide SIMD units.

Mechanics: the first enqueue arms a latency-bound flush (the batch
window); everything that arrives while the window is open — concurrent
client ops on the 4 op shards, recovery decodes, scrub rebuilds — rides
the same launch. `launches`/`objects` counters let tests assert the
coalescing actually happened (objects >> launches under concurrency).

CLAY codecs batch through the same queues with their plane API
(ErasureCodeClay.plane_plan / planes): each object's chunks are cut into
their q^t planes and laid side by side along the plane axis, and the whole
layered decode of the batch is one device program, encode included. Codecs
with neither API (LRC and SHEC compositions) fall back to their per-object
paths transparently.
"""

from __future__ import annotations

import asyncio

import numpy as np

from ceph_tpu.common import tracer as tracing
from ceph_tpu.common.perf_counters import PerfCounters
from ceph_tpu.ops import gf_pallas as gp
from ceph_tpu.ops.gf import gf_region_matmul


def _bucket_pad(words: np.ndarray) -> tuple[np.ndarray, int]:
    """Pad the planar width up to a power-of-2 bucket so batches of
    varying composition reuse a handful of compiled kernels instead of
    jitting per width (zero columns encode to zero parity; sliced off)."""
    w = words.shape[-1]
    bucket = max(256, 1 << (w - 1).bit_length())
    if bucket == w:
        return words, w
    padded = np.zeros((*words.shape[:-1], bucket), dtype=words.dtype)
    padded[..., :w] = words
    return padded, w


def _has_planes(codec, blocksize: int) -> bool:
    """Whether `codec` batches through the plane API at this chunk size:
    a chunk cut into its sub-chunk planes must hold whole int32 words."""
    return hasattr(codec, "plane_plan") and (
        blocksize % (4 * codec.get_sub_chunk_count()) == 0)


def _device_leg(sp, launch):
    """Run `launch`: the call into the kernel up to its result held in
    host memory. On a traced batch, tag the span with the seconds of
    packing before it (pack_s), when it began (device_start, on the
    tracer's clock) and how long it took (device_s); an untraced batch
    takes no timestamp."""
    if sp is None:
        return launch()
    t0 = tracing.now()
    out = launch()
    t1 = tracing.now()
    sp.set_tag("pack_s", t0 - sp.start)
    sp.set_tag("device_start", t0)
    sp.set_tag("device_s", t1 - t0)
    return out


def _tag_unpacked(sp) -> None:
    """The seconds from the device leg's end to every caller's result."""
    if sp is not None and "device_start" in sp.tags:
        sp.set_tag("unpack_s", tracing.now() - sp.tags["device_start"]
                   - sp.tags["device_s"])


_UNSET = object()


class EncodeService:
    def __init__(
        self, window: float = 0.002, max_batch: int = 128,
        mesh_min_bytes: int = 8192, tracer=None,
    ):
        #: optional distributed tracer: traced ops get an encode_wait or
        #: decode_wait span (enqueue -> result) and each device launch an
        #: encode_batch/decode_batch span tagged with batch size, whether
        #: this planar shape compiled fresh or reused a cached
        #: executable, the objects' and the launch's planar bytes, and
        #: the pack, device and unpack legs
        self.tracer = tracer
        #: (op, path, k, n, bucket width) planar shapes already launched,
        #: op "encode" or "decode" — a first launch at a shape pays the
        #: jit compile, and the paths say which kernel served the batches
        self._seen_shapes: set[tuple] = set()
        #: seconds the first op of a batch waits for company
        self.window = window
        self.max_batch = max_batch
        #: planar widths >= this dispatch through the device MESH
        #: (parallel.sharding): the coalesced batch's byte axis folds
        #: onto the (stripe, byte) mesh with no communication, so every
        #: visible chip shares the launch; below it the single-device
        #: kernel wins (dispatch overhead beats the parallelism)
        self.mesh_min_bytes = mesh_min_bytes
        self._mesh_cache = _UNSET
        #: launches that went through the sharded mesh path
        self.mesh_launches = 0
        self._enc_q: dict[int, list] = {}
        self._dec_q: dict[tuple, list] = {}
        self._codecs: dict[int, object] = {}
        #: armed window timers, cancelled on flush (a stale timer from a
        #: max_batch-flushed batch would otherwise cut the NEXT window
        #: short and erode coalescing under sustained load). Decode
        #: timers are keyed per CODEC: one shared window drains every
        #: erasure signature queued for it (mass-failure recovery waves
        #: mix signatures; a window per signature would serialize them)
        self._enc_timers: dict[int, object] = {}
        self._dec_timers: dict[int, object] = {}
        #: device launches / objects served — the coalescing evidence
        self.launches = 0
        self.objects = 0
        #: the `perf dump` block: plane-API erasure signatures whose plan
        #: was built at a launch, and launches that found theirs cached
        self.perf = PerfCounters("encode_service")
        self.perf.add_u64_counter(
            "plane_plans_built", "plane-API signature plans built")
        self.perf.add_u64_counter(
            "plane_plans_cached", "plane-API launches whose plan was cached")

    def _mesh(self, width_bytes: int):
        """The device mesh for a planar launch of `width_bytes`, or None
        (single device / width too small to amortize dispatch)."""
        if width_bytes < self.mesh_min_bytes:
            return None
        if self._mesh_cache is _UNSET:
            import jax

            n = len(jax.devices())
            if n > 1:
                from ceph_tpu.parallel import sharding

                # largest power-of-2 subset: bucket-padded planar widths
                # then always fold evenly onto the (stripe, byte) axes
                self._mesh_cache = sharding.ec_mesh(
                    1 << (n.bit_length() - 1)
                )
            else:
                self._mesh_cache = None
        return self._mesh_cache

    def device_path(self) -> str:
        """Which kernel this process's launches take, for the boot log:
        the mesh at or above mesh_min_bytes on a multi-device host, the
        Pallas kernel on one TPU, numpy off the chip."""
        import jax

        devs = jax.devices()
        single = "pallas" if gp.available() else "numpy"
        where = f"{len(devs)} x {devs[0].device_kind}"
        if len(devs) > 1:
            return (
                f"mesh for batches >= {self.mesh_min_bytes} B, "
                f"{single} below ({where})"
            )
        return f"{single} ({where})"

    # -- encode ---------------------------------------------------------------

    async def encode(self, codec, data: bytes) -> dict[int, bytes]:
        """All k+m chunks for one object, batched across callers."""
        blocksize = codec.get_chunk_size(len(data))
        if not _has_planes(codec, blocksize) and (
                not hasattr(codec, "encode_words") or blocksize % 4):
            self.launches += 1
            self.objects += 1
            return codec.encode(range(codec.get_chunk_count()), data)
        key = id(codec)
        self._codecs[key] = codec
        fut = asyncio.get_event_loop().create_future()
        q = self._enc_q.setdefault(key, [])
        q.append((data, blocksize, fut))
        sp = None if self.tracer is None else self.tracer.child(
            "encode_wait", tags={"bytes": len(data)}
        )
        if len(q) >= self.max_batch:
            self._flush_encode(key)
        elif len(q) == 1:
            # call_later captures the current context, so the flush
            # callback's encode_batch span parents to THIS op's trace
            self._enc_timers[key] = asyncio.get_event_loop().call_later(
                self.window, self._flush_encode, key
            )
        if sp is None:
            return await fut
        try:
            return await fut
        finally:
            sp.finish()

    def _flush_encode(self, key: int) -> None:
        timer = self._enc_timers.pop(key, None)
        if timer is not None:
            timer.cancel()
        q = self._enc_q.pop(key, [])
        if not q:
            return
        codec = self._codecs[key]
        k, n = codec.k, codec.get_chunk_count()
        sp = None if self.tracer is None else self.tracer.child(
            "encode_batch", tags={"batch": len(q)}
        )
        try:
            self._flush_encode_inner(key, q, codec, k, n, sp)
        finally:
            if sp is not None:
                sp.finish()

    def _flush_encode_inner(self, key, q, codec, k, n, sp) -> None:
        try:
            # pack every object's chunk j end-to-end into planar row j
            rows: list[list[np.ndarray]] = [[] for _ in range(k)]
            for data, bs, _fut in q:
                padded = np.zeros(k * bs, dtype=np.uint8)
                padded[: len(data)] = np.frombuffer(data, dtype=np.uint8)
                for i in range(k):
                    rows[i].append(padded[i * bs: (i + 1) * bs])
            if hasattr(codec, "plane_plan"):
                plan = self._plan(codec, range(k), range(k, n))
                parity = self._launch_planes(
                    sp, codec, plan, [[r[j] for r in rows]
                                      for j in range(len(q))])
                self._resolve_encode(q, codec, k, n, rows, lambda j, i, off,
                                     bs: parity[j][i])
                _tag_unpacked(sp)
                return
            planes = np.stack([np.concatenate(r) for r in rows])
            mesh = self._mesh(planes.shape[1])
            path, bucket = "numpy", planes.shape[1]
            if mesh is not None:
                from ceph_tpu.parallel import sharding

                padded, width = _bucket_pad(planes)
                path, bucket = "mesh", padded.shape[-1]
                self._note_launch(sp, "encode", path, k, n, bucket, len(q),
                                  planes.nbytes, padded.nbytes)
                parity = _device_leg(sp, lambda: sharding.mesh_encode_planar(
                    codec, padded, mesh
                ))[:, :width]
                self.mesh_launches += 1
            elif gp.available():
                words = np.stack(
                    [np.concatenate(r).view(np.int32) for r in rows]
                )
                words, width = _bucket_pad(words)
                path, bucket = "pallas", words.shape[-1]
                self._note_launch(sp, "encode", path, k, n, bucket, len(q),
                                  planes.nbytes, words.nbytes)
                parity = _device_leg(sp, lambda: np.asarray(
                    codec.encode_words(words)
                ))[:, :width].view(np.uint8)
                parity = parity.reshape(codec.m, -1)
            else:
                # off-device: exact table-driven numpy planar path — no
                # jit-per-width (tiny batches would otherwise recompile
                # for every composition)
                parity_mat = codec._gen[codec.k:]
                self._note_launch(sp, "encode", path, k, n, bucket, len(q),
                                  planes.nbytes, planes.nbytes)
                if getattr(codec, "_xor_ok", False):
                    parity = _device_leg(sp, lambda: np.bitwise_xor.reduce(
                        planes, axis=0
                    )[None])
                else:
                    parity = _device_leg(
                        sp, lambda: gf_region_matmul(parity_mat, planes))
            self._resolve_encode(q, codec, k, n, rows, lambda j, i, off,
                                 bs: parity[i, off: off + bs])
            _tag_unpacked(sp)
        except Exception as e:
            for _data, _bs, fut in q:
                if not fut.done():
                    fut.set_exception(e)

    def _resolve_encode(self, q, codec, k, n, rows, parity_of) -> None:
        """Hand every caller its k + m chunks: parity_of(j, i, off, bs) is
        parity row i of object j, whose chunks start `off` bytes into the
        launch's planar rows."""
        self.launches += 1
        self.objects += len(q)
        off = 0
        for j, (data, bs, fut) in enumerate(q):
            chunks: dict[int, bytes] = {}
            for logical in range(k):
                chunks[codec.chunk_index(logical)] = rows[logical][j].tobytes()
            for logical in range(k, n):
                chunks[codec.chunk_index(logical)] = parity_of(
                    j, logical - k, off, bs).tobytes()
            off += bs
            if not fut.done():
                fut.set_result(chunks)

    def _plan(self, codec, present, targets):
        """The codec's plan for a plane-API launch, counted in `perf`."""
        built = codec.plans_built
        plan = codec.plane_plan(present, targets)
        self.perf.inc("plane_plans_built" if codec.plans_built != built
                      else "plane_plans_cached")
        return plan

    def _launch_planes(self, sp, codec, plan, objects) -> list:
        """One launch of a plane-API codec's device program over a batch:
        objects[j] holds object j's chunks of plan.inputs, laid out by
        codec.pack_planes (padded to the bucket of the batch's whole-chunk
        width, as _bucket_pad pads an RS batch). Returns, per object, its
        rebuilt chunks of plan.targets as (len(targets), chunk) uint8."""
        words, widths = codec.pack_planes(objects)
        k, S = words.shape[0], words.shape[1]
        # one executable per bucket serves encode and every decode
        self._note_launch(sp, "layered", "clay", k, codec.m,
                          S * words.shape[-1], len(objects),
                          4 * k * S * sum(widths), words.nbytes)
        if sp is not None:
            sp.set_tag("planes", S)
            sp.set_tag("groups", plan.groups)
        out = _device_leg(sp, lambda: codec.planes(plan, words))
        return codec.unpack_planes(out, widths)

    def _note_launch(self, sp, op: str, path: str, k: int, n: int,
                     bucket: int, batch: int, object_bytes: int,
                     launch_bytes: int) -> None:
        """Tag the batch span with the compile-vs-execute split: a
        planar shape's FIRST launch pays the jit compile, later ones
        reuse the cached executable — the difference dominates tail
        latency and must be attributable in a trace. object_bytes are
        the batch's planar bytes over all input rows, launch_bytes the
        same rows padded to the bucket: the launch's padded share."""
        shape = (op, path, k, n, bucket)
        fresh = shape not in self._seen_shapes
        self._seen_shapes.add(shape)
        if sp is not None:
            sp.set_tag("path", path)
            sp.set_tag("width", bucket)
            sp.set_tag("compile", fresh)
            sp.set_tag("batch", batch)
            sp.set_tag("object_bytes", object_bytes)
            sp.set_tag("launch_bytes", launch_bytes)

    # -- decode ---------------------------------------------------------------

    async def decode(
        self, codec, want_to_read, chunks: dict[int, bytes]
    ) -> dict[int, bytes]:
        """Batched degraded-read decode: objects sharing an erasure
        signature (same survivors/targets) decode in one launch."""
        want = set(want_to_read)
        have = set(chunks)
        if want <= have:
            return {i: bytes(chunks[i]) for i in want}
        blocksize = len(next(iter(chunks.values())))
        planes = _has_planes(codec, blocksize)
        if not planes and (
                not hasattr(codec, "decode_words") or blocksize % 4):
            self.launches += 1
            self.objects += 1
            return codec.decode(want, chunks)
        # a plane-API plan reads every survivor it is offered (it picks
        # k of them); an RS decode reads the first k
        present = tuple(
            sorted(codec.logical_index(p) for p in have)
        )[: None if planes else codec.k]
        targets = tuple(
            sorted(codec.logical_index(p) for p in want - have)
        )
        key = (id(codec), present, targets)
        self._codecs[id(codec)] = codec
        fut = asyncio.get_event_loop().create_future()
        q = self._dec_q.setdefault(key, [])
        q.append((chunks, blocksize, want, fut))
        # the survivors' bytes the decode reads, and the chunks it rebuilds
        sp = None if self.tracer is None else self.tracer.child(
            "decode_wait",
            tags={"bytes": blocksize * min(len(present), codec.k),
                  "targets": len(targets)},
        )
        if len(q) >= self.max_batch:
            self._flush_decode(key)
            if not any(k[0] == id(codec) for k in self._dec_q):
                timer = self._dec_timers.pop(id(codec), None)
                if timer is not None:
                    timer.cancel()
        elif id(codec) not in self._dec_timers:
            # ONE window per codec, not per signature: a mass-failure
            # recovery wave decodes with many erasure signatures at
            # once, and paying a fresh window per signature would
            # serialize exactly when throughput matters most — window
            # expiry drains EVERY signature queued for this codec
            # (one launch each, shared window)
            self._dec_timers[id(codec)] = (
                asyncio.get_event_loop().call_later(
                    self.window, self._flush_decode_all, id(codec)
                )
            )
        if sp is None:
            return await fut
        try:
            return await fut
        finally:
            sp.finish()

    def _flush_decode_all(self, codec_id: int) -> None:
        """Window expiry: drain every signature queued for this codec."""
        self._dec_timers.pop(codec_id, None)
        for key in [k for k in self._dec_q if k[0] == codec_id]:
            self._flush_decode(key)

    def _flush_decode(self, key: tuple) -> None:
        q = self._dec_q.pop(key, None)
        if not q:
            return
        codec_id, present, targets = key
        codec = self._codecs[codec_id]
        sp = None if self.tracer is None else self.tracer.child(
            "decode_batch",
            tags={"batch": len(q), "targets": len(targets)},
        )
        try:
            self._flush_decode_inner(key, q, codec, sp)
        finally:
            if sp is not None:
                sp.finish()

    def _flush_decode_inner(self, key, q, codec, sp) -> None:
        codec_id, present, targets = key
        try:
            if hasattr(codec, "plane_plan"):
                plan = self._plan(codec, present, targets)
                rebuilt = self._launch_planes(sp, codec, plan, [
                    [chunks[codec.chunk_index(i)] for i in plan.inputs]
                    for chunks, _bs, _want, _fut in q])
                self._resolve_decode(q, codec, targets, lambda j, t, off,
                                     bs: rebuilt[j][t])
                _tag_unpacked(sp)
                return
            rows: list[list[np.ndarray]] = [[] for _ in present]
            for chunks, bs, _want, _fut in q:
                for i, logical in enumerate(present):
                    phys = codec.chunk_index(logical)
                    rows[i].append(
                        np.frombuffer(chunks[phys], dtype=np.uint8)
                    )
            planes = np.stack([np.concatenate(r) for r in rows])
            mesh = self._mesh(planes.shape[1])
            k, n = codec.k, len(targets)
            if mesh is not None:
                from ceph_tpu.parallel import sharding

                padded, width = _bucket_pad(planes)
                self._note_launch(
                    sp, "decode", "mesh", k, n, padded.shape[-1], len(q),
                    planes.nbytes, padded.nbytes)
                rebuilt = _device_leg(sp, lambda: sharding.mesh_decode_planar(
                    codec, list(present), list(targets), padded, mesh
                ))[:, :width]
                self.mesh_launches += 1
            elif gp.available():
                words = np.stack(
                    [np.concatenate(r).view(np.int32) for r in rows]
                )
                words, width = _bucket_pad(words)
                self._note_launch(
                    sp, "decode", "pallas", k, n, words.shape[-1], len(q),
                    planes.nbytes, words.nbytes)
                rebuilt = _device_leg(sp, lambda: np.asarray(
                    codec.decode_words(
                        list(present), list(targets), words
                    )
                ))[:, :width].view(np.uint8).reshape(len(targets), -1)
            else:
                from ceph_tpu.ec import matrices

                self._note_launch(
                    sp, "decode", "numpy", k, n, planes.shape[1], len(q),
                    planes.nbytes, planes.nbytes)
                dm = matrices.decode_matrix(
                    codec._gen, codec.k, list(present), list(targets)
                )
                rebuilt = _device_leg(
                    sp, lambda: gf_region_matmul(dm, planes))
            self._resolve_decode(q, codec, targets, lambda j, t, off,
                                 bs: rebuilt[t, off: off + bs])
            _tag_unpacked(sp)
        except Exception as e:
            for _c, _b, _w, fut in q:
                if not fut.done():
                    fut.set_exception(e)

    def _resolve_decode(self, q, codec, targets, rebuilt_of) -> None:
        """Hand every caller the chunks it wants: rebuilt_of(j, t, off,
        bs) is target t of object j, whose chunks start `off` bytes into
        the launch's planar rows."""
        self.launches += 1
        self.objects += len(q)
        off = 0
        for j, (chunks, bs, want, fut) in enumerate(q):
            out = {i: bytes(chunks[i]) for i in want if i in chunks}
            for t, logical in enumerate(targets):
                phys = codec.chunk_index(logical)
                if phys in want:
                    out[phys] = rebuilt_of(j, t, off, bs).tobytes()
            off += bs
            if not fut.done():
                fut.set_result(out)
