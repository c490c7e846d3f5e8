"""EncodeService: coalesce concurrent per-object EC work into one device
launch per batch.

SURVEY §7 calls packing "stripes from many concurrent objects" into one
launch the real performance design problem: a 4 KiB object write encodes
512 B chunks — far too small to feed the MXU — but N concurrent writes
laid side by side along the chunk axis are one wide launch. The
reference's analogue is ECBackend's op pipelining (start_rmw batches
in-flight ops, ECBackend.cc:1830) feeding ISA-L's wide SIMD units.

Mechanics: the first enqueue arms a latency-bound flush (the batch
window); everything that arrives while the window is open — concurrent
client ops on the 4 op shards, recovery decodes, scrub rebuilds — rides
the same launch. `launches`/`objects` counters let tests assert the
coalescing actually happened (objects >> launches under concurrency).

Every codec that batches does so through its plane API (ErasureCode's
plane_plan / planes, with the shared pack_planes / unpack_planes layout):
per batch the service builds the erasure signature's plan, packs the
objects' chunks side by side, makes one launch and hands each caller its
chunks. The codec's plan chooses the kernel (the fused Pallas kernel on
the TPU, numpy tables elsewhere, CLAY's layered program); the service
chooses only between that and the device mesh, for single-plane plans
(one GF(2^8) matrix over byte columns) wide enough to pay for it. Codecs
without the plane API (LRC and SHEC compositions) take their per-object
paths.
"""

from __future__ import annotations

import asyncio

import numpy as np

from ceph_tpu.common import tracer as tracing
from ceph_tpu.common.perf_counters import PerfCounters
from ceph_tpu.parallel import sharding


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
    takes no timestamp. The loop's CPU in it is the account ec_device;
    its wall seconds less its CPU bound the wait on the device and the
    transfers from above (they also hold waits for the interpreter lock
    and the loop thread's preemption)."""
    with tracing.account("ec_device"):
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
        #: (op, path, k, n, bucket width) shapes already launched, op and
        #: n from the plan's program — a first launch at a shape pays the
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
        """Where this process's launches run, for the boot log: the mesh
        at or above mesh_min_bytes on a multi-device host, else the
        kernel each codec's plan names for the device."""
        import jax

        devs = jax.devices()
        where = f"{len(devs)} x {devs[0].device_kind}"
        if len(devs) > 1:
            return (
                f"mesh for batches >= {self.mesh_min_bytes} B, "
                f"the codec's kernel below ({where})"
            )
        return f"the codec's kernel ({where})"

    # -- encode ---------------------------------------------------------------

    async def encode(self, codec, data: bytes) -> dict[int, bytes]:
        """All k+m chunks for one object, batched across callers."""
        blocksize = codec.get_chunk_size(len(data))
        if not _has_planes(codec, blocksize):
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
            # a launch's CPU is three accounts: ec_pack (plan and pack,
            # and whatever the other two leave), ec_device, ec_unpack
            with tracing.account("ec_pack"):
                self._flush_encode_inner(key, q, codec, k, n, sp)
        finally:
            if sp is not None:
                sp.finish()

    def _flush_encode_inner(self, key, q, codec, k, n, sp) -> None:
        try:
            plan = self._plan(codec, range(k), range(k, n))
            data = []
            for obj, bs, _fut in q:
                padded = np.zeros(k * bs, dtype=np.uint8)
                padded[: len(obj)] = np.frombuffer(obj, dtype=np.uint8)
                data.append(padded.reshape(k, bs))
            parity = self._launch(
                sp, codec, plan, data,
                lambda planes, mesh: sharding.mesh_encode_planar(
                    codec, planes, mesh))
            self.launches += 1
            self.objects += len(q)
            with tracing.account("ec_unpack"):
                for rows, out, (_obj, _bs, fut) in zip(data, parity, q):
                    chunks = {codec.chunk_index(i): rows[i].tobytes()
                              for i in range(k)}
                    for logical, row in zip(plan.targets, out):
                        chunks[codec.chunk_index(logical)] = row.tobytes()
                    if not fut.done():
                        fut.set_result(chunks)
                _tag_unpacked(sp)
        except Exception as e:
            for _obj, _bs, fut in q:
                if not fut.done():
                    fut.set_exception(e)

    def _plan(self, codec, present, targets):
        """The codec's plan for a launch, counted in `perf`."""
        built = codec.plans_built
        plan = codec.plane_plan(present, targets)
        self.perf.inc("plane_plans_built" if codec.plans_built != built
                      else "plane_plans_cached")
        return plan

    def _launch(self, sp, codec, plan, objects, on_mesh) -> list:
        """One launch over a batch: objects[j] holds object j's chunks of
        plan.inputs, laid out by codec.pack_planes (padded to the bucket
        of the batch's whole-chunk width). A single-plane plan is one
        GF(2^8) matrix over byte columns, so a batch at least
        mesh_min_bytes wide folds onto the device mesh through on_mesh(
        (k, W) uint8 rows, mesh); every other batch runs codec.planes.
        Returns, per object, its chunks of plan.targets as
        (len(targets), chunk) uint8."""
        words, widths = codec.pack_planes(objects)
        k, S, W = words.shape
        object_bytes = 4 * k * S * sum(widths)
        mesh = self._mesh(object_bytes // k) if S == 1 else None
        op, n = plan.program
        if mesh is None:
            path, width = plan.path, S * W
            launch = lambda: codec.planes(plan, words)
        else:
            path, width = "mesh", 4 * W
            launch = lambda: on_mesh(
                words.reshape(k, -1).view(np.uint8), mesh).view("<i4")[:, None]
        # a shape's first launch pays the jit compile, later ones reuse
        # the cached executable: the difference dominates tail latency
        # and must be attributable in a trace
        shape = (op, path, k, n, width)
        fresh = shape not in self._seen_shapes
        self._seen_shapes.add(shape)
        if sp is not None:
            sp.set_tag("path", path)
            sp.set_tag("width", width)
            sp.set_tag("compile", fresh)
            sp.set_tag("batch", len(objects))
            # the input rows' bytes, and the same rows padded to the
            # bucket: the launch's padded share
            sp.set_tag("object_bytes", object_bytes)
            sp.set_tag("launch_bytes", words.nbytes)
            sp.set_tag("planes", S)
            sp.set_tag("groups", plan.groups)
        out = _device_leg(sp, launch)
        if mesh is not None:
            self.mesh_launches += 1
        with tracing.account("ec_unpack"):
            return codec.unpack_planes(out, widths)

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
        if not _has_planes(codec, blocksize):
            self.launches += 1
            self.objects += 1
            return codec.decode(want, chunks)
        present = codec.plan_survivors(
            sorted(codec.logical_index(p) for p in have))
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
            with tracing.account("ec_pack"):
                self._flush_decode_inner(key, q, codec, sp)
        finally:
            if sp is not None:
                sp.finish()

    def _flush_decode_inner(self, key, q, codec, sp) -> None:
        codec_id, present, targets = key
        try:
            plan = self._plan(codec, present, targets)
            rebuilt = self._launch(
                sp, codec, plan, [
                    [chunks[codec.chunk_index(i)] for i in plan.inputs]
                    for chunks, _bs, _want, _fut in q],
                lambda planes, mesh: sharding.mesh_decode_planar(
                    codec, list(plan.inputs), list(targets), planes, mesh))
            self.launches += 1
            self.objects += len(q)
            with tracing.account("ec_unpack"):
                for (chunks, _bs, want, fut), out in zip(q, rebuilt):
                    res = {i: bytes(chunks[i]) for i in want if i in chunks}
                    for logical, row in zip(targets, out):
                        phys = codec.chunk_index(logical)
                        if phys in want:
                            res[phys] = row.tobytes()
                    if not fut.done():
                        fut.set_result(res)
                _tag_unpacked(sp)
        except Exception as e:
            for _c, _b, _w, fut in q:
                if not fut.done():
                    fut.set_exception(e)
