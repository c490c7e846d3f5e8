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

Codecs without the planar API (clay/lrc/shec compositions) fall back to
their per-object paths transparently.
"""

from __future__ import annotations

import asyncio

import numpy as np

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


_UNSET = object()


class EncodeService:
    def __init__(
        self, window: float = 0.002, max_batch: int = 128,
        mesh_min_bytes: int = 8192, tracer=None,
    ):
        #: optional distributed tracer: traced ops get an encode_wait
        #: span (enqueue -> result) and each device launch an
        #: encode_batch span tagged with batch size and whether this
        #: planar shape compiled fresh or reused a cached executable
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
        if not hasattr(codec, "encode_words") or blocksize % 4:
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
            planes = np.stack([np.concatenate(r) for r in rows])
            mesh = self._mesh(planes.shape[1])
            path, bucket = "numpy", planes.shape[1]
            if mesh is not None:
                from ceph_tpu.parallel import sharding

                padded, width = _bucket_pad(planes)
                path, bucket = "mesh", padded.shape[-1]
                self._note_launch(sp, "encode", path, k, n, bucket, len(q))
                parity = sharding.mesh_encode_planar(
                    codec, padded, mesh
                )[:, :width]
                self.mesh_launches += 1
            elif gp.available():
                words = np.stack(
                    [np.concatenate(r).view(np.int32) for r in rows]
                )
                words, width = _bucket_pad(words)
                path, bucket = "pallas", words.shape[-1]
                self._note_launch(sp, "encode", path, k, n, bucket, len(q))
                parity = np.asarray(
                    codec.encode_words(words)
                )[:, :width].view(np.uint8)
                parity = parity.reshape(codec.m, -1)
            else:
                # off-device: exact table-driven numpy planar path — no
                # jit-per-width (tiny batches would otherwise recompile
                # for every composition)
                parity_mat = codec._gen[codec.k:]
                self._note_launch(sp, "encode", path, k, n, bucket, len(q))
                if getattr(codec, "_xor_ok", False):
                    parity = np.bitwise_xor.reduce(
                        planes, axis=0
                    )[None]
                else:
                    parity = gf_region_matmul(parity_mat, planes)
            self.launches += 1
            self.objects += len(q)
            off = 0
            for j, (data, bs, fut) in enumerate(q):
                chunks: dict[int, bytes] = {}
                for logical in range(k):
                    chunks[codec.chunk_index(logical)] = (
                        rows[logical][j].tobytes()
                    )
                for logical in range(k, n):
                    chunks[codec.chunk_index(logical)] = parity[
                        logical - k, off: off + bs
                    ].tobytes()
                off += bs
                if not fut.done():
                    fut.set_result(chunks)
        except Exception as e:
            for _data, _bs, fut in q:
                if not fut.done():
                    fut.set_exception(e)

    def _note_launch(self, sp, op: str, path: str, k: int, n: int,
                     bucket: int, batch: int) -> None:
        """Tag the batch span with the compile-vs-execute split: a
        planar shape's FIRST launch pays the jit compile, later ones
        reuse the cached executable — the difference dominates tail
        latency and must be attributable in a trace."""
        shape = (op, path, k, n, bucket)
        fresh = shape not in self._seen_shapes
        self._seen_shapes.add(shape)
        if sp is not None:
            sp.set_tag("path", path)
            sp.set_tag("width", bucket)
            sp.set_tag("compile", fresh)
            sp.set_tag("batch", batch)

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
        if not hasattr(codec, "decode_words") or blocksize % 4:
            self.launches += 1
            self.objects += 1
            return codec.decode(want, chunks)
        present = tuple(
            sorted(codec.logical_index(p) for p in have)
        )[: codec.k]
        targets = tuple(
            sorted(codec.logical_index(p) for p in want - have)
        )
        key = (id(codec), present, targets)
        self._codecs[id(codec)] = codec
        fut = asyncio.get_event_loop().create_future()
        q = self._dec_q.setdefault(key, [])
        q.append((chunks, blocksize, want, fut))
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
        return await fut

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
                    sp, "decode", "mesh", k, n, padded.shape[-1], len(q))
                rebuilt = sharding.mesh_decode_planar(
                    codec, list(present), list(targets), padded, mesh
                )[:, :width]
                self.mesh_launches += 1
            elif gp.available():
                words = np.stack(
                    [np.concatenate(r).view(np.int32) for r in rows]
                )
                words, width = _bucket_pad(words)
                self._note_launch(
                    sp, "decode", "pallas", k, n, words.shape[-1], len(q))
                rebuilt = np.asarray(
                    codec.decode_words(
                        list(present), list(targets), words
                    )
                )[:, :width].view(np.uint8).reshape(len(targets), -1)
            else:
                from ceph_tpu.ec import matrices

                self._note_launch(
                    sp, "decode", "numpy", k, n, planes.shape[1], len(q))
                dm = matrices.decode_matrix(
                    codec._gen, codec.k, list(present), list(targets)
                )
                rebuilt = gf_region_matmul(dm, planes)
            self.launches += 1
            self.objects += len(q)
            off = 0
            for chunks, bs, want, fut in q:
                out = {
                    i: bytes(chunks[i]) for i in want if i in chunks
                }
                for t, logical in enumerate(targets):
                    phys = codec.chunk_index(logical)
                    if phys in want:
                        out[phys] = rebuilt[t, off: off + bs].tobytes()
                off += bs
                if not fut.done():
                    fut.set_result(out)
        except Exception as e:
            for _c, _b, _w, fut in q:
                if not fut.done():
                    fut.set_exception(e)
