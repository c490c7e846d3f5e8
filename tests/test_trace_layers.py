"""The spans that put each leg of an op down to a layer: the event-loop lag
probe (loop_lag), the EncodeService's decode_wait span and the launch
phases and bytes on its batch spans, all on the tracer's clock."""

# the lag probe measures how late the event loop runs on the wall clock:
# its tests block the loop and wait on the loop's own 10 ms timers
# cephlint: disable-file=clock-discipline

import asyncio
import time

import numpy as np
import pytest

from ceph_tpu.common import tracer as tracing
from ceph_tpu.common.config import Config
from ceph_tpu.common.tracer import Tracer


def traced_config(**kw) -> Config:
    cfg = Config()
    cfg.set("tracer_enabled", True)
    cfg.set("tracer_sample_rate", 1.0)
    for k, v in kw.items():
        cfg.set(k, v)
    return cfg


def spans_named(tr: Tracer, name: str) -> list[dict]:
    return [s for s in tr._ring if s["name"] == name]


async def until(pred, timeout=5.0):
    """Poll `pred` on the loop's own timers: a loaded machine may run a
    10 ms firing late, never not at all."""
    loop = asyncio.get_running_loop()
    end = loop.time() + timeout
    while not pred():
        assert loop.time() < end, "condition never held"
        await asyncio.sleep(0.005)


# -- the event-loop lag probe ------------------------------------------------


def test_disabled_tracer_starts_no_probe():
    async def main():
        loop = asyncio.get_running_loop()
        cfg = Config()  # tracer_enabled defaults off
        tr = Tracer("osd.0", config=cfg)
        assert tr.start("op") is None
        await asyncio.sleep(0.03)
        assert tr._probe is None and loop not in tracing._probes
        # enabling starts it; disabling stops it within one period
        cfg.set("tracer_enabled", True)
        probe = tracing._probes[loop]
        assert probe.handle is not None
        await until(lambda: spans_named(tr, "loop_lag"))
        cfg.set("tracer_enabled", False)
        await until(lambda: probe.handle is None)
        assert loop not in tracing._probes

    asyncio.run(main())


def test_one_probe_per_loop_whatever_the_tracers():
    async def main():
        loop = asyncio.get_running_loop()
        cfg = traced_config()
        a, b = Tracer("osd.1", config=cfg), Tracer("osd.2", config=cfg)
        assert a._probe is b._probe is tracing._probes[loop]
        # the first registered tracer records; the other sees none
        await until(lambda: len(spans_named(a, "loop_lag")) >= 2)
        assert not spans_named(b, "loop_lag")
        # closing the recorder hands the probe to the next tracer
        a.close()
        await until(lambda: spans_named(b, "loop_lag"))
        b.close()
        assert loop not in tracing._probes

    asyncio.run(main())


def test_loop_lag_covers_a_blocked_loop():
    """A callback that blocks the loop for 50 ms, after the probe's firing
    fell due, makes that firing's loop_lag span at least 50 ms long."""

    async def main():
        loop = asyncio.get_running_loop()
        tr = Tracer("osd.3", config=traced_config())
        probe = tracing._probes[loop]
        await asyncio.sleep(0.025)
        due = probe.due
        blocked = []

        def block():
            while tracing.now() < due:
                pass
            t0 = time.perf_counter()
            time.sleep(0.05)
            blocked.append(time.perf_counter() - t0)

        # just ahead of the firing: the blocker runs first, the firing
        # right after it
        loop.call_at(probe.handle.when() - 0.001, block)
        await until(lambda: blocked and any(
            s["start"] == due for s in spans_named(tr, "loop_lag")))
        lags = [s for s in spans_named(tr, "loop_lag") if s["start"] == due]
        assert blocked and blocked[0] >= 0.05
        assert len(lags) == 1
        assert lags[0]["duration"] >= blocked[0]
        tr.close()

    asyncio.run(main())


def test_loop_lag_is_sampled_never_promoted_and_histogrammed():
    async def main():
        cfg = traced_config(tracer_sample_rate=0.0,
                            tracer_tail_slow_ms=0.001)
        tr = Tracer("osd.4", config=cfg)
        key = "lat_us_loop_lag"
        await until(lambda: key in tr.perf.dump())
        time.sleep(0.04)  # a slow firing: still no tail promotion
        await until(lambda: sum(tr.perf.dump()[key].values()) >= 3)
        assert not spans_named(tr, "loop_lag")  # unsampled: not exported
        assert tr.drain_promoted() == []
        # perf dump / Prometheus: one histogram count per firing, the
        # slow one in a bucket of 16 ms or more
        hist = tr.perf.dump()["lat_us_loop_lag"]
        assert sum(hist.values()) >= 3
        assert any(int(b) >= 1 << 14 for b in hist)
        tr.close()

    asyncio.run(main())


# -- the EncodeService --------------------------------------------------------


def _payloads(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, n, np.uint8).tobytes() for n in sizes]


def test_disabled_tracer_takes_no_timestamps_in_flushes(monkeypatch):
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.osd.encode_service import EncodeService

    calls = []
    real = tracing.now
    monkeypatch.setattr(tracing, "now", lambda: calls.append(1) or real())

    async def main():
        codec = factory("tpu", {"k": "2", "m": "2"})
        svc = EncodeService(window=0.001, tracer=Tracer("osd.5",
                                                        config=Config()))
        full = codec.encode(range(4), _payloads(1, [4096])[0])
        await svc.encode(codec, _payloads(2, [4096])[0])
        await svc.decode(codec, {1}, {i: full[i] for i in (0, 2)})
        assert svc.launches == 2

    asyncio.run(main())
    assert calls == []


def test_decode_wait_brackets_a_batched_decode():
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.osd.encode_service import EncodeService

    async def main():
        codec = factory("tpu", {"k": "2", "m": "2"})
        tr = Tracer("osd.6", config=traced_config())
        svc = EncodeService(window=0.02, tracer=tr)
        payloads = _payloads(3, [2048, 2048, 2048])
        full = [codec.encode(range(4), p) for p in payloads]
        got_at = {}

        async def one(j):
            root = tr.start("op")
            tok = tr.use(root)
            try:
                out = await svc.decode(
                    codec, {1}, {i: full[j][i] for i in (0, 2)})
                got_at[root.trace_id] = tracing.now()
                return out
            finally:
                tr.release(tok)
                root.finish()

        outs = await asyncio.gather(*(one(j) for j in range(3)))
        assert [o[1] for o in outs] == [f[1] for f in full]
        assert svc.launches == 1
        waits = spans_named(tr, "decode_wait")
        batch = spans_named(tr, "decode_batch")
        assert len(waits) == 3 and len(batch) == 1
        b = batch[0]
        for w in waits:
            assert w["tags"] == {"bytes": 2 * 1024, "targets": 1}
            # enqueue .. result: the launch that set the result lies
            # inside each wait, and the caller holds it right after
            assert w["start"] <= b["start"]
            end = w["start"] + w["duration"]
            assert b["start"] + b["duration"] <= end <= got_at[w["trace_id"]]
        # the phases of the launch, on the tracer's clock, inside it
        tags = b["tags"]
        assert tags["path"] == "numpy"
        assert b["start"] <= tags["device_start"]
        assert tags["pack_s"] == pytest.approx(
            tags["device_start"] - b["start"])
        end = tags["device_start"] + tags["device_s"] + tags["unpack_s"]
        assert end <= b["start"] + b["duration"] + 1e-6
        assert min(tags["pack_s"], tags["device_s"], tags["unpack_s"]) >= 0

    asyncio.run(main())


@pytest.mark.parametrize("size,padded", [(20000, True), (16384, False)])
def test_object_bytes_never_exceed_launch_bytes(size, padded):
    """On the mesh path (the 8-device CPU mesh) a batch pads its planar
    width to a power of two; a width already at one launches unpadded."""
    from ceph_tpu.ec.registry import factory
    from ceph_tpu.osd.encode_service import EncodeService

    async def main():
        codec = factory("tpu", {"k": "2", "m": "2"})
        tr = Tracer("osd.7", config=traced_config())
        svc = EncodeService(window=0.001, mesh_min_bytes=4096, tracer=tr)
        root = tr.start("op")
        tok = tr.use(root)
        try:
            data = _payloads(4, [size])[0]
            assert await svc.encode(codec, data) == codec.encode(
                range(4), data)
        finally:
            tr.release(tok)
            root.finish()
        (b,) = spans_named(tr, "encode_batch")
        tags = b["tags"]
        assert tags["path"] == "mesh"
        chunk = codec.get_chunk_size(size)
        assert tags["object_bytes"] == 2 * chunk
        assert tags["launch_bytes"] == 2 * tags["width"]
        assert tags["object_bytes"] <= tags["launch_bytes"]
        assert (tags["object_bytes"] < tags["launch_bytes"]) == padded

    asyncio.run(main())
