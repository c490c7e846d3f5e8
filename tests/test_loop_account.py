"""The event loop's CPU accounts (common/tracer.py LoopAccounts): exclusive
accounts by layer, the probe's select timing and its loop_account spans,
and the benchmark's readers of those spans on a recorded fixture."""

# the accounts and the busy share are read off the thread's CPU clock and
# the wall clock by design
# cephlint: disable-file=clock-discipline

import asyncio
import os
import time
import tracemalloc
from types import SimpleNamespace

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


def burn(seconds: float) -> None:
    """Spend `seconds` of this thread's CPU."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < seconds:
        pass


# -- the accounts --------------------------------------------------------------


def test_nested_accounts_are_exclusive():
    """An outer account with an inner one nested in it: each is charged
    its own CPU, and together they are charged what the thread used."""

    async def main():
        tr = Tracer("osd.0", config=traced_config())
        acc = tracing.loop_accounts()
        assert acc is tracing._probes[asyncio.get_running_loop()].accounts
        acc.take()
        t0 = time.thread_time()
        with tracing.account("outer"):
            burn(0.02)
            with tracing.account("inner"):
                burn(0.03)
            burn(0.01)
        used = time.thread_time() - t0
        cpu, wall, n = acc.take()
        assert cpu["outer"] == pytest.approx(0.03, abs=0.004)
        assert cpu["inner"] == pytest.approx(0.03, abs=0.004)
        assert cpu["outer"] + cpu["inner"] <= used
        assert n == {"outer": 1, "inner": 1}
        # wall holds the CPU; the two clocks are read one after the
        # other, so they may part by a clock read
        assert wall["inner"] >= cpu["inner"] - 1e-5
        tr.close()

    asyncio.run(main())


def test_disabled_account_is_a_shared_noop_that_allocates_nothing():
    async def charged():
        return 7

    decorated = tracing.accounted("x")(charged)

    async def main():
        Tracer("osd.1", config=Config())  # tracer_enabled defaults off
        assert not tracing._accounts
        assert tracing.loop_accounts() is None
        assert tracing.account("a") is tracing.account("b")
        # an accounted function hands back its own coroutine
        coro = decorated()
        assert asyncio.iscoroutine(coro)
        assert not isinstance(coro, tracing._Stepped)
        assert await coro == 7

        def sites():
            for _ in range(1000):
                with tracing.account("msg_frame"):
                    pass

        sites()  # warm
        tracemalloc.start()
        try:
            before = tracemalloc.take_snapshot()
            sites()
            after = tracemalloc.take_snapshot()
        finally:
            tracemalloc.stop()
        grown = [d for d in after.compare_to(before, "filename")
                 if d.size_diff > 0
                 and d.traceback[0].filename == tracing.__file__]
        assert grown == []

    asyncio.run(main())


def test_accounted_coroutine_charges_its_steps_never_its_waits():
    @tracing.accounted("osd")
    async def op():
        burn(0.01)
        await asyncio.sleep(0.05)
        burn(0.01)
        with tracing.account("store"):
            burn(0.01)
        return "done"

    async def main():
        tr = Tracer("osd.2", config=traced_config())
        acc = tracing.loop_accounts()
        acc.take()
        assert await op() == "done"
        assert await asyncio.create_task(op()) == "done"
        cpu, wall, n = acc.take()
        assert cpu["osd"] == pytest.approx(0.04, abs=0.006)
        assert cpu["store"] == pytest.approx(0.02, abs=0.004)
        # the two 50 ms sleeps are nobody's
        assert wall["osd"] < 0.09
        assert n["osd"] == 2 and n["store"] == 2
        tr.close()

    asyncio.run(main())


# -- the probe -----------------------------------------------------------------


def test_selector_wrapper_installed_once_per_loop_and_restored():
    async def main():
        loop = asyncio.get_running_loop()
        sel = loop._selector
        assert "select" not in vars(sel)
        a = Tracer("osd.3", config=traced_config())
        wrapper = vars(sel)["select"]
        b = Tracer("osd.4", config=traced_config())
        assert vars(sel)["select"] is wrapper
        probe = tracing._probes[loop]
        await asyncio.sleep(0.03)
        assert probe.select_s > 0.0
        a.close()
        assert vars(sel)["select"] is wrapper
        b.close()
        assert "select" not in vars(sel)
        assert loop not in tracing._probes
        assert tracing.loop_accounts() is None

    asyncio.run(main())


def test_loop_closing_under_an_enabled_tracer_drops_its_accounts():
    """A loop that closes under an enabled tracer takes its accounts and
    its selector wrappers with it."""
    seen = {}

    async def main():
        loop = asyncio.get_running_loop()
        seen["sel"] = loop._selector
        seen["tracer"] = Tracer("osd.5", config=traced_config())
        assert tracing.loop_accounts() is not None
        assert {"select", "close"} <= set(vars(seen["sel"]))

    asyncio.run(main())
    assert not tracing._accounts
    assert not {"select", "close"} & set(vars(seen["sel"]))
    assert seen["tracer"].enabled


def test_probe_records_a_loop_account_span(monkeypatch):
    monkeypatch.setattr(tracing, "LOOP_ACCOUNT_S", 0.1)

    async def main():
        tr = Tracer("osd.5", config=traced_config())

        def accounts():
            return [s for s in tr._ring if s["name"] == "loop_account"]

        while len(accounts()) < 2:
            with tracing.account("store"):
                burn(0.01)
            await asyncio.sleep(0.01)
        for sp in accounts():
            t = sp["tags"]
            assert t["busy_s"] + t["select_s"] == pytest.approx(
                sp["duration"], abs=0.01)
            assert 0 < t["thread_cpu_s"] <= t["busy_s"] * 1.05
            assert t["cpu_store_s"] <= t["thread_cpu_s"]
            assert t["n_store"] >= 1
            assert t["wall_store_s"] >= t["cpu_store_s"] * 0.9
            assert t["minflt"] >= 0 and t["proc_user_s"] >= 0
        tr.close()

    asyncio.run(main())


def test_live_cluster_loop_account_spans(monkeypatch, tmp_path):
    """EC writes through a live in-process cluster: every loop_account
    span's accounts add up to no more than the loop thread's CPU, and
    that CPU to no more than the loop's busy seconds."""
    from benchmark import spans as span_file
    from ceph_tpu.rados.client import Rados
    from tests.test_cluster_live import EC_POOL, Cluster, live_config

    monkeypatch.setattr(tracing, "LOOP_ACCOUNT_S", 0.25)
    path = str(tmp_path / "spans.jsonl")

    async def main():
        cluster = Cluster(cfg=live_config())
        await cluster.start()
        rados = Rados("client.acct", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        io = rados.io_ctx(EC_POOL)
        cluster.cfg.set("tracer_sample_rate", 1.0)
        cluster.cfg.set("tracer_export_path", path)
        cluster.cfg.set("tracer_enabled", True)
        rng = np.random.default_rng(30)
        blob = rng.integers(0, 256, 64 << 10, dtype=np.uint8).tobytes()
        end = asyncio.get_running_loop().time() + 1.5

        async def lane(i):
            j = 0
            while asyncio.get_running_loop().time() < end:
                await io.write_full(f"acct-{i}-{j}", blob[: 4096 << (j % 5)])
                j += 1

        await asyncio.gather(*(lane(i) for i in range(4)))
        cluster.cfg.set("tracer_enabled", False)
        cluster.cfg.set("tracer_export_path", "")
        await rados.shutdown()
        await cluster.stop()

    asyncio.run(asyncio.wait_for(main(), 180))
    accounts = [s for s in span_file.load_jsonl(path)
                if s["name"] == "loop_account"]
    assert len(accounts) >= 3
    names = set()
    for sp in accounts:
        t = {k: float(v) for k, v in sp["tags"].items()}
        names |= {k for k in t if k.startswith("cpu_")}
        charged = sum(v for k, v in t.items() if k.startswith("cpu_"))
        assert charged <= t["thread_cpu_s"] + 1e-6
        assert t["thread_cpu_s"] <= t["busy_s"] * 1.05
    for layer in ("msg_frame", "msg_codec", "osd", "store", "client",
                  "ec_pack", "ec_device", "ec_unpack"):
        assert f"cpu_{layer}_s" in names


# -- the benchmark's readers ---------------------------------------------------

#: a recorded CPU run of the write cell cut to what the readers read: the
#: loop_account spans and the root op_submit spans that ended in these
#: profiled seconds. Three loop_account spans lie in them whole, over
#: which 201 of the 222 ops ended and 188 launches ran; a fourth ends in
#: them but began before, and is not read.
FIXTURE = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                       "benchmark", "tests", "data",
                       "loop_account.spans.jsonl")
PROFILED = (1792450634.3365889, 1792450637.748653)
#: each worked from the fixture's tags: busy over busy + select; every
#: cpu_<account>_s over thread_cpu_s; the thread's CPU, the cpu_msg_*
#: tags, cpu_osd_s + cpu_store_s, each over the 201 ops; the three launch
#: legs' CPU, and the device leg's wall less its CPU, each over the 188
#: launches
EXPECTED = {
    "loop_busy_share": 99.43960632140258,
    "loop_cpu_named_share": 79.70887621802768,
    "host_cpu_ms_per_op": 14.761124626865676,
    "msg_cpu_ms_per_op": 4.9221754079625235,
    "osd_cpu_ms_per_op": 3.690152402985816,
    "ec_launch_cpu_ms": 0.5125681808510226,
    "ec_device_leg_wait_ms": 0.0027290159671168866,
}


def _ctx(keep=lambda s: True):
    from benchmark import spans as span_file

    t0, t1 = PROFILED
    return SimpleNamespace(
        profiled=PROFILED,
        spans=[s for s in span_file.load_jsonl(FIXTURE)
               if t0 <= s["start"] + s["duration"] <= t1 and keep(s)])


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_on_recorded_spans(metric):
    from benchmark import harness

    got = harness.load_module("metrics", metric).read(_ctx())
    assert got == pytest.approx(EXPECTED[metric], rel=1e-9)


@pytest.mark.parametrize("metric", sorted(EXPECTED))
def test_reader_finds_nothing_without_loop_account_spans(metric):
    from benchmark import harness

    ctx = _ctx(lambda s: s["name"] != "loop_account")
    assert ctx.spans
    assert harness.load_module("metrics", metric).read(ctx) is None
