"""Multi-process cluster tier: real fork+exec'd daemons (the reference's
vstart.sh + qa/standalone deployment shape).

Every mon and OSD here is its own OS process with its own interpreter,
event loop, and FileDB; the test process is a pure client.  Covers the
full lifecycle the single-process live tier can't honestly claim: boot
over TCP between interpreters, IO on replicated + EC pools, SIGKILL crash
of an OSD (no cooperative stop()), failure detection -> map epoch -> op
re-target, and revival of the SAME daemon identity over its surviving
store (ceph-osd restart semantics).
"""

import asyncio
import json
import os
import signal

import pytest

from ceph_tpu.vstart import VStart

REP_POOL = 1
EC_POOL = 2


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 180))


async def wait_until(pred, timeout=60.0):
    loop = asyncio.get_event_loop()
    end = loop.time() + timeout
    while not pred():
        if loop.time() > end:
            raise TimeoutError
        await asyncio.sleep(0.1)


async def connect_client(vstart, tries=6):
    """Daemon processes spend seconds importing jax before binding; retry
    the initial map fetch instead of racing their interpreter startup."""
    last = None
    for _ in range(tries):
        r = vstart.client()
        try:
            await r.connect()
            return r
        except Exception as e:  # noqa: BLE001 - retried, re-raised at end
            last = e
            await r.shutdown()
            await asyncio.sleep(2)
    raise last


async def create_pools(rados):
    await rados.mon_command(
        "osd erasure-code-profile set",
        {"name": "k2m2", "profile": {"plugin": "tpu", "k": "2", "m": "2"}},
    )
    await rados.mon_command(
        "osd pool create",
        {"pool_id": REP_POOL, "crush_rule": 1, "size": 3, "pg_num": 8},
    )
    await rados.mon_command(
        "osd pool create",
        {"pool_id": EC_POOL, "crush_rule": 0,
         "erasure_code_profile": "k2m2", "pg_num": 8},
    )


@pytest.fixture
def vstart(tmp_path):
    v = VStart(str(tmp_path), n_mons=3, n_osds=5)
    v.start()
    yield v
    v.stop()


def test_multiprocess_io_round_trip(vstart):
    """Boot 3 mons + 5 OSDs as real processes; write/read/delete on a
    replicated and an EC pool from a client in the test process."""

    async def main():
        r = await connect_client(vstart)
        await vstart.wait_healthy(rados=r)
        await create_pools(r)
        rep = r.io_ctx(REP_POOL)
        ec = r.io_ctx(EC_POOL)
        payload = os.urandom(1 << 15)
        await rep.write_full("rep-obj", payload)
        await ec.write_full("ec-obj", payload)
        assert await rep.read("rep-obj") == payload
        assert await ec.read("ec-obj") == payload
        await rep.remove("rep-obj")
        from ceph_tpu.rados.client import ObjectNotFound

        with pytest.raises(ObjectNotFound):
            await rep.read("rep-obj")
        # every daemon is really a distinct OS process
        pids = {p.pid for p in vstart.mons.values()} | {
            p.pid for p in vstart.osds.values()
        }
        assert len(pids) == 8
        assert os.getpid() not in pids
        await r.shutdown()

    run(main())


def test_multiprocess_osd_crash_and_revival(vstart):
    """SIGKILL one OSD process: the survivors report it, the mons mark it
    down, ops re-target; then the same identity reboots over its surviving
    FileDB and rejoins (peering brings it back to consistency)."""

    async def main():
        r = await connect_client(vstart)
        await vstart.wait_healthy(rados=r)
        await create_pools(r)
        rep = r.io_ctx(REP_POOL)
        payload = os.urandom(1 << 14)
        for i in range(6):
            await rep.write_full(f"pre-{i}", payload)

        # crash the primary of pre-0's PG for maximum disruption
        victim = r.objecter._calc_target(REP_POOL, "pre-0")
        vstart.kill_osd(victim, sig=signal.SIGKILL)

        await wait_until(
            lambda: r.objecter.osdmap is not None
            and not r.objecter.osdmap.osd_up[victim],
            timeout=90,
        )
        # ops re-target away from the dead process and still serve
        assert await rep.read("pre-0") == payload
        await rep.write_full("during-outage", payload)

        # revive: same id, same FileDB directory, brand-new process
        vstart.start_osd(victim)
        await vstart.wait_healthy(rados=r, timeout=90)
        assert await rep.read("during-outage") == payload
        assert await rep.read("pre-0") == payload
        await r.shutdown()

    run(main())


def test_multiprocess_full_stack_mds_rgw_mgr(vstart):
    """The whole service tier as real processes: MDS (cephfs), RGW (S3
    over HTTP), and mgr (dashboard HTTP) daemons join the multi-process
    cluster; a client in the test process drives all three."""

    async def main():
        vstart.spec.extras.update({
            "mds_data_pool": REP_POOL,
            "rgw_data_pool": EC_POOL,
            "rgw_index_pool": REP_POOL,
            "rgw_users": {"AKMP": "multiprocess-secret"},
        })
        vstart.spec.save(vstart.spec_path)
        r = await connect_client(vstart)
        await vstart.wait_healthy(rados=r)
        await create_pools(r)
        vstart.start_daemon("mds", 0)
        vstart.start_daemon("rgw", 0)
        vstart.start_daemon("mgr", 0)

        # -- CephFS against the MDS process (interpreter startup takes
        # seconds: wait for its beacon to claim the active rank)
        from ceph_tpu.cephfs import CephFSClient

        async def mds_active():
            fm = (await r.mon_command("fs map"))["fsmap"]
            return fm.get("active") is not None

        end = asyncio.get_event_loop().time() + 90
        while not await mds_active():
            assert asyncio.get_event_loop().time() < end, "no MDS"
            await asyncio.sleep(0.5)

        fs = CephFSClient(r, REP_POOL)
        await fs.mount()
        await fs.mkfs()
        await fs.mkdir("/docs")
        await fs.write_file("/docs/hello", b"multi-process fs")
        assert await fs.read_file("/docs/hello") == b"multi-process fs"

        # -- S3 against the RGW process (real HTTP + SigV4)
        from tests.test_s3_rest import MiniS3Client

        s3_port = vstart.daemon_port("rgw", 0)
        c = MiniS3Client(
            "127.0.0.1", s3_port, "AKMP", "multiprocess-secret"
        )
        st, _, _ = await c.request("PUT", "/bucket")
        assert st == 200
        st, _, _ = await c.request(
            "PUT", "/bucket/obj", payload=b"s3 across processes"
        )
        assert st == 200
        st, _, body = await c.request("GET", "/bucket/obj")
        assert st == 200 and body == b"s3 across processes"

        # -- dashboard against the mgr process
        from tests.test_s3_auth_ext import raw_http

        mgr_port = vstart.daemon_port("mgr", 0)
        st, _, body = await raw_http(
            "127.0.0.1", mgr_port, "GET", "/api/status"
        )
        assert st == 200
        doc = json.loads(body)
        assert doc["cluster"]["num_osds"] == 5
        assert doc["mgrmap"]["active"] == "mgr.0"

        # every service really is its own OS process
        assert len(vstart.extra) == 3
        assert all(
            p.poll() is None for p in vstart.extra.values()
        )
        await r.shutdown()

    run(main())
