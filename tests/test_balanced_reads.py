"""Scale-out read path over the live cluster: balanced replica reads
(rados_read_policy balance/localize) and EC direct-shard reads must be
bit-identical to primary reads under seeded writes; an acting member
that cannot prove its copy current (peering, backfill, stale/cleared
marker, mid-read death) must redirect to the primary — never serve
wrong data; and a replica-side read EIO on a balanced read triggers the
primary-driven write-back repair outside scrub."""

import asyncio
import random

import pytest

from ceph_tpu.msg import Message, Policy
from ceph_tpu.rados.client import Rados
from tests.test_cluster_live import (
    EC_POOL,
    N_OSDS,
    REP_POOL,
    Cluster,
    live_config,
    wait_until,
)


def run(coro):
    return asyncio.run(asyncio.wait_for(coro, 240))


def fleet_perf(cluster, key) -> int:
    return sum(o.perf.dump()[key] for o in cluster.osds.values())


async def raw_read(rados, osd_id, pool, name, balanced=True, timeout=5.0):
    """One read aimed at a SPECIFIC daemon, bypassing the objecter's
    target selection — the deterministic probe for 'this exact member
    must redirect/serve right now'. Returns the reply payload dict."""
    objecter = rados.objecter
    tid = next(objecter._tids)
    payload = {"tid": tid, "pool": pool, "name": name, "op": "read"}
    if balanced:
        payload["balanced"] = True
    fut = asyncio.get_event_loop().create_future()
    objecter._waiters[tid] = fut
    try:
        conn = objecter.messenger.connect(
            tuple(objecter.osdmap.osd_addrs[osd_id]),
            Policy.lossless_client(),
        )
        conn.send_message(
            Message(type="osd_op", tid=tid,
                    epoch=objecter.osdmap.epoch, payload=payload)
        )
        return await asyncio.wait_for(fut, timeout)
    finally:
        objecter._waiters.pop(tid, None)


def acting_of(cluster, pool, name):
    osd = next(iter(cluster.osds.values()))
    ps = osd.object_pg(pool, name)
    return (ps, *osd.acting_of(pool, ps))


def test_balanced_and_direct_reads_bit_identical():
    """Property: for seeded writes over rep + EC pools, every read
    policy (primary, balance, localize, EC direct-shard) returns the
    same bytes — full reads, ranged reads crossing stripe bounds, stats
    — and the replica/shard fast paths actually served traffic."""

    async def main():
        cluster = Cluster()
        await cluster.start()
        rados = Rados("client.bal", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        rep = rados.io_ctx(REP_POOL)
        ec = rados.io_ctx(EC_POOL)

        rng = random.Random(1123)
        payloads = {}
        for i in range(10):
            blob = bytes(rng.randrange(256)
                         for _ in range(rng.randrange(1, 9000)))
            payloads[f"o{i}"] = blob
            await rep.write_full(f"o{i}", blob)
            await ec.write_full(f"o{i}", blob)

        # ground truth via the default primary path
        truth = {}
        for name in payloads:
            truth[("rep", name)] = await rep.read(name)
            truth[("ec", name)] = await ec.read(name)
            assert truth[("rep", name)] == payloads[name]

        for policy in ("balance", "localize"):
            rep.read_policy = policy
            ec.read_policy = policy
            for name, blob in payloads.items():
                assert await rep.read(name) == blob, (policy, name)
                assert await ec.read(name) == blob, (policy, name)
                assert (await rep.stat(name))["size"] == len(blob)
                # ranged reads, including spans crossing chunk bounds
                # and tails past EOF
                for _ in range(3):
                    off = rng.randrange(0, max(1, len(blob)))
                    ln = rng.randrange(1, 6000)
                    want = blob[off: off + ln]
                    assert await rep.read(name, off=off, length=ln) == want
                    assert await ec.read(name, off=off, length=ln) == want

        # the fast paths really carried reads: non-primary members
        # served replicated reads, data shards served EC ranges directly
        assert fleet_perf(cluster, "read_balanced") > 0
        assert fleet_perf(cluster, "read_shard_direct") > 0

        await rados.shutdown()
        await cluster.stop()

    run(main())


def test_unproven_member_redirects_never_serves():
    """A member whose activation marker is gone (the lost-broadcast /
    flapped-interval shape) must bounce balanced reads to the primary
    with a redirect reply; the op still completes with correct data and
    read_redirected climbs."""

    async def main():
        cluster = Cluster()
        await cluster.start()
        rados = Rados("client.rdr", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        rep = rados.io_ctx(REP_POOL)
        blob = b"redirect-me" * 300
        await rep.write_full("obj", blob)

        ps, acting, primary = acting_of(cluster, REP_POOL, "obj")
        replica = next(o for o in acting if o != primary)

        # the replica IS licensed after activation: a targeted balanced
        # read serves locally
        rp = await raw_read(rados, replica, REP_POOL, "obj")
        assert rp.get("ok") and rp["_raw"] == blob
        assert cluster.osds[replica].perf.dump()["read_balanced"] >= 1

        # revoke the license (exactly what a membership flap the replica
        # never saw does): the same read must now redirect, not serve
        cluster.osds[replica]._pg_of((REP_POOL, ps)).replica_marker = None
        before = cluster.osds[replica].perf.dump()["read_redirected"]
        rp = await raw_read(rados, replica, REP_POOL, "obj")
        assert rp.get("redirect") and rp.get("primary") == primary
        assert (
            cluster.osds[replica].perf.dump()["read_redirected"]
            == before + 1
        )

        # through the objecter the op degrades to the primary and still
        # returns the right bytes
        rep.read_policy = "balance"
        for _ in range(2 * len(acting)):
            assert await rep.read("obj") == blob

        await rados.shutdown()
        await cluster.stop()

    run(main())


def test_backfilling_member_redirects_and_kill_mid_read_degrades():
    """The two wrong-data hazards from the acceptance bar: a backfilling
    acting member must redirect balanced reads while it is amnesiac (it
    would otherwise serve stale/absent data), and a replica dying with
    reads in flight degrades the ops to the primary — zero wrong reads
    in both."""

    async def main():
        cluster = Cluster()
        await cluster.start()
        rados = Rados("client.bkf", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        rep = rados.io_ctx(REP_POOL)
        ec = rados.io_ctx(EC_POOL)

        data = {}
        for i in range(6):
            data[f"k{i}"] = bytes([i + 1]) * (1200 + 311 * i)
            await rep.write_full(f"k{i}", data[f"k{i}"])
            await ec.write_full(f"k{i}", data[f"k{i}"])

        # -- kill a replica with balanced reads in flight ----------------
        ps, acting, primary = acting_of(cluster, REP_POOL, "k0")
        victim = next(o for o in acting if o != primary)
        rep.read_policy = "balance"
        ec.read_policy = "balance"
        reads = [
            asyncio.ensure_future(rep.read(f"k{i}")) for i in range(6)
        ]
        await cluster.kill_osd(victim)  # conns drop mid-op (kill -9)
        got = await asyncio.gather(*reads)
        for i, blob in enumerate(got):
            assert blob == data[f"k{i}"], f"wrong bytes for k{i}"
        # the dead member keeps timing out until the mon marks it down;
        # every read keeps degrading to the primary and stays correct
        leader = next(m for m in cluster.mons if m.is_leader)
        await wait_until(lambda: leader.osdmap.is_down(victim), timeout=30)
        for i in range(6):
            assert await rep.read(f"k{i}") == data[f"k{i}"]
            assert await ec.read(f"k{i}") == data[f"k{i}"]

        # new versions while the victim is down: the revived store must
        # never serve these objects until its backfill drains
        for i in range(6):
            data[f"k{i}"] = bytes([i + 101]) * (900 + 97 * i)
            await rep.write_full(f"k{i}", data[f"k{i}"])

        # -- amnesiac revival: reads stay correct through backfill -------
        reborn = await cluster.start_osd(victim)

        # park the license: drop every pg_activate grant the reborn
        # member receives, so the amnesiac window is deterministic
        # instead of a race against a six-object backfill that can
        # drain in milliseconds
        async def park_activate(conn, p):
            reborn._reply_peer(conn, p, {"ok": True})

        reborn._h_pg_activate = park_activate
        await wait_until(
            lambda: leader.osdmap.osd_up[victim]
            and not leader.osdmap.is_down(victim),
            timeout=30,
        )
        # the targeted probe needs the victim's NEW address; the
        # objecter's map rides the mon subscription
        await _wait_async(
            _async_pred(
                lambda: not rados.objecter.osdmap.is_down(victim)
                and tuple(rados.objecter.osdmap.osd_addrs[victim])
                == tuple(leader.osdmap.osd_addrs[victim])
            ),
            timeout=30,
        )
        redirected = 0
        for _round in range(12):
            for i in range(6):
                assert await rep.read(f"k{i}") == data[f"k{i}"], (
                    f"stale read of k{i} during backfill"
                )
            # the member is provably amnesiac (no marker): a targeted
            # balanced read must redirect, never serve
            rp = await raw_read(rados, victim, REP_POOL, "k0")
            assert rp.get("redirect"), (
                "unlicensed member served a balanced read"
            )
            redirected += 1
            pg = reborn._pg_of((REP_POOL, ps))
            if not pg.self_backfill and _round >= 2:
                break
        assert redirected > 0, "never caught the member backfilling"
        assert fleet_perf(cluster, "read_redirected") >= redirected

        # un-park: restore the class handler, wait for the backfill to
        # drain, and have the primary re-vouch for the interval
        del reborn._h_pg_activate

        async def drained():
            return not reborn._pg_of((REP_POOL, ps)).self_backfill

        await _wait_async(drained, timeout=30)
        ps2, acting2, primary2 = acting_of(cluster, REP_POOL, "k0")
        ppg = cluster.osds[primary2]._pg_of((REP_POOL, ps2))
        await cluster.osds[primary2]._broadcast_activate(
            ppg, list(acting2)
        )

        # after recovery settles the revived member serves again
        async def licensed():
            pg = reborn._pg_of((REP_POOL, ps))
            return pg.replica_marker is not None and not pg.self_backfill

        await _wait_async(licensed, timeout=30)
        for i in range(6):
            assert await rep.read(f"k{i}") == data[f"k{i}"]

        await rados.shutdown()
        await cluster.stop()

    run(main())


async def _wait_async(pred, timeout=30.0):
    """wait_until for async predicates (marker grants arrive on peer
    dispatch, so ride the same event hook)."""
    from ceph_tpu.msg.messenger import next_dispatch_event

    loop = asyncio.get_event_loop()
    end = loop.time() + timeout
    while not await pred():
        remaining = end - loop.time()
        if remaining <= 0:
            raise TimeoutError
        fut = next_dispatch_event()
        try:
            await asyncio.wait_for(fut, min(0.25, remaining))
        except asyncio.TimeoutError:
            pass


@pytest.mark.slow
def test_replica_read_error_triggers_primary_repair():
    """EIO on a replica serving a balanced read: the client is redirected
    (and still gets the right bytes from the primary) while the replica
    reports the rot; the primary pushes a verified copy back OUTSIDE
    scrub — read_error_repaired climbs and the cluster log says so."""

    async def main():
        cfg = live_config()
        cfg.set("osd_objectstore", "blockstore")
        cfg.set("blockstore_buffer_cache_bytes", 0)

        def mk():
            c = live_config()
            c.set("osd_objectstore", "blockstore")
            c.set("blockstore_buffer_cache_bytes", 0)
            return c

        cluster = Cluster(
            cfg=cfg, osd_configs={i: mk() for i in range(N_OSDS)}
        )
        await cluster.start()
        rados = Rados("client.heal", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        rep = rados.io_ctx(REP_POOL)
        blob = b"\xabhealme" * 700
        await rep.write_full("rot", blob)

        ps, acting, primary = acting_of(cluster, REP_POOL, "rot")
        sick = next(o for o in acting if o != primary)
        await rados.objecter.osd_admin(
            sick, "injectdataerr", {"pool": REP_POOL, "name": "rot"}
        )

        # the targeted balanced read redirects (never serves the rotten
        # copy) and fires the report; the primary heals by push
        rp = await raw_read(rados, sick, REP_POOL, "rot")
        assert rp.get("redirect")
        await _wait_async(
            _async_pred(
                lambda: cluster.osds[primary].perf.dump()[
                    "read_error_repaired"
                ] >= 1
            ),
            timeout=30,
        )
        # healed in place: the replica's copy reads clean again and a
        # licensed balanced read serves it
        assert cluster.osds[sick].store.read(f"pg_{REP_POOL}_{ps}",
                                             "rot") == blob
        rp = await raw_read(rados, sick, REP_POOL, "rot")
        assert rp.get("ok") and rp["_raw"] == blob

        # the heal is an operator-visible event
        out = await rados.mon_command("log last", {"n": 50})
        assert any(
            "healed by primary push" in l["message"]
            for l in out["lines"]
        )

        rep.read_policy = "balance"
        for _ in range(6):
            assert await rep.read("rot") == blob

        await rados.shutdown()
        await cluster.stop()

    run(main())


def _async_pred(sync_pred):
    async def p():
        return sync_pred()

    return p

def test_backfill_hint_spares_redirect_round_trips():
    """Satellite fix: balanced reads against a PG with backfill in
    progress used to pay one redirect round-trip per read that landed
    on the backfill target.  The redirect reply now carries the
    marker's backfill set, the objecter caches it, and subsequent
    balanced reads go straight to clean acting members — the
    read_redirected counter stays FLAT while reads keep flowing."""

    async def main():
        # aggressive log trim: the writes below push h0's PG log past
        # the amnesiac member's position 0, so revival MUST backfill
        # (log recovery would drain instantly and close the window)
        cfg = live_config()
        cfg.set("osd_min_pg_log_entries", 20)
        # the measurement below must not race the hint's expiry: on a
        # loaded box 80 priming + 40 measured reads can outlast the
        # default 10 s TTL, and the expiry re-probe is one legitimate
        # redirect that would fail the flat-counter assertion
        cfg.set("rados_backfill_hint_ttl", 600.0)
        cluster = Cluster(cfg=cfg)
        await cluster.start()
        rados = Rados("client.hint", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        await cluster.create_pools(rados)
        rep = rados.io_ctx(REP_POOL)

        data = {}
        for i in range(8):
            data[f"h{i}"] = bytes([i + 1]) * (800 + 131 * i)
            await rep.write_full(f"h{i}", data[f"h{i}"])

        ps, acting, primary = acting_of(cluster, REP_POOL, "h0")
        victim = next(o for o in acting if o != primary)
        await cluster.kill_osd(victim)
        leader = next(m for m in cluster.mons if m.is_leader)
        await wait_until(lambda: leader.osdmap.is_down(victim), timeout=30)
        for round_ in range(30):
            for i in range(8):
                data[f"h{i}"] = bytes([(i + round_) % 251 + 1]) * 700
                await rep.write_full(f"h{i}", data[f"h{i}"])

        # amnesiac revival with recovery PARKED on the reborn member:
        # pushes are swallowed and pulls fail, so the PG deterministically
        # stays a backfill-in-progress PG for the whole measurement
        reborn = await cluster.start_osd(victim)

        async def swallow(conn, p):
            return None  # no ack: the source retries forever

        reborn._h_obj_push_batch = swallow
        reborn._h_obj_push = swallow

        async def no_pull(*a, **kw):
            return None

        reborn._pull_object = no_pull
        await wait_until(
            lambda: leader.osdmap.osd_up[victim]
            and not leader.osdmap.is_down(victim),
            timeout=30,
        )

        # wait until the reborn member holds a marker that PROVES it is
        # a backfill target (the redirect hint's source of truth)
        def marked():
            pg = reborn.pgs.get((REP_POOL, ps))
            mk = pg.replica_marker if pg else None
            return bool(mk and victim in (mk.get("backfill") or ()))

        await _wait_async(_async_pred(marked), timeout=30)

        # prime: balanced reads of h0 run until one lands on the
        # backfill target and comes back with the redirect + hint
        rep.read_policy = "balance"
        for _ in range(80):
            assert await rep.read("h0") == data["h0"]
            if (REP_POOL, ps) in rados.objecter._avoid_cache:
                break
        assert (REP_POOL, ps) in rados.objecter._avoid_cache, (
            "the redirect reply never delivered a backfill hint"
        )

        # measure (h0's PG only — the hint is cached per PG): with the
        # avoid set cached, NO further read pays a redirect round-trip —
        # the counter stays flat while balanced reads keep serving from
        # clean members
        before_rdr = fleet_perf(cluster, "read_redirected")
        before_bal = fleet_perf(cluster, "read_balanced")
        for _round in range(40):
            assert await rep.read("h0") == data["h0"]
        assert fleet_perf(cluster, "read_redirected") == before_rdr, (
            "reads kept bouncing off the backfill target despite the hint"
        )
        assert fleet_perf(cluster, "read_balanced") > before_bal

        await rados.shutdown()
        await cluster.stop()

    run(main())
