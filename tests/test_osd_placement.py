"""The OSD's placement step on the batched path, in a live in-process
cluster: every `acting_of` answer equals the scalar pipeline's at every
epoch of boot and after an OSD goes down, and each map epoch costs one
whole-pool fill per pool (`placement_pool_launches`), no PG placed one
at a time (`placement_scalar_pgs`)."""

from ceph_tpu.osd import PgPool, daemon
from ceph_tpu.osd.daemon import OSDService
from ceph_tpu.osd.types import TYPE_ERASURE, TYPE_REPLICATED
from ceph_tpu.rados.client import Rados
from tests.test_cluster_live import (
    EC_POOL, REP_POOL, Cluster, initial_osdmap, run, wait_until,
)


def _count(osd, key: str) -> int:
    return osd.perf.dump()[key]


def _warm_mapper() -> None:
    """Compile the mapper for the cluster's two pools before it boots, so
    no daemon's event loop stalls on the compile."""
    m = initial_osdmap()
    m.pools[REP_POOL] = PgPool(pg_num=8, size=3, type=TYPE_REPLICATED,
                               crush_rule=1)
    m.pools[EC_POOL] = PgPool(pg_num=8, size=4, type=TYPE_ERASURE,
                              crush_rule=0)
    for pid in m.pools:
        m.pool_up_acting(pid)


def test_acting_of_fills_each_pool_once_per_epoch_from_batched_mapper(
        monkeypatch):
    monkeypatch.setattr(daemon, "batched_placement", lambda m, pid: True)
    _warm_mapper()
    real = OSDService.acting_of
    checked: set = set()
    mismatches: list = []
    fills: list = []

    def acting_of(self, pool_id, ps):
        before = _count(self, "placement_pool_launches")
        got = real(self, pool_id, ps)
        m = self.osdmap
        if _count(self, "placement_pool_launches") != before:
            fills.append((self.id, m.epoch, pool_id))
        key = (self.id, m.epoch, pool_id, ps)
        if key not in checked:
            checked.add(key)
            _up, _upp, acting, primary = m.pg_to_up_acting_osds(pool_id, ps)
            if got != (acting, primary):
                mismatches.append((key, got, (acting, primary)))
        return got

    monkeypatch.setattr(OSDService, "acting_of", acting_of)

    def place_everything(cluster):
        for osd in cluster.osds.values():
            for pid, pool in osd.osdmap.pools.items():
                for ps in range(pool.pg_num):
                    osd.acting_of(pid, ps)

    async def main():
        cluster = Cluster()
        await cluster.start()
        rados = Rados("client.place", cluster.monmap, config=cluster.cfg)
        await rados.connect()
        try:
            await cluster.create_pools(rados)
            # every PG active first: stopping an OSD mid-peering can hang
            await wait_until(lambda: sum(
                pg.active for o in cluster.osds.values()
                for pg in o.pgs.values()) >= 16)
            place_everything(cluster)
            victim = 5
            await cluster.kill_osd(victim)
            # marked down by command: its peers may not heartbeat it yet
            await rados.mon_command("osd down", {"osd": victim})
            await wait_until(lambda: all(
                o.osdmap.is_down(victim) for o in cluster.osds.values()))
            place_everything(cluster)
            # read with the fills so far: shutting down brings epochs too
            return list(fills), {
                i: (o.osdmap.epoch, _count(o, "placement_pool_launches"),
                    _count(o, "placement_scalar_pgs"))
                for i, o in cluster.osds.items()
            }
        finally:
            await rados.shutdown()
            await cluster.stop()

    fills, final = run(main())
    assert not mismatches, mismatches[:3]
    # both phases ran: boot epochs, then the epoch with osd.5 down
    assert len({k[1] for k in checked}) > 2
    assert len(fills) == len(set(fills)), "a pool filled twice in an epoch"
    for osd_id, (epoch, launches, scalar_pgs) in final.items():
        assert scalar_pgs == 0
        assert launches == sum(1 for f in fills if f[0] == osd_id)
        assert {(osd_id, epoch, REP_POOL), (osd_id, epoch, EC_POOL)} <= \
            set(fills)
