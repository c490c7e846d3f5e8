"""OSDMap placement-pipeline tests.

Reference: /root/reference/src/osd/OSDMap.cc (_pg_to_raw_osds 2359,
_apply_upmap 2389, _raw_to_up_osds 2436, _apply_primary_affinity 2460,
_pg_to_up_acting_osds 2591, calc_pg_upmaps 4512) and
src/osd/osd_types.cc:1640 raw_pg_to_pps. The scalar pipeline IS the spec
here (it's a line-by-line re-expression); the batched TPU path is asserted
identical to it, and behavioral properties (override semantics, down/out
handling, balancing) are asserted directly.
"""

import numpy as np
import pytest

from ceph_tpu.crush import builder as cb
from ceph_tpu.crush.types import (
    BucketAlg, CrushMap, RuleOp, RuleStep, Tunables,
)
from ceph_tpu.osd import OSDMap, PgPool, ceph_stable_mod, pg_num_mask
from ceph_tpu.osd.osdmap import CRUSH_ITEM_NONE
from ceph_tpu.osd.types import TYPE_ERASURE, TYPE_REPLICATED


def build_cluster(n_hosts=6, per_host=4, seed=7):
    """hosts of straw2 osds under a straw2 root, plus firstn + indep rules."""
    cmap = CrushMap(tunables=Tunables.jewel())
    rng = np.random.default_rng(seed)
    host_ids, host_weights = [], []
    osd = 0
    bid = -2
    for h in range(n_hosts):
        items = list(range(osd, osd + per_host))
        osd += per_host
        ws = [0x10000] * per_host
        b = cb.make_bucket(cmap, bid, BucketAlg.STRAW2, 1, items, ws)
        host_ids.append(b.id)
        host_weights.append(b.weight)
        bid -= 1
    cb.make_bucket(cmap, -1, BucketAlg.STRAW2, 10, host_ids, host_weights)
    cb.make_simple_rule(cmap, 0, -1, 1, "firstn", 0)
    cb.make_simple_rule(cmap, 1, -1, 1, "indep", 0)
    # an erasure rule that emits 3 slots whatever the pool's size
    cb.make_rule(cmap, 2, [
        RuleStep(RuleOp.TAKE, -1),
        RuleStep(RuleOp.CHOOSELEAF_INDEP, 3, 1),
        RuleStep(RuleOp.EMIT),
    ], rule_type=3)
    del rng
    return cmap


def build_osdmap(**pool_kw):
    cmap = build_cluster()
    m = OSDMap(crush=cmap, max_osd=cmap.max_devices)
    m.pools[1] = PgPool(pg_num=32, size=3, type=TYPE_REPLICATED, crush_rule=0)
    m.pools[2] = PgPool(
        pg_num=32, size=5, type=TYPE_ERASURE, crush_rule=1, **pool_kw
    )
    return m


def test_stable_mod_and_mask():
    # include/rados.h:86: b=12 -> bmask=15, b=123 -> bmask=127
    assert pg_num_mask(12) == 15
    assert pg_num_mask(123) == 127
    assert pg_num_mask(8) == 7
    for b in (5, 8, 12, 123):
        mask = pg_num_mask(b)
        for x in range(500):
            got = ceph_stable_mod(x, b, mask)
            assert 0 <= got < b


def test_pps_vectorized_matches_scalar():
    pool = PgPool(pg_num=64, pgp_num=48, size=3)
    ps = np.arange(200)
    vec = pool.raw_pg_to_pps_np(5, ps)
    for i in range(200):
        assert int(vec[i]) == pool.raw_pg_to_pps(5, int(ps[i]))


def test_up_acting_basics():
    m = build_osdmap()
    for ps in range(32):
        up, up_primary, acting, acting_primary = m.pg_to_up_acting_osds(1, ps)
        assert len(up) == 3
        assert len(set(up)) == 3  # distinct hosts -> distinct osds
        assert up_primary == up[0]
        assert acting == up and acting_primary == up_primary
    up, up_primary, acting, _ = m.pg_to_up_acting_osds(2, 3)
    assert len(up) == 5


def test_batched_matches_scalar_replicated_and_erasure():
    m = build_osdmap()
    m.mark_down(5)
    m.mark_out(9)
    for pid in (1, 2):
        batched = m.pool_mappings(pid)
        pool = m.pools[pid]
        assert batched.shape == (pool.pg_num, pool.size)
        for ps in range(pool.pg_num):
            up, *_ = m.pg_to_up_acting_osds(pid, ps)
            want = np.full(pool.size, CRUSH_ITEM_NONE, np.int32)
            want[: len(up)] = up
            assert np.array_equal(batched[ps], want), (pid, ps)


def test_down_osd_leaves_hole_in_erasure_up():
    m = build_osdmap()
    # find a PG mapped onto osd 0 then take it down
    target = None
    for ps in range(32):
        up, *_ = m.pg_to_up_acting_osds(2, ps)
        if 0 in up:
            target = ps, up.index(0)
            break
    assert target is not None
    m.mark_down(0)
    ps, pos = target
    up, *_ = m.pg_to_up_acting_osds(2, ps)
    assert up[pos] == CRUSH_ITEM_NONE  # positional hole (can_shift_osds false)
    rep_up, *_ = m.pg_to_up_acting_osds(1, ps)
    assert 0 not in rep_up and CRUSH_ITEM_NONE not in rep_up


def test_pg_upmap_full_override_and_out_target():
    m = build_osdmap()
    up0, *_ = m.pg_to_up_acting_osds(1, 4)
    override = [o for o in range(m.max_osd) if o not in up0][:3]
    m.pg_upmap[(1, 4)] = override
    up, *_ = m.pg_to_up_acting_osds(1, 4)
    assert up == override
    # marked-out target invalidates the whole explicit mapping
    m.mark_out(override[0])
    up, *_ = m.pg_to_up_acting_osds(1, 4)
    assert up == up0


def test_pg_upmap_items_swap():
    m = build_osdmap()
    up0, *_ = m.pg_to_up_acting_osds(1, 7)
    frm = up0[1]
    to = next(o for o in range(m.max_osd) if o not in up0)
    m.pg_upmap_items[(1, 7)] = [(frm, to)]
    up, *_ = m.pg_to_up_acting_osds(1, 7)
    assert up[1] == to and frm not in up
    # no-op when the target already appears in the set
    m.pg_upmap_items[(1, 7)] = [(frm, up0[0])]
    up, *_ = m.pg_to_up_acting_osds(1, 7)
    assert up == up0
    # batched path honors overrides identically
    m.pg_upmap_items[(1, 7)] = [(frm, to)]
    batched = m.pool_mappings(1)
    scal, *_ = m.pg_to_up_acting_osds(1, 7)
    assert list(batched[7][: len(scal)]) == scal


def test_pg_temp_and_primary_temp():
    m = build_osdmap()
    up0, up_primary0, *_ = m.pg_to_up_acting_osds(1, 9)
    temp = [o for o in range(m.max_osd) if o not in up0][:3]
    m.pg_temp[(1, 9)] = temp
    up, up_primary, acting, acting_primary = m.pg_to_up_acting_osds(1, 9)
    assert up == up0 and up_primary == up_primary0  # up unaffected
    assert acting == temp and acting_primary == temp[0]
    m.primary_temp[(1, 9)] = temp[2]
    *_, acting_primary = m.pg_to_up_acting_osds(1, 9)
    assert acting_primary == temp[2]


def test_primary_affinity_zero_never_primary():
    m = build_osdmap()
    m.osd_primary_affinity = np.full(m.max_osd, 0x10000, np.int64)
    victim = m.pg_to_up_acting_osds(1, 0)[1]  # whoever leads PG (1, 0)
    victim_pgs = [
        ps for ps in range(32)
        if m.pg_to_up_acting_osds(1, ps)[1] == victim
    ]
    assert victim_pgs
    m.osd_primary_affinity[victim] = 0
    for ps in victim_pgs:
        up, up_primary, *_ = m.pg_to_up_acting_osds(1, ps)
        assert up_primary != victim
        assert victim in up  # still serves the PG, just not as primary


def test_topology_change_remaps_deterministically():
    """Elastic recovery contract: placement is a pure function of the map."""
    m = build_osdmap()
    before = m.pool_mappings(1).copy()
    m.mark_down(2)
    after = m.pool_mappings(1)
    again = m.pool_mappings(1)
    assert np.array_equal(after, again)
    assert not np.array_equal(before, after)
    m.mark_up(2)
    restored = m.pool_mappings(1)
    assert np.array_equal(before, restored)


def test_calc_pg_upmaps_reduces_deviation():
    m = build_osdmap()
    # skew load: cut one host's osds out of crush weighting via reweight
    pool = m.pools[1]

    def deviations():
        counts = np.zeros(m.max_osd)
        ups = m.pool_mappings(1)
        for row in ups:
            for o in row:
                if o != CRUSH_ITEM_NONE:
                    counts[int(o)] += 1
        weights = m.osd_weight * (m.osd_exists & m.osd_up)
        target = weights / weights.sum() * pool.pg_num * pool.size
        return counts - target

    before = np.abs(deviations()).max()
    changed = m.calc_pg_upmaps(max_deviation=1.0, max_changes=24, pools={1})
    after_dev = deviations()
    assert changed > 0
    assert np.abs(after_dev).max() <= max(before, 1.0)
    assert np.abs(after_dev).max() < before
    # upmapped sets stay duplicate-free and fully mapped
    for ps in range(pool.pg_num):
        up, *_ = m.pg_to_up_acting_osds(1, ps)
        assert len(up) == 3 and len(set(up)) == 3


def test_pg_temp_erasure_keeps_positional_holes():
    """_get_temp_osds on a non-shifting pool NONEs dead members in place
    (OSDMap.cc:2524-2529) so shard offsets survive."""
    m = build_osdmap()
    temp = [1, 2, 3, 5, 6]
    m.pg_temp[(2, 0)] = temp
    m.mark_down(2)
    _, _, acting, _ = m.pg_to_up_acting_osds(2, 0)
    assert acting == [1, CRUSH_ITEM_NONE, 3, 5, 6]
    # replicated pools compact instead
    m.pg_temp[(1, 0)] = [1, 2, 3]
    _, _, acting, _ = m.pg_to_up_acting_osds(1, 0)
    assert acting == [1, 3]


def test_rejected_pg_upmap_short_circuits_items():
    """An out target in pg_upmap invalidates the override AND skips
    pg_upmap_items entirely (OSDMap.cc:2395-2400 returns early)."""
    m = build_osdmap()
    up0, *_ = m.pg_to_up_acting_osds(1, 4)
    override = [o for o in range(m.max_osd) if o not in up0][:3]
    other = next(o for o in range(m.max_osd) if o not in up0 + override)
    m.pg_upmap[(1, 4)] = override
    m.pg_upmap_items[(1, 4)] = [(up0[1], other)]
    m.mark_out(override[0])
    up, *_ = m.pg_to_up_acting_osds(1, 4)
    assert up == up0  # untouched: no override, no item swap


def test_batched_matches_scalar_with_primary_affinity():
    m = build_osdmap()
    m.osd_primary_affinity = np.full(m.max_osd, 0x10000, np.int64)
    victim = m.pg_to_up_acting_osds(1, 0)[1]
    m.osd_primary_affinity[victim] = 0
    batched = m.pool_mappings(1)
    for ps in range(32):
        up, *_ = m.pg_to_up_acting_osds(1, ps)
        want = np.full(3, CRUSH_ITEM_NONE, np.int32)
        want[: len(up)] = up
        assert np.array_equal(batched[ps], want), ps


#: the map changes pool_up_acting is held to the scalar pipeline across,
#: applied in order (each case replays the sequence up to its step)
PLACEMENT_STEPS = (
    "boot", "down", "out", "pg_temp", "primary_temp", "pg_upmap_items",
    "primary_affinity", "split",
)
#: pool id -> what it exercises: a replicated pool (firstn, left-compacted
#: up sets), an erasure pool (indep, positional NONE holes), and an erasure
#: pool whose rule emits 3 of its 5 slots (the rows' emitted length)
PLACEMENT_POOLS = {1: "replicated", 2: "erasure", 3: "erasure-short-rule"}


def _placement_map():
    m = build_osdmap()
    m.pools[3] = PgPool(pg_num=32, size=5, type=TYPE_ERASURE, crush_rule=2)
    # pg_num 24 (not a power of two) until the split takes it to 32
    for pool in m.pools.values():
        pool.pg_num = pool.pgp_num = 24
    # osd.22 and osd.23 are in the crush map but not yet booted
    m.osd_exists[22:] = False
    m.osd_up[22:] = False
    return m


def _first_not_in(m, osds):
    return next(
        o for o in range(m.max_osd)
        if o not in osds and not m.is_down(o) and m.osd_weight[o]
    )


def _apply_placement_step(m, step: str) -> None:
    if step == "boot":
        # osd.22 boots; osd.23 stays nonexistent for the rest
        m.osd_exists[22] = True
        m.mark_up(22)
    elif step == "down":
        m.mark_down(5)
    elif step == "out":
        m.mark_out(9)
    elif step == "pg_temp":
        for pid, pool in m.pools.items():
            up = m.pg_to_up_acting_osds(pid, 3)[0]
            # the down osd.5 leads: compacted away or left as a hole
            temp = [5]
            while len(temp) < pool.size:
                temp.append(_first_not_in(m, up + temp))
            m.pg_temp[(pid, 3)] = temp
    elif step == "primary_temp":
        for pid in m.pools:
            m.primary_temp[(pid, 3)] = m.pg_temp[(pid, 3)][2]
            live = [o for o in m.pg_to_up_acting_osds(pid, 4)[0]
                    if o != CRUSH_ITEM_NONE]
            m.primary_temp[(pid, 4)] = live[-1]
    elif step == "pg_upmap_items":
        for pid in m.pools:
            up = m.pg_to_up_acting_osds(pid, 7)[0]
            frm = next(o for o in up if o != CRUSH_ITEM_NONE)
            m.pg_upmap_items[(pid, 7)] = [(frm, _first_not_in(m, up))]
        up = m.pg_to_up_acting_osds(1, 8)[0]
        m.pg_upmap[(1, 8)] = [_first_not_in(m, up), up[0], up[1]]
    elif step == "primary_affinity":
        m.osd_primary_affinity = np.full(m.max_osd, 0x10000, np.int64)
        m.osd_primary_affinity[0] = 0
        m.osd_primary_affinity[1] = 0x8000
        m.osd_primary_affinity[12] = 0x4000
        m.epoch += 1
    elif step == "split":
        # pg_num grows ahead of pgp_num, as a split begins
        for pool in m.pools.values():
            pool.pg_num = 32
        m.epoch += 1


@pytest.mark.parametrize("step", PLACEMENT_STEPS)
@pytest.mark.parametrize(
    "pool_id", list(PLACEMENT_POOLS), ids=list(PLACEMENT_POOLS.values())
)
def test_pool_up_acting_matches_scalar_across_map_changes(pool_id, step):
    """The whole-pool placement an OSD fills its per-epoch cache from is
    the scalar pipeline's answer for every PG, after every kind of map
    change, from the batched mapper."""
    from ceph_tpu.crush import jax_mapper

    m = _placement_map()
    for s in PLACEMENT_STEPS[: PLACEMENT_STEPS.index(step) + 1]:
        _apply_placement_step(m, s)
    pool = m.pools[pool_id]
    ruleno = m.find_rule(pool.crush_rule, pool.type, pool.size)
    assert jax_mapper.supports(m.crush, ruleno)
    want = [m.pg_to_up_acting_osds(pool_id, ps)
            for ps in range(pool.pg_num)]
    assert m.pool_up_acting(pool_id) == want
