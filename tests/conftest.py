"""Test harness config: run JAX on a virtual 8-device CPU mesh.

Tests run on the CPU on purpose: interpret-mode Pallas and the 8-device
mesh are what tier-1 checks, the driver runs them with JAX_PLATFORMS=cpu,
and a test process must never take the chip. Chip runs go through
chip_smoke.py; tests/test_chip_compile.py compiles the kernels for a
described v5e chip without one.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running live tests excluded from the tier-1 "
        "budgeted run (-m 'not slow')",
    )
    # runtime race/leak detector rides every tier-1 run (cheap: lock
    # bookkeeping + task weakrefs); CEPH_TPU_RACECHECK=0 opts out
    if os.environ.get("CEPH_TPU_RACECHECK", "1") not in ("", "0"):
        from ceph_tpu.lint import racecheck

        racecheck.install()


import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _racecheck_clean():
    """Fail the run (in teardown, so every test still executes) when the
    session accumulated lock-order inversions or unawaited-task leaks."""
    yield
    from ceph_tpu.lint import racecheck

    if racecheck.active():
        try:
            racecheck.assert_clean()
        finally:
            racecheck.uninstall()


def make_mini_cluster(
    n_hosts=6,
    osds_per_host=2,
    pools=(("ec", 1, {"plugin": "tpu", "k": "2", "m": "2"}, 4),),
):
    """Shared MiniCluster builder: straw2 hosts under one root, an indep rule
    (id 0) and a firstn rule (id 1), pools as (kind, pool_id, profile|None,
    size) tuples — kind "ec" uses the indep rule, "rep" the firstn rule."""
    from ceph_tpu.crush import builder as cb
    from ceph_tpu.crush.types import BucketAlg, CrushMap, Tunables
    from ceph_tpu.osd import OSDMap, PgPool
    from ceph_tpu.osd.types import TYPE_ERASURE, TYPE_REPLICATED
    from ceph_tpu.rados import MiniCluster

    cmap = CrushMap(tunables=Tunables.jewel())
    host_ids, host_ws, osd = [], [], 0
    for h in range(n_hosts):
        items = list(range(osd, osd + osds_per_host))
        osd += osds_per_host
        b = cb.make_bucket(
            cmap, -(h + 2), BucketAlg.STRAW2, 1, items,
            [0x10000] * osds_per_host,
        )
        host_ids.append(b.id)
        host_ws.append(b.weight)
    cb.make_bucket(cmap, -1, BucketAlg.STRAW2, 10, host_ids, host_ws)
    cb.make_simple_rule(cmap, 0, -1, 1, "indep", 0)
    cb.make_simple_rule(cmap, 1, -1, 1, "firstn", 0)
    m = OSDMap(crush=cmap, max_osd=cmap.max_devices)
    profiles = {}
    for kind, pool_id, profile, size in pools:
        if kind == "ec":
            m.pools[pool_id] = PgPool(
                pg_num=16, size=size, type=TYPE_ERASURE, crush_rule=0
            )
        else:
            m.pools[pool_id] = PgPool(
                pg_num=16, size=size, type=TYPE_REPLICATED, crush_rule=1
            )
        profiles[pool_id] = profile
    return MiniCluster(osdmap=m, profiles=profiles)
