"""Compiles of the main path's kernels for a described TPU v5e chip.

No chip is attached here: the TPU compiler builds for a topology that is
only described, and refuses what the chip would refuse (a kernel that
does not lower, a tile over the VMEM budget, a program over HBM). Each
compile runs with jax_enable_x64 on, the dtype rules of a process that
serves CRUSH placement (vstart.daemon_main, chip_smoke.py).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every xdist worker imports this
file. Compile-cache reads are off around these compiles, since a program
compiled for a described chip cannot be read back without one.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from ceph_tpu.ec.registry import factory
from ceph_tpu.ops import gf_pallas as gp

#: HBM of one v5e chip (Google Cloud, "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache_reads():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def x64():
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", True)
    yield
    jax.config.update("jax_enable_x64", was)


def _fits_one_chip(compiled) -> bool:
    mem = compiled.memory_analysis()
    used = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
    )
    return used < V5E_HBM_BYTES


@pytest.mark.parametrize(
    "k,m,lost,words",
    [
        (8, 3, (), 65536),
        # bench.py's 256 MiB launch
        (8, 3, (), 8 * 1024 * 1024),
        (8, 3, (0, 1, 2), 8 * 1024 * 1024),
        # a degraded read with one OSD down rebuilds one chunk
        (8, 3, (5,), 65536),
        (4, 2, (), 65536),
    ],
    ids=["rs83-encode-64k", "rs83-encode-8m", "rs83-decode3-8m",
         "rs83-decode1-64k", "rs42-encode-64k"],
)
def test_gf_kernel_compiles_for_v5e(one_chip, no_cache_reads, x64,
                                    k, m, lost, words):
    ec = factory("isa", {"k": str(k), "m": str(m), "technique": "cauchy"})
    if lost:
        present = [i for i in range(k + m) if i not in lost][:k]
        _, packed = ec.decode_bitmatrix(present, list(lost))
    else:
        packed = ec._encode_packed
    mat = jax.ShapeDtypeStruct(packed.shape, jnp.int8, sharding=one_chip)
    data = jax.ShapeDtypeStruct((k, words), jnp.int32, sharding=one_chip)
    compiled = gp.gf_matmul_packed.lower(mat, data).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _fits_one_chip(compiled)


def test_crush_choose_stage_compiles_for_v5e(one_chip, no_cache_reads, x64):
    """The firstn choose stage the daemons' placement runs (replicated
    rule of the vstart map), at a lane count that compiles in seconds."""
    from ceph_tpu.crush import jax_mapper as jm
    from ceph_tpu.vstart import initial_osdmap

    cmap = initial_osdmap(12).crush
    cm = jm.compile_map(cmap)
    t = cmap.tunables
    lanes = 1024
    compiled = jm._choose_firstn_static.lower(
        jax.ShapeDtypeStruct((lanes,), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((cmap.max_devices,), jnp.int64,
                             sharding=one_chip),
        cm=cm, start_bid=-1, numrep=3, want_type=1, recurse_to_leaf=True,
        tries=t.choose_total_tries + 1, recurse_tries=1,
        vary_r=t.chooseleaf_vary_r, stable=t.chooseleaf_stable, out_slots=3,
    ).compile()
    assert _fits_one_chip(compiled)
