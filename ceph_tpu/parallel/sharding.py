"""shard_map'd erasure-code kernels over a (stripe, byte) device mesh.

Each chunk-byte column is independent in GF(2^8) linear algebra, so both the
stripe-batch axis and the chunk-byte axis shard with NO communication in the
kernels themselves; collectives only appear in cross-shard reductions
(integrity votes, stats). This module packages the mesh construction and the
sharded encode/decode entry points used by the data-path tests and the
driver's multi-chip dryrun.

On a real pod the mesh axes ride ICI; in tests they ride the virtual
8-device CPU mesh (tests/conftest.py).
"""

from __future__ import annotations

import functools
import itertools

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ceph_tpu.ops import gf_bitplane as bp

DATA_SPEC = P("stripe", None, "byte")


def ec_mesh(n_devices: int | None = None) -> Mesh:
    """2D (stripe, byte) mesh over the first n devices (all by default)."""
    devs = jax.devices()
    n = len(devs) if n_devices is None else n_devices
    if n % 2 == 0:
        shape = (n // 2, 2)
    else:
        shape = (n, 1)
    return Mesh(np.array(devs[:n]).reshape(shape), ("stripe", "byte"))


def shard_batch(data: np.ndarray, mesh: Mesh):
    """Place a (batch, n, chunk) uint8 array onto the mesh, stripe/byte
    sharded. batch must divide the stripe axis, chunk the byte axis."""
    return jax.device_put(data, NamedSharding(mesh, DATA_SPEC))


@functools.lru_cache(maxsize=None)
def _sharded_matmul(mesh: Mesh):
    """One jitted sharded GF matmul per mesh; the bit-matrix is an ordinary
    (replicated) argument so jit's cache covers every codec and erasure
    signature without retracing per call."""

    @jax.jit
    def run(bits, d):
        return shard_map(
            lambda b, local: bp.gf_matmul_bitplane(b, local),
            mesh=mesh,
            in_specs=(P(), DATA_SPEC),
            out_specs=DATA_SPEC,
        )(bits, d)

    return run


def sharded_encode(ec, data, mesh: Mesh):
    """(batch, k, chunk) sharded -> (batch, m, chunk) parity, sharded.

    Pure map over shards: every device encodes its (batch/S, k, chunk/B)
    block with the single-chip kernel; no collectives needed.
    """
    return _sharded_matmul(mesh)(ec._encode_bits, data)


def sharded_decode(ec, present, targets, survivors, mesh: Mesh):
    """Rebuild logical chunks `targets` from sharded survivors.

    survivors: (batch, >=k, chunk) sharded on (stripe, byte); the decode
    matrix is resolved host-side from the erasure signature (the table-cache
    contract) and broadcast into every shard's kernel.
    """
    bits, _ = ec.decode_bitmatrix(list(present), list(targets))
    return _sharded_matmul(mesh)(
        jnp.asarray(bits), survivors[:, : ec.k, :]
    )


# -- planar entry points (the EncodeService mesh path) ------------------------
#
# The batch service packs concurrent objects' chunks end to end into (k, W)
# planar rows. Byte columns are independent, so the W axis folds exactly into
# the 2D mesh: split W into `stripe` blocks (data-parallel) whose chunks then
# shard on `byte` — one reshape, no communication, bit-exact vs single-device.


def mesh_encode_planar(ec, planes: np.ndarray, mesh: Mesh) -> np.ndarray:
    """(k, W) uint8 planar rows -> (m, W) parity via the sharded kernel.
    W must divide evenly into the mesh (callers bucket-pad to powers of
    two, which any <=8-device mesh divides)."""
    k, w = planes.shape
    s = mesh.shape["stripe"]
    data = planes.reshape(k, s, w // s).transpose(1, 0, 2)
    out = np.asarray(sharded_encode(ec, shard_batch(data, mesh), mesh))
    return out.transpose(1, 0, 2).reshape(-1, w)


def mesh_decode_planar(
    ec, present, targets, planes: np.ndarray, mesh: Mesh
) -> np.ndarray:
    """(k, W) planar survivor rows (logical ids `present`, ascending) ->
    (len(targets), W) rebuilt rows, sharded like mesh_encode_planar."""
    k, w = planes.shape
    s = mesh.shape["stripe"]
    data = planes.reshape(k, s, w // s).transpose(1, 0, 2)
    out = np.asarray(
        sharded_decode(ec, present, targets, shard_batch(data, mesh), mesh)
    )
    return out.transpose(1, 0, 2).reshape(len(targets), w)


# -- reshard-on-load (the ckpt reader's mesh-independence contract) -----------
#
# A checkpoint records each array's PartitionSpec, not its devices. Restore
# resolves the spec against whatever mesh is present NOW and asks jax which
# index-slab each local device owns; the byte-run translation below turns a
# slab into the minimal contiguous runs of the array's row-major serialized
# bytes, which the reader maps onto chunk objects for partial reads.


def device_slices(shape, spec, mesh: Mesh):
    """{device: index-tuple} for `shape` sharded as `spec` on `mesh`.

    Spec axis names absent from the mesh degrade to replication, so a
    checkpoint saved on a ("stripe", "byte") mesh restores on a mesh with
    different axis names (or a plain data-parallel one) without edits.
    """
    names = set(mesh.axis_names)

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    entries = tuple(keep(e) for e in tuple(spec))[: len(shape)]
    sharding = NamedSharding(mesh, P(*entries))
    return sharding.addressable_devices_indices_map(tuple(shape))


def host_slice(n: int, num_hosts: int, host: int) -> slice:
    """Balanced contiguous partition of `n` items across `num_hosts`:
    host h owns items [start, stop) with the first n % num_hosts hosts
    taking one extra. Pure and total — every process computes the same
    partition, which is what makes the dataset iterator's per-host
    record sequences deterministic without coordination (the same
    contract device_slices provides for array slabs)."""
    if num_hosts <= 0:
        raise ValueError("num_hosts must be positive")
    if not 0 <= host < num_hosts:
        raise ValueError(f"host {host} outside [0, {num_hosts})")
    base, extra = divmod(n, num_hosts)
    start = host * base + min(host, extra)
    stop = start + base + (1 if host < extra else 0)
    return slice(start, stop)


def slice_byte_runs(shape, itemsize: int, idx) -> list[tuple[int, int]]:
    """Contiguous (offset, length) byte runs of a row-major array covered
    by index-tuple `idx`, coalesced: a slab contiguous in memory (the
    common leading-axis shard) collapses to ONE run regardless of rank."""
    shape = tuple(shape)
    if not shape:
        return [(0, itemsize)]
    starts, stops = [], []
    for dim, sl in zip(shape, tuple(idx) + (slice(None),) * len(shape)):
        start, stop, step = sl.indices(dim)
        if step != 1:
            raise ValueError("strided shards are not supported")
        starts.append(start)
        stops.append(stop)
    # trailing axes taken whole are part of one contiguous row
    row = itemsize
    tail = len(shape)
    while tail > 0 and starts[tail - 1] == 0 and stops[tail - 1] == shape[tail - 1]:
        row *= shape[tail - 1]
        tail -= 1
    if tail == 0:
        return [(0, row)] if row else []
    row_len = (stops[tail - 1] - starts[tail - 1]) * row
    if row_len <= 0:
        return []
    # iterate the remaining (outer) index space, coalescing adjacency
    runs: list[tuple[int, int]] = []
    outer = [range(starts[d], stops[d]) for d in range(tail - 1)]
    stride = [row]
    for d in range(tail - 1, 0, -1):
        stride.insert(0, stride[0] * shape[d])

    def emit(off, length):
        if runs and runs[-1][0] + runs[-1][1] == off:
            runs[-1] = (runs[-1][0], runs[-1][1] + length)
        else:
            runs.append((off, length))

    for combo in itertools.product(*outer) if outer else [()]:
        off = sum(c * s for c, s in zip(combo, stride[:-1]))
        off += starts[tail - 1] * row
        emit(off, row_len)
    return runs
