"""CLAY (Coupled LAYer) MSR regenerating code — TPU-native implementation.

Re-expresses the reference's clay plugin
(/root/reference/src/erasure-code/clay/ErasureCodeClay.{h,cc}, IISc): an
(k, m, d) vector code that wraps a scalar MDS code and couples its codewords
across q^t sub-chunk "planes" so that repairing ONE lost chunk reads only a
1/q fraction (sub_chunk_no/q sub-chunks) of each of d helper chunks — the
minimum-storage-regenerating (MSR) point.

Geometry (parse, ErasureCodeClay.cc:188-302): q = d-k+1, nu pads k+m to a
multiple of q, t = (k+m+nu)/q, sub_chunk_no = q^t. Nodes live on a (t x q)
grid; node_xy = y*q + x; data chunks are nodes 0..k-1, nu virtual zero chunks
k..k+nu-1, parity chunks map to nodes k+nu..q*t-1. A plane z in [0, q^t) has
base-q digit vector z_vec (get_plane_vector, .cc:888-894).

Coupling: in plane z, node (x, y) with z_vec[y] != x pairs with node
(z_vec[y], y) in plane z_sw (z with digit y replaced by x). The pair's
coupled values (C_hi, C_lo) and uncoupled values (U_hi, U_lo) — hi is the
point whose x exceeds its plane digit — form one codeword of a (k=2, m=2)
scalar "pft" code, so ANY two of the four determine the rest
(get_uncoupled_from_coupled / get_coupled_from_uncoupled / recover_type1,
.cc:776-871). Dot points (z_vec[y] == x) have U == C.

Decode is layered (decode_layered, .cc:647-712): planes are processed in
increasing "intersection score" order (number of erased nodes whose x equals
their plane digit); each group computes U for intact nodes from coupled data
recovered in earlier groups, MDS-decodes the erased nodes' U across the plane
(decode_uncoupled -> the scalar mds code), then maps U back to C.

TPU mapping: the whole layered decode of a batch is ONE jitted program
(_layered_planes). Chunks are (nodes, q^t planes, W) int32 words, many
objects side by side along W; every step is column-wise, so objects of
different sizes share a launch. Per order group the program computes the
uncoupled values of every point with one (2,2) pair transform, the lost
nodes' uncoupled values with one MDS decode over all planes, and the lost
points' coupled values with one small solve, each a GF(2^8) matmul on the
fused Pallas kernel (ops/gf_pallas.py) on the TPU and on the XLA
bit-plane path elsewhere. The erasure signature (which nodes are lost,
the planes' order groups, each lost point's sources) is data built once
per signature on the host (plane_plan), so one compiled program serves
every signature; the order groups run in a lax.fori_loop, each over all
planes with the commit masked to the group's planes.
"""

from __future__ import annotations

import errno
import functools
import itertools
from typing import Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ceph_tpu.ec.interface import (
    DecodeTableCache,
    ErasureCode,
    ErasureCodeError,
    PlanePlan,
    align_up,
    profile_to_int,
    profile_to_string,
)
from ceph_tpu.ops import gf
from ceph_tpu.ops import gf_bitplane as bp
from ceph_tpu.ops import gf_pallas as gp


def _pow_int(a: int, x: int) -> int:
    return a ** x


@functools.lru_cache(maxsize=None)
def _pair_rows(q: int, t: int) -> tuple[np.ndarray, np.ndarray]:
    """Rows (node * q^t + plane) of the hi and the lo point of every
    coupled pair: node (x, y) in plane z pairs with node (z_vec[y], y) in
    the plane whose digit y is x, and the point whose x exceeds its plane
    digit is hi."""
    S = q ** t
    hi, lo = [], []
    for node in range(q * t):
        x, y = node % q, node // q
        stride = q ** (t - 1 - y)
        for z in range(S):
            dig = (z // stride) % q
            if x > dig:
                hi.append(node * S + z)
                lo.append((y * q + dig) * S + z + (x - dig) * stride)
    return np.asarray(hi, dtype=np.int32), np.asarray(lo, dtype=np.int32)


#: the Pallas kernel's largest tile in this program: at the kernel's
#: default, the pair transform's (2 x 2) launch needs more than the 16 MiB
#: of scoped VMEM a v5e kernel may take
TILE_WORDS = 16384


def _gf_rows(mat, rows, pallas: bool):
    """GF(2^8) matrix x (n, L) int32 word rows -> (r, L) word rows: the
    Pallas kernel with `mat` packed, or the XLA bit-plane path with `mat`
    as its bit-matrix. The kernel's tile is the largest power of two up
    to TILE_WORDS that divides L, so no block runs past the rows."""
    if not pallas:
        return bp.gf_matmul_words(mat, rows)
    n4 = rows.shape[1]
    tile = n4 & -n4
    tile = min(tile, TILE_WORDS) if tile >= 128 else n4
    return gp.gf_matmul_packed(mat, rows, tile_words=tile)


@functools.partial(jax.jit, static_argnames=("q", "t", "pallas"))
def _layered_planes(words, reads, lost, survivors, order, levels, n_groups,
                    mds, src, case, pair, solve, *, q, t, pallas):
    """The layered decode (decode_layered, .cc:647-712) of a batch: words
    (k, q^t, W) holds the chunks of `reads`; returns the coupled planes of
    every lost node, (m, q^t, W), so that one executable per plane width
    serves encode and every decode whatever it rebuilds. The signature's
    arrays come from plane_plan."""
    nodes, S = q * t, q ** t
    width = words.shape[-1]
    hi, lo = _pair_rows(q, t)
    c = jnp.zeros((nodes, S, width), jnp.int32).at[reads].set(words)
    c = c.reshape(nodes * S, width)
    lost_rows = (lost[:, None] * S + jnp.arange(S, dtype=lost.dtype)).ravel()
    lost_order = jnp.tile(order, lost.shape[0])[:, None]
    n_cases = solve.shape[0] // (32 if pallas else 8)

    def group(i, c):
        # uncoupled values of every point from the coupled values known so
        # far; right on this group's planes for every surviving node
        u = c
        if hi.size:
            pairs = _gf_rows(pair, jnp.stack(
                [c[hi].ravel(), c[lo].ravel()]), pallas)
            pairs = pairs.reshape(2, hi.size, width)
            u = u.at[hi].set(pairs[0]).at[lo].set(pairs[1])
        # the lost nodes' uncoupled values, one MDS decode per plane
        u = u.reshape(nodes, S, width)
        u_lost = _gf_rows(
            mds, u[survivors].reshape(survivors.shape[0], -1), pallas)
        u = u.at[lost].set(u_lost.reshape(-1, S, width))
        u = u.reshape(nodes * S, width)
        # the lost points' coupled values: each is row `case` of the solve
        # matrix applied to two rows of [C; U]
        both = jnp.concatenate([c, u])
        cand = _gf_rows(solve, jnp.stack(
            [both[src[0]].ravel(), both[src[1]].ravel()]), pallas)
        cand = cand.reshape(n_cases, -1, width)
        new = cand[0]
        for r in range(1, n_cases):
            new = jnp.where((case == r)[:, None], cand[r], new)
        keep = lost_order == levels[i]
        return c.at[lost_rows].set(jnp.where(keep, new, c[lost_rows]))

    c = jax.lax.fori_loop(jnp.zeros_like(n_groups), n_groups, group, c)
    return c.reshape(nodes, S, width)[lost]


class ErasureCodeClay(ErasureCode):
    DEFAULT_K = 4
    DEFAULT_M = 2

    def __init__(self):
        super().__init__()
        self.d = 0
        self.w = 8
        self.q = self.t = self.nu = 0
        self.sub_chunk_no = 0
        self.mds = None  # scalar MDS over k+nu data / m parity (plane decode)
        self.pft = None  # (2,2) pairwise coupling transform code
        self._G4: np.ndarray | None = None  # (4,2) pair generator
        #: erasure signature -> PlanePlan, and how many were built
        self._plans = DecodeTableCache()
        self.plans_built = 0

    # -- profile -------------------------------------------------------------

    def parse(self, profile) -> None:
        self.k = profile_to_int(profile, "k", self.DEFAULT_K)
        self.m = profile_to_int(profile, "m", self.DEFAULT_M)
        self.sanity_check_k_m()
        self.d = profile_to_int(profile, "d", self.k + self.m - 1)
        scalar_mds = profile_to_string(profile, "scalar_mds", "jerasure")
        # deviation from the reference: scalar_mds=shec is accepted there
        # (ErasureCodeClay.cc:207) but SHEC(2,2,c=2) has no systematic
        # [I; P] generator to derive the pairwise transform from; this
        # implementation supports the MDS wrappers only
        if scalar_mds not in ("jerasure", "isa"):
            raise ErasureCodeError(
                errno.EINVAL,
                f"scalar_mds {scalar_mds!r} is not supported here, use "
                "one of 'jerasure', 'isa'",
            )
        technique = profile_to_string(profile, "technique", "reed_sol_van")
        # liber8tion (allowed by the reference, .cc:232) is omitted until the
        # bitmatrix techniques land in the jerasure family
        allowed = {
            "jerasure": ("reed_sol_van", "reed_sol_r6_op", "cauchy_orig",
                         "cauchy_good"),
            "isa": ("reed_sol_van", "cauchy"),
        }[scalar_mds]
        if technique not in allowed:
            raise ErasureCodeError(
                errno.EINVAL,
                f"technique {technique!r} is not currently supported for "
                f"scalar_mds={scalar_mds}, use one of {allowed}",
            )
        if technique == "reed_sol_r6_op" and self.m != 2:
            # the inner jerasure codec coerces its m to 2 for RAID6; with
            # CLAY's m baked into the plane geometry that coercion would
            # desynchronize the two, so require agreement up front
            raise ErasureCodeError(
                errno.EINVAL, "technique=reed_sol_r6_op requires m=2"
            )
        if not self.k <= self.d <= self.k + self.m - 1:
            raise ErasureCodeError(
                errno.EINVAL,
                f"value of d {self.d} must be within "
                f"[{self.k},{self.k + self.m - 1}]",
            )
        self.q = self.d - self.k + 1
        self.nu = (-(self.k + self.m)) % self.q
        if self.k + self.m + self.nu > 254:
            raise ErasureCodeError(errno.EINVAL, "k+m+nu must be <= 254")
        self.t = (self.k + self.m + self.nu) // self.q
        self.sub_chunk_no = _pow_int(self.q, self.t)
        self._scalar_mds = scalar_mds
        self._technique = technique
        self._parse_mapping(profile)

    def prepare(self) -> None:
        from ceph_tpu.ec.registry import registry

        mds_profile = {
            "k": str(self.k + self.nu), "m": str(self.m), "w": "8",
            "technique": self._technique,
        }
        pft_profile = {"k": "2", "m": "2", "w": "8",
                       "technique": self._technique}
        self.mds = registry.factory(self._scalar_mds, mds_profile)
        self.pft = registry.factory(self._scalar_mds, pft_profile)
        # (4, 2) pair generator: rows (C_hi, C_lo, U_hi, U_lo) over the
        # variables (C_hi, C_lo); any 2 rows invert (the pft code is MDS).
        # The 6 possible 2x2 inverses are precomputed — _pair_solve runs in
        # every plane of every decode/repair
        pft_parity = np.asarray(self.pft._gen[2:4], dtype=np.uint8)
        self._G4 = np.concatenate([np.eye(2, dtype=np.uint8), pft_parity])
        self._pair_inv = {
            rows: gf.gf_invert_matrix(self._G4[list(rows)])
            for rows in itertools.combinations(range(4), 2)
        }
        # the device program's two fixed matrices: the pair transform
        # (U_hi, U_lo) from (C_hi, C_lo), and the five ways a lost point's
        # C follows from two known values X, Y: a dot (C = U = X); own
        # partner known (X = the partner's C, Y = own U), hi or lo; both
        # lost (X = own U, Y = the partner's U), hi or lo
        inv = self._pair_inv
        solve = np.array([
            [1, 0], inv[(1, 2)][0], inv[(0, 3)][1],
            inv[(2, 3)][0], inv[(2, 3)][1][::-1],
        ], dtype=np.uint8)
        self._pair_bits = tuple(
            gf.matrix_to_bitmatrix(a).astype(np.int8)
            for a in (self._G4[2:4], solve))
        self._pair_mats = tuple(gp.pack_matrix(b) for b in self._pair_bits)
        self._plans.clear()

    # -- geometry ------------------------------------------------------------

    def get_sub_chunk_count(self) -> int:
        return self.sub_chunk_no

    def get_chunk_size(self, object_size: int) -> int:
        # reference: alignment = sub_chunk_no * k * pft.get_chunk_size(1)
        # (ErasureCodeClay.cc:90-96)
        alignment = self.sub_chunk_no * self.k * self.pft.get_chunk_size(1)
        return align_up(max(1, object_size), alignment) // self.k

    # -- node/plane helpers ----------------------------------------------------

    def _node_of(self, chunk: int) -> int:
        """Logical chunk id -> grid node id (parities shift past virtuals)."""
        return chunk if chunk < self.k else chunk + self.nu

    def _chunk_of(self, node: int) -> int | None:
        """Grid node id -> logical chunk id (None for virtual nodes)."""
        if node < self.k:
            return node
        if node < self.k + self.nu:
            return None
        return node - self.nu

    def _plane_digits(self) -> np.ndarray:
        """(sub_chunk_no, t) base-q digits; column y is z_vec[y]
        (get_plane_vector: z_vec[t-1-i] = z-th least significant digit)."""
        z = np.arange(self.sub_chunk_no)
        digits = np.empty((self.sub_chunk_no, self.t), dtype=np.int64)
        for i in range(self.t):
            digits[:, self.t - 1 - i] = z % self.q
            z = z // self.q
        return digits

    # -- pairwise transform ----------------------------------------------------

    def _pair_solve(
        self, knowns: dict[int, np.ndarray], targets: Sequence[int]
    ) -> list[np.ndarray]:
        """Solve the (2,2) pair code: given 2 of (C_hi, C_lo, U_hi, U_lo)
        (positions 0..3), return the requested positions. Vectorized over
        arbitrary array shapes."""
        rows = sorted(knowns)[:2]
        v0, v1 = knowns[rows[0]], knowns[rows[1]]

        def lin2(a, x, b, y):
            return gf.gf_mul(a, x) ^ gf.gf_mul(b, y)

        if rows == [0, 1]:  # knowns ARE the variables; skip the identity solve
            c_hi, c_lo = v0, v1
        else:
            Minv = self._pair_inv[tuple(rows)]
            c_hi = lin2(Minv[0, 0], v0, Minv[0, 1], v1)
            c_lo = lin2(Minv[1, 0], v0, Minv[1, 1], v1)
        out = []
        for tpos in targets:
            if tpos == 0:
                out.append(c_hi)
            elif tpos == 1:
                out.append(c_lo)
            else:
                a, b = self._G4[tpos]
                out.append(lin2(a, c_hi, b, c_lo))
        return out

    # -- the layered decode as one device program ------------------------------

    def plane_plan(self, present, targets) -> PlanePlan:
        """The full-chunk decode that rebuilds logical chunks `targets`
        from logical chunks `present`, as the arguments of the one device
        program (_layered_planes): the erasure signature is data, so one
        compiled program per plane width serves every signature, encode
        included. More than k chunks may be present; the plan names the
        k it reads in `inputs`. Encode is the plan whose targets are the
        parity chunks. Memoized per signature, as the inner codec's
        decode tables are."""
        key = (tuple(present), tuple(targets))

        def build():
            self.plans_built += 1
            return self._build_plan(*key)

        return self._plans.get_or(key, build)

    def _build_plan(self, present, targets) -> PlanePlan:
        q, t, k, m, nu = self.q, self.t, self.k, self.m, self.nu
        nodes, S = q * t, self.sub_chunk_no
        have = {self._node_of(c) for c in present}
        erased = {n for n in range(nodes)
                  if self._chunk_of(n) is not None and n not in have}
        if len(erased) > m:
            raise ErasureCodeError(errno.EIO, "too many erasures")
        # pad the erasures to exactly m with parity nodes (.cc:658-664)
        for n in range(k + nu, nodes):
            if len(erased) >= m:
                break
            erased.add(n)
        lost = sorted(erased)
        if not {self._node_of(c) for c in targets} <= erased:
            raise ErasureCodeError(
                errno.EINVAL, f"targets {list(targets)} are not all lost")
        survivors = [n for n in range(nodes) if n not in erased]
        reads = [n for n in survivors if self._chunk_of(n) is not None]
        bits, packed = self.mds.decode_bitmatrix(survivors, lost)
        digits = self._plane_digits()
        # a plane's order: how many lost nodes are dots in it (.cc:763);
        # the order groups run in increasing order (decode_layered)
        order = np.zeros(S, dtype=np.int32)
        for n in lost:
            order += digits[:, n // q] == n % q
        groups = np.unique(order)
        levels = np.full(t + 1, -1, dtype=np.int32)
        levels[: groups.size] = groups
        # each lost point's coupled value is row `case` of the solve
        # matrix applied to two rows of [C; U] (see prepare)
        src = np.zeros((2, m * S), dtype=np.int32)
        case = np.zeros(m * S, dtype=np.int32)
        at_u = nodes * S
        for i, node in enumerate(lost):
            x, y = node % q, node // q
            stride = q ** (t - 1 - y)
            for z in range(S):
                p, own = i * S + z, at_u + node * S + z
                dig = int(digits[z, y])
                if dig == x:
                    src[:, p] = own
                    continue
                partner = y * q + dig
                row = partner * S + z + (x - dig) * stride
                if partner in erased:
                    case[p] = 3 if x > dig else 4
                    src[:, p] = (own, at_u + row)
                else:
                    case[p] = 1 if x > dig else 2
                    src[:, p] = (row, own)
        pallas = gp.available()
        ints = [np.asarray(a, dtype=np.int32) for a in (
            reads, lost, survivors, order, levels, groups.size)]
        args = tuple(jnp.asarray(a) for a in (
            *ints, packed if pallas else bits, src, case,
            *(self._pair_mats if pallas else self._pair_bits)))
        return PlanePlan(
            inputs=tuple(self._chunk_of(n) for n in reads),
            targets=tuple(targets),
            rows=tuple(lost.index(self._node_of(c)) for c in targets),
            groups=int(groups.size),
            pallas=pallas, path="clay",
            # one executable per bucket serves encode and every decode
            program=("layered", m), args=args)

    def planes(self, plan: PlanePlan, words) -> jnp.ndarray:
        """Run `plan` on (k, sub_chunk_no, W) int32 words whose row j holds
        chunk plan.inputs[j] of every object of a batch, each object's
        chunk cut into its sub_chunk_no planes and the objects side by
        side along W. Returns (len(plan.targets), sub_chunk_no, W) in
        host memory."""
        out = _layered_planes(
            jnp.asarray(words, dtype=jnp.int32), *plan.args,
            q=self.q, t=self.t, pallas=plan.pallas)
        return np.asarray(out)[list(plan.rows)]

    def _run_batch(self, plan: PlanePlan, rows) -> np.ndarray:
        """(batch, k, chunk) uint8 rows of plan.inputs -> (batch,
        len(plan.targets), chunk): the batch side by side in one launch."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        words, widths = self.pack_planes(list(rows))
        return np.stack(self.unpack_planes(self.planes(plan, words), widths))

    def encode_array(self, data) -> np.ndarray:
        """(batch, k, chunk) -> (batch, m, chunk): parity via the layered
        decode with the parity nodes erased (encode_chunks, .cc:129-157)."""
        plan = self.plane_plan(
            range(self.k), range(self.k, self.k + self.m))
        return self._run_batch(plan, data)

    def decode_array(self, present, targets, survivors) -> np.ndarray:
        """Full-chunk decode: every survivor node takes part (the layered
        decode needs all of them), so the plan reads k of `present`."""
        plan = self.plane_plan(present, targets)
        pos = {c: i for i, c in enumerate(present)}
        survivors = np.asarray(survivors, dtype=np.uint8)
        return self._run_batch(
            plan, survivors[:, [pos[c] for c in plan.inputs]])

    # -- repair (the MSR read-minimal path) ------------------------------------

    def is_repair(self, want_to_read: set[int], available: set[int]) -> bool:
        """Single lost chunk, whole q-group co-located, >= d helpers
        (is_repair, .cc:304-323). Ids are physical (as in the byte API) and
        are translated through chunk_mapping before the group-geometry test."""
        if want_to_read <= available:
            return False
        if len(want_to_read) > 1:
            return False
        want_to_read = {self.logical_index(p) for p in want_to_read}
        available = {self.logical_index(p) for p in available}
        lost = next(iter(want_to_read))
        lost_node = self._node_of(lost)
        for x in range(self.q):
            node = (lost_node // self.q) * self.q + x
            chunk = self._chunk_of(node)
            if chunk is None or chunk == lost:
                continue
            if chunk not in available:
                return False
        return len(available) >= self.d

    def get_repair_subchunks(self, lost_node: int) -> list[tuple[int, int]]:
        """(offset, count) runs of the planes with digit y_lost == x_lost
        (get_repair_subchunks, .cc:363-377)."""
        y_lost, x_lost = lost_node // self.q, lost_node % self.q
        seq = _pow_int(self.q, self.t - 1 - y_lost)
        runs = []
        index = x_lost * seq
        for _ in range(_pow_int(self.q, y_lost)):
            runs.append((index, seq))
            index += self.q * seq
        return runs

    def get_repair_sub_chunk_count(self, want_to_read: set[int]) -> int:
        weight = [0] * self.t
        for c in want_to_read:
            weight[self._node_of(self.logical_index(c)) // self.q] += 1
        remaining = 1
        for y in range(self.t):
            remaining *= self.q - weight[y]
        return self.sub_chunk_no - remaining

    def minimum_to_decode(
        self, want_to_read: set[int], available: set[int]
    ) -> dict[int, list[tuple[int, int]]]:
        want_to_read, available = set(want_to_read), set(available)
        if not self.is_repair(want_to_read, available):
            return super().minimum_to_decode(want_to_read, available)
        # minimum_to_repair (.cc:325-361): the lost node's q-group first,
        # then arbitrary helpers up to d, all reading the repair sub-chunks.
        # Group geometry is logical; the returned keys are the caller's
        # physical ids
        lost_node = self._node_of(
            self.logical_index(next(iter(want_to_read)))
        )
        runs = self.get_repair_subchunks(lost_node)
        minimum: dict[int, list[tuple[int, int]]] = {}
        for j in range(self.q):
            if j == lost_node % self.q:
                continue
            chunk = self._chunk_of((lost_node // self.q) * self.q + j)
            if chunk is not None:
                minimum[self.chunk_index(chunk)] = list(runs)
        for chunk in sorted(available):
            if len(minimum) >= self.d:
                break
            minimum.setdefault(chunk, list(runs))
        assert len(minimum) == self.d
        return minimum

    def repair_array(
        self, lost: int, helpers: Mapping[int, np.ndarray], batch_cols: int
    ) -> np.ndarray:
        """Rebuild logical chunk `lost` from d helpers' repair sub-chunks.

        helpers: {logical chunk id: (S/q, cols) uint8} holding ONLY the repair
        planes (in ascending plane order, as minimum_to_decode requests them).
        Returns (S, cols). Mirrors repair_one_lost_chunk (.cc:462-644), with
        each order group's planes processed as one batch: phase 1 is
        vectorized over the group, phase 2 is a single batched MDS decode.
        """
        q, t, k, m, nu = self.q, self.t, self.k, self.m, self.nu
        qt, S = q * t, self.sub_chunk_no
        lost_node = self._node_of(lost)
        digits = self._plane_digits()
        runs = self.get_repair_subchunks(lost_node)
        repair_planes = [
            z for (off, count) in runs for z in range(off, off + count)
        ]
        n_rep = len(repair_planes)
        plane_pos = np.full(S, -1, dtype=np.int64)
        plane_pos[repair_planes] = np.arange(n_rep)

        helper_nodes = {self._node_of(c): a for c, a in helpers.items()}
        for i in range(k, k + nu):  # virtual shortening nodes are zero
            helper_nodes[i] = np.zeros((n_rep, batch_cols), dtype=np.uint8)
        aloof = {
            n for n in range(qt)
            if n != lost_node and n not in helper_nodes
        }
        erasures = {
            (lost_node // q) * q + i for i in range(q)
        } | aloof
        if len(erasures) > m:
            raise ErasureCodeError(errno.EIO, "not repairable")

        # dense helper C view (zeros at the lost/aloof rows, masked out below)
        H = np.zeros((qt, n_rep, batch_cols), dtype=np.uint8)
        for node, arr in helper_nodes.items():
            H[node] = arr
        aloof_mask = np.zeros(qt, dtype=bool)
        aloof_mask[list(aloof)] = True

        # plane order: #({lost} ∪ aloof) hole-dot intersections (.cc:481-498)
        order_of = np.zeros(S, dtype=np.int64)
        for node in {lost_node} | aloof:
            order_of += digits[:, node // q] == node % q
        groups: dict[int, list[int]] = {}
        for z in repair_planes:
            groups.setdefault(int(order_of[z]), []).append(z)

        U = np.zeros((qt, S, batch_cols), dtype=np.uint8)
        u_known = np.zeros((qt, S), dtype=bool)
        C_lost = np.zeros((S, batch_cols), dtype=np.uint8)
        present_nodes = [i for i in range(qt) if i not in erasures]
        targets = sorted(erasures)

        for o in sorted(groups):
            zs = np.asarray(groups[o])
            # phase 1: uncoupled values of helper nodes (.cc:536-593),
            # vectorized over the group's planes
            for node in present_nodes:
                x, y = node % q, node // q
                dig = digits[zs, y]
                z_sw = zs + (x - dig) * _pow_int(q, t - 1 - y)
                node_sw = y * q + dig
                c_xy = H[node, plane_pos[zs]]  # (G, cols)
                c_sw = H[node_sw, plane_pos[z_sw]]
                u_sw = U[node_sw, z_sw]
                dot = dig == x
                hi = dig < x
                pair_aloof = aloof_mask[node_sw]
                # pair C of an aloof node is unavailable: its U from an
                # earlier (order-1) plane substitutes (.cc:553-566)
                assert bool(np.all(u_known[node_sw, z_sw] | ~pair_aloof))
                u_hi = self._pair_solve({0: c_xy, 1: c_sw}, [2])[0]
                u_lo = self._pair_solve({0: c_sw, 1: c_xy}, [3])[0]
                u_hi_al = self._pair_solve({0: c_xy, 3: u_sw}, [2])[0]
                u_lo_al = self._pair_solve({1: c_xy, 2: u_sw}, [3])[0]
                sel = np.where(
                    hi[:, None],
                    np.where(pair_aloof[:, None], u_hi_al, u_hi),
                    np.where(pair_aloof[:, None], u_lo_al, u_lo),
                )
                U[node, zs] = np.where(dot[:, None], c_xy, sel)
                u_known[node, zs] = True
            # phase 2: one batched MDS decode of erased U rows (.cc:595)
            surv = np.stack([U[n][zs] for n in present_nodes[: k + nu]], axis=1)
            rebuilt = np.asarray(
                self.mds.decode_array(present_nodes, targets, surv)
            )
            for pos, node in enumerate(targets):
                U[node, zs] = rebuilt[:, pos]
                u_known[node, zs] = True
            # phase 3: recover lost-chunk C sub-chunks (.cc:597-639).
            # On repair planes the lost node is always the hole-dot (its
            # digit equals x_lost), and every other non-aloof erasure is a
            # same-group helper whose pair is the lost node
            for node in targets:
                if node in aloof:
                    continue
                x, y = node % q, node // q
                if node == lost_node:
                    C_lost[zs] = U[node, zs]
                    continue
                dig = digits[zs, y]  # == x_lost on every repair plane
                z_sw = zs + (x - dig) * _pow_int(q, t - 1 - y)
                c_xy = H[node, plane_pos[zs]]
                if x > lost_node % q:  # node is hi, lost node is lo
                    C_lost[z_sw] = self._pair_solve(
                        {0: c_xy, 2: U[node, zs]}, [1]
                    )[0]
                else:
                    C_lost[z_sw] = self._pair_solve(
                        {1: c_xy, 3: U[node, zs]}, [0]
                    )[0]
        return C_lost

    # -- byte-level API overrides ----------------------------------------------

    def decode(
        self,
        want_to_read,
        chunks: Mapping[int, bytes],
        chunk_size: int | None = None,
    ) -> dict[int, bytes]:
        """Repair-aware decode (decode, .cc:109-125): when the provided
        buffers are the partial repair reads (shorter than chunk_size), run
        the sub-chunk repair path; otherwise fall back to full decode."""
        want = set(want_to_read)
        have = set(chunks)
        if chunks and chunk_size is not None and self.is_repair(want, have):
            some = len(next(iter(chunks.values())))
            if chunk_size > some:
                return self._repair_bytes(want, chunks, chunk_size)
        return super().decode(want, chunks)

    def _repair_bytes(
        self, want: set[int], chunks: Mapping[int, bytes], chunk_size: int
    ) -> dict[int, bytes]:
        lost = next(iter(want))
        repair_subchunks = self.sub_chunk_no // self.q
        repair_blocksize = len(next(iter(chunks.values())))
        if repair_blocksize % repair_subchunks:
            raise ErasureCodeError(errno.EINVAL, "bad repair block size")
        sc = repair_blocksize // repair_subchunks
        if sc * self.sub_chunk_no != chunk_size:
            raise ErasureCodeError(errno.EINVAL, "bad repair chunk size")
        if len(chunks) != self.d:
            raise ErasureCodeError(
                errno.EIO, f"repair needs exactly d={self.d} helpers"
            )
        helpers = {
            self.logical_index(c): np.frombuffer(b, dtype=np.uint8).reshape(
                repair_subchunks, sc
            )
            for c, b in chunks.items()
        }
        rebuilt = self.repair_array(self.logical_index(lost), helpers, sc)
        return {lost: rebuilt.reshape(-1).tobytes()}
