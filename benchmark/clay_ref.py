"""The plain reference for CLAY codes: their defining equations, solved.

CLAY(k, m, d) (Vajha et al., "Clay Codes: Moulding MDS Codes to Yield an
MSR Code", USENIX FAST '18; Ceph's src/erasure-code/clay) with an ISA
Cauchy scalar code (scalar_mds=isa technique=cauchy). It imports only
gf_ref and follows no decode schedule: it writes the code down as linear
equations on every coupled sub-chunk symbol and solves them.

Geometry: q = d - k + 1, nu = (-(k + m)) mod q virtual zero nodes,
t = (k + m + nu) / q, q^t planes. Data chunk i is node i, parity chunk
k + i is node k + nu + i; node i sits at (x, y) = (i mod q, i div q).
Plane z has base-q digits z_vec, most significant first:
z_vec[y] = (z div q^(t-1-y)) mod q. A chunk of c bytes holds q^t
sub-chunks of c / q^t bytes, plane z being bytes [z c/q^t, (z+1) c/q^t).

The equations, on the coupled symbols C[node, z] of one byte column:

- coupling: in plane z, node (x, y) with z_vec[y] != x pairs with node
  (z_vec[y], y) in plane z', which is z with digit y set to x. The point
  whose x exceeds its own plane digit is hi. Its uncoupled values are
  (U_hi, U_lo) = P2 (C_hi, C_lo), P2[i][j] = 1 / ((2 + i) ^ j): the
  parity rows of the (2,2) ISA Cauchy code;
- dots: where z_vec[y] == x, U = C;
- planes: in every plane, (U_0 .. U_{qt-1}) is a codeword of the
  (k + nu, m) ISA Cauchy code: U_{k+nu+i} = sum_j U_j / ((k + nu + i) ^ j);
- virtual nodes hold C = 0.

For a set of known chunks, the erased symbols x_E solve H_E x_E = H_K x_K
(H: the m q^t plane equations over all coupled symbols, in
characteristic 2). Gaussian elimination over GF(2^8) gives x_E = M x_K
once per erasure signature, and gf_ref.matmul applies M.
"""

from __future__ import annotations

import numpy as np

from benchmark import gf_ref


class ClayRef:
    """CLAY(k, m, d) over the ISA Cauchy scalar code."""

    def __init__(self, k: int, m: int, d: int):
        if not k <= d <= k + m - 1:
            raise ValueError(f"d={d} must lie in [{k}, {k + m - 1}]")
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = (-(k + m)) % self.q
        self.t = (k + m + self.nu) // self.q
        self.planes = self.q ** self.t
        self.nodes = self.q * self.t
        self.h = self._equations()
        self._solved: dict = {}

    def node(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def chunk_size(self, size: int) -> int:
        """Bytes per chunk of an object of `size` bytes: ceil(size / k)
        rounded up to q^t sub-chunks of 32 bytes (Ceph's clay plugin
        aligns the object to q^t * k * the (2,2) code's 32 bytes)."""
        return gf_ref.chunk_size(self.k, size, 32 * self.planes)

    def _uncoupled(self) -> np.ndarray:
        """T with U = T C, over rows and columns node * q^t + z."""
        q, t, S = self.q, self.t, self.planes
        p2 = gf_ref.cauchy_parity(2, 2)
        tm = np.zeros((self.nodes * S, self.nodes * S), dtype=np.uint8)
        for node in range(self.nodes):
            x, y = node % q, node // q
            step = q ** (t - 1 - y)
            for z in range(S):
                r = node * S + z
                dig = (z // step) % q
                if dig == x:
                    tm[r, r] = 1
                    continue
                partner = (y * q + dig) * S + z + (x - dig) * step
                hi, lo = (r, partner) if x > dig else (partner, r)
                row = 0 if x > dig else 1
                tm[r, hi] = p2[row, 0]
                tm[r, lo] = p2[row, 1]
        return tm

    def _equations(self) -> np.ndarray:
        """H, (m q^t, q t q^t): H C = 0 for every codeword C."""
        S, kk = self.planes, self.k + self.nu
        tm = self._uncoupled()
        parity = gf_ref.cauchy_parity(kk, self.m)
        h = np.zeros((self.m * S, self.nodes * S), dtype=np.uint8)
        for i in range(self.m):
            for z in range(S):
                row = tm[(kk + i) * S + z].copy()
                for j in range(kk):
                    row ^= gf_ref.MUL_TABLE[parity[i, j]][tm[j * S + z]]
                h[i * S + z] = row
        return h

    def _solve(self, known: tuple) -> tuple[list, np.ndarray]:
        """(erased nodes, M) with x_E = M x_K for the real nodes `known`
        (sorted); x_K lists the known nodes' symbols node by node."""
        if known in self._solved:
            return self._solved[known]
        S = self.planes
        real = [n for n in range(self.nodes)
                if not self.k <= n < self.k + self.nu]
        erased = [n for n in real if n not in known]
        cols_e = [n * S + z for n in erased for z in range(S)]
        cols_k = [n * S + z for n in known for z in range(S)]
        aug = np.concatenate([self.h[:, cols_e], self.h[:, cols_k]], axis=1)
        rows = aug.shape[0]
        for c in range(len(cols_e)):
            piv = next((r for r in range(c, rows) if aug[r, c]), None)
            if piv is None:
                raise ValueError(f"erasures {erased} are not recoverable")
            aug[[c, piv]] = aug[[piv, c]]
            aug[c] = gf_ref.MUL_TABLE[gf_ref.inv(int(aug[c, c]))][aug[c]]
            f = aug[:, c].copy()
            f[c] = 0
            nz = np.nonzero(f)[0]
            aug[nz] ^= gf_ref.MUL_TABLE[f[nz][:, None], aug[c][None, :]]
        out = (erased, aug[: len(cols_e), len(cols_e):].copy())
        self._solved[known] = out
        return out

    def rebuild(self, chunks: dict, targets) -> np.ndarray:
        """The chunks `targets` (ids not in `chunks`) from the chunks
        `chunks` ({chunk id: uint8 array of one length}), (len(targets),
        chunk) bytes."""
        known = sorted(self.node(c) for c in chunks)
        by_node = {self.node(c): np.asarray(a, dtype=np.uint8)
                   for c, a in chunks.items()}
        erased, mat = self._solve(tuple(known))
        c = len(next(iter(by_node.values())))
        if c % self.planes:
            raise ValueError(f"chunk of {c} bytes is not {self.planes} "
                             "sub-chunks")
        xk = np.concatenate([by_node[n].reshape(self.planes, -1)
                             for n in known])
        xe = gf_ref.matmul(mat, xk)
        S = self.planes
        return np.stack([
            xe[erased.index(self.node(t)) * S:
               (erased.index(self.node(t)) + 1) * S].reshape(-1)
            for t in targets])

    def encode(self, data: bytes) -> np.ndarray:
        """All k + m chunks of one object, (k + m, chunk) bytes."""
        d = gf_ref.data_chunks(data, self.k, 32 * self.planes)
        parity = self.rebuild(dict(enumerate(d)),
                              range(self.k, self.k + self.m))
        return np.concatenate([d, parity])
