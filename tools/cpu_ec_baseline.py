"""Measure the in-repo CPU erasure-code baselines (VERDICT round-1 item 7).

Two measured numbers for RS(8,3) encode on this host, single thread:

  1. `numpy`  — the pure-numpy GF(2^8) oracle (ceph_tpu.ops.gf.gf_matmul),
     log/antilog table gathers: the slow correctness reference.
  2. `c-xor`  — tools/ec_cpu_baseline.c: bit-plane XOR-schedule encode in
     64-bit words, the same algorithm class as the reference's jerasure
     bitmatrix techniques (ErasureCodeJerasure.cc:305 prepare_schedule).
     This is the honest single-core CPU number BASELINE.md compares the
     TPU path against.

Usage: python tools/cpu_ec_baseline.py [--size BYTES_PER_CHUNK] [--iters N]
Prints one JSON line with both GB/s figures.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ceph_tpu.ec import matrices  # noqa: E402
from ceph_tpu.ops import gf  # noqa: E402

K, M = 8, 3


def measure_numpy(chunk: int, iters: int) -> float:
    rng = np.random.default_rng(0)
    parity = matrices.build_parity_matrix("isa_cauchy", K, M)
    data = rng.integers(0, 256, (K, chunk), np.uint8)
    gf.gf_matmul(parity, data)  # warm tables
    t0 = time.perf_counter()
    for _ in range(iters):
        gf.gf_matmul(parity, data)
    dt = time.perf_counter() - t0
    return K * chunk * iters / dt / 1e9


def measure_c(chunk: int, iters: int) -> float | None:
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "ec_cpu_baseline.c")
    out = os.path.join(tempfile.mkdtemp(prefix="ec_base_"), "ec_base")
    try:
        subprocess.run(
            ["gcc", "-O3", "-march=native", src, "-o", out],
            check=True, capture_output=True,
        )
    except (subprocess.CalledProcessError, FileNotFoundError):
        return None
    parity = matrices.build_parity_matrix("isa_cauchy", K, M)
    bits = gf.matrix_to_bitmatrix(parity)
    psize = 2048  # jerasure default packetsize (ErasureCodeJerasure.h:140)
    feed = f"{K} {M} {psize} {iters} {chunk}\n" + " ".join(
        str(int(v)) for v in bits.reshape(-1)
    )
    best = None
    for _ in range(3):
        proc = subprocess.run(
            [out], input=feed, capture_output=True, text=True, check=True
        )
        el = float(proc.stdout.strip())
        best = el if best is None else min(best, el)
    return K * chunk * iters / best / 1e9


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", type=int, default=1 << 22,
                    help="bytes per chunk (default 4 MiB)")
    ap.add_argument("--iters", type=int, default=8)
    args = ap.parse_args()
    numpy_gbps = measure_numpy(args.size, max(1, args.iters // 4))
    c_gbps = measure_c(args.size, args.iters)
    print(json.dumps({
        "config": f"RS({K},{M}) encode, {args.size} B chunks, single thread",
        "numpy_gbps": round(numpy_gbps, 3),
        "c_xor_gbps": round(c_gbps, 3) if c_gbps else None,
    }))


if __name__ == "__main__":
    main()
