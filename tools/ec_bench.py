"""ec_bench — drop-in CLI for the reference's ceph_erasure_code_benchmark.

Accepts the same flags (/root/reference/src/test/erasure-code/
ceph_erasure_code_benchmark.cc:40-66) and emits the same output format:
`elapsed_seconds \t KiB_processed` (.cc:179,310), so the reference's sweep
scripts (qa/workunits/erasure-code/bench.sh) can drive the TPU backend
unmodified:

    python tools/ec_bench.py -p isa -P k=8 -P m=3 -P technique=cauchy \
        -s 1048576 -i 100 -w encode

TPU extension: --batch N packs N objects into one (N, k, chunk) device launch
(the HBM stripe-packing mode BASELINE.md measures); default 1 keeps the
reference's one-object-at-a-time behavior.

Workloads:
  encode — encode `iterations` times, print wall seconds and KiB encoded.
  decode — encode once; per iteration erase chunks (at random, from --erased,
           or exhaustively over all combinations with -E exhaustive, verifying
           rebuilt content each time) and decode.
"""

from __future__ import annotations

import argparse
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def parse_args(argv):
    p = argparse.ArgumentParser(
        prog="ec_bench", description="erasure code benchmark (TPU backend)"
    )
    p.add_argument("-v", "--verbose", action="store_true", help="explain what happens")
    p.add_argument("-s", "--size", type=int, default=1024 * 1024,
                   help="size of the buffer to be encoded")
    p.add_argument("-i", "--iterations", type=int, default=1,
                   help="number of encode/decode runs")
    p.add_argument("-p", "--plugin", default="jerasure",
                   help="erasure code plugin name")
    p.add_argument("-w", "--workload", default="encode",
                   choices=["encode", "decode"], help="run either encode or decode")
    p.add_argument("-e", "--erasures", type=int, default=1,
                   help="number of erasures when decoding")
    p.add_argument("--erased", type=int, action="append", default=[],
                   help="erased chunk (repeat if more than one chunk is erased)")
    p.add_argument("-E", "--erasures-generation", default="random",
                   choices=["random", "exhaustive"], dest="erasures_generation")
    p.add_argument("-P", "--parameter", action="append", default=[],
                   help="add a parameter to the erasure code profile (k=v)")
    p.add_argument("--batch", type=int, default=1,
                   help="TPU extension: objects packed per device launch")
    return p.parse_args(argv)


def build_profile(params: list[str]) -> dict:
    profile = {}
    for item in params:
        if item.count("=") != 1:
            print(
                f"--parameter {item} ignored because it does not contain "
                "exactly one =",
                file=sys.stderr,
            )
            continue
        key, value = item.split("=")
        profile[key] = value
    return profile


def display_chunks(chunks, chunk_count):
    out = "chunks "
    for chunk in range(chunk_count):
        out += f"({chunk})  " if chunk not in chunks else f" {chunk}   "
    print(out + "(X) is an erased chunk")


def run_encode(ec, args) -> float:
    import jax
    import numpy as np

    data = b"X" * args.size
    if args.batch > 1:
        chunks, _ = ec.encode_prepare(data)
        batch = np.repeat(chunks, args.batch, axis=0)
        batch = jax.device_put(batch)
        t0 = time.perf_counter()
        for _ in range(args.iterations):
            out = ec.encode_array(batch)
        np.asarray(out[0, 0, :1])
        return time.perf_counter() - t0
    want = set(range(ec.get_chunk_count()))
    t0 = time.perf_counter()
    for _ in range(args.iterations):
        ec.encode(want, data)
    return time.perf_counter() - t0


def decode_erasures(ec, all_chunks, chunks, start, want_erasures, verbose):
    """Exhaustive erasure enumeration with verification (.cc:196-244)."""
    n = ec.get_chunk_count()
    if want_erasures == 0:
        if verbose:
            display_chunks(chunks, n)
        want_to_read = {c for c in range(n) if c not in chunks}
        decoded = ec.decode(want_to_read, chunks)
        for c in want_to_read:
            # chunks absent from all_chunks (pre-erased via --erased) cannot
            # be verified; the reference dereferences map.end() here
            if c in all_chunks and decoded[c] != all_chunks[c]:
                raise SystemExit(
                    f"chunk {c} content and recovered content are different"
                )
        return
    for i in range(start, n):
        # the reference recurses even when i is already absent (erase is a
        # no-op but want_erasures still decrements, .cc:234-240)
        one_less = {c: v for c, v in chunks.items() if c != i}
        decode_erasures(ec, all_chunks, one_less, i + 1, want_erasures - 1, verbose)


def run_decode(ec, args) -> float:
    data = b"X" * args.size
    n = ec.get_chunk_count()
    encoded = ec.encode(range(n), data)
    want_to_read = set(range(n))

    if args.erased:
        for c in args.erased:
            encoded.pop(c, None)
        display_chunks(encoded, n)

    t0 = time.perf_counter()
    for _ in range(args.iterations):
        if args.erasures_generation == "exhaustive":
            decode_erasures(ec, encoded, encoded, 0, args.erasures, args.verbose)
        elif args.erased:
            ec.decode(want_to_read, encoded)
        else:
            chunks = dict(encoded)
            for _ in range(args.erasures):
                while True:
                    erasure = random.randrange(n)
                    if erasure in chunks:
                        break
                del chunks[erasure]
            ec.decode(want_to_read, chunks)
    return time.perf_counter() - t0


def main(argv=None) -> int:
    args = parse_args(argv if argv is not None else sys.argv[1:])
    from ceph_tpu.common.config import config
    from ceph_tpu.ec.registry import factory

    profile = build_profile(args.parameter)
    ec = factory(args.plugin, profile)

    def run():
        if args.workload == "encode":
            return run_encode(ec, args)
        return run_decode(ec, args)

    # profiling hook (SURVEY §5): config-driven jax.profiler trace capture,
    # the analogue of the reference's LTTng tracepoints around the op loop
    if config.get("bench_profile"):
        import jax

        trace_dir = config.get("bench_profile_trace_dir") or "/tmp/ceph_tpu_trace"
        with jax.profiler.trace(trace_dir):
            elapsed = run()
        print(f"# jax.profiler trace written to {trace_dir}", file=sys.stderr)
    else:
        elapsed = run()
    kib = args.iterations * (args.size // 1024) * max(1, args.batch)
    print(f"{elapsed:.6f}\t{kib}")
    return 0


if __name__ == "__main__":
    from ceph_tpu.chip import use_compile_cache

    use_compile_cache()
    sys.exit(main())
