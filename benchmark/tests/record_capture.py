"""Record a traced capture of one cell on its chips, for the clock test:

    python3 -m benchmark.tests.record_capture --workload rs83-write \
        --seed <n> --seconds 5 --out <dir>

It runs the cell as `benchmark/run.py --trace 1` does, keeps what the run
leaves in its working directory and writes to <dir>:

- <workload>.spans.jsonl.gz: the tracer's encode_batch and decode_batch
  spans that ended in the profiled seconds, as the tracer exported them;
- <workload>.xplane.pb: the profiler's trace;
- <workload>.anchors.json: the anchor pair clock.py maps by (the
  profiled seconds on the tracer's clock, the window on the profiler's).

It prints one JSON line: the cell's per-layer metrics and checks, the ops
the window completed, how many of them carried both the messenger's send
and its dispatch span on the reply leg, and the traced run's ops per
second.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

#: the reply leg of a client op, on both sides of the wire
REPLY_LEG = {("msg_send", "osd_op_reply"), ("msg_dispatch", "osd_op_reply")}


def reply_legs(traces) -> int:
    """Of the window's op traces, those whose reply leg carries both the
    messenger's send and dispatch spans."""
    return sum(1 for ss in traces if REPLY_LEG <= {
        (s["name"], s["tags"].get("type")) for s in ss})


def keep_batches(span_path: str, dest: str, t0: float, t1: float) -> int:
    from benchmark import clock

    kept = 0
    with open(span_path, encoding="utf-8") as f, \
            gzip.open(dest, "wt", encoding="utf-8") as out:
        for line in f:
            j = json.loads(line)
            end = (j["startTime"] + j["duration"]) / 1e6
            if j["operationName"] in clock.BATCH and t0 <= end <= t1:
                out.write(line)
                kept += 1
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmark import harness, spans, xplane
    from benchmark.run import ONE_CHIP_ENV

    cell = harness.resolve(harness.load_spec(), args.workload)
    if cell.chips == 1:
        for key, value in ONE_CHIP_ENV.items():
            os.environ.setdefault(key, value)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = harness.CACHE_DIR
    import jax

    jax.config.update("jax_enable_x64", True)
    harness.use_compile_cache()
    from benchmark.cluster import CompileMeter

    device = harness.check_device(cell.chips)
    peaks = harness.peaks_of(device["kind"])
    workdir = tempfile.mkdtemp(prefix="capture-")
    try:
        out = asyncio.run(harness.drive(
            cell, args.seed, args.seconds, True, T_START, CompileMeter(),
            workdir))
        res = harness.result(cell, device, out, True, peaks)
        prof = out["profile"]
        trace = xplane.extract(xplane.find_xplane(prof["dir"]))
        traces = spans.window_traces(spans.load_jsonl(out["span_path"]),
                                     *out["wall"])
        os.makedirs(args.out, exist_ok=True)
        base = os.path.join(args.out, args.workload)
        kept = keep_batches(out["span_path"], base + ".spans.jsonl.gz",
                            prof["t0"], prof["t1"])
        shutil.copy(xplane.find_xplane(prof["dir"]), base + ".xplane.pb")
        with open(base + ".anchors.json", "w", encoding="utf-8") as f:
            json.dump({"profiled": [prof["t0"], prof["t1"]],
                       "window": trace["window"]}, f)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(json.dumps({
        "workload": args.workload, "seed": args.seed,
        "correct": res["correct"], "checks": res["checks"],
        "metrics": {k: v["value"] for k, v in res["metrics"].items()},
        "window_ops": len(traces), "reply_legs_traced": reply_legs(traces),
        "traced_ops_per_s": len(out["run"].lat) / out["seconds"],
        "batch_spans_kept": kept,
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
