"""The tracer's time on the device trace's clock.

The two sides keep different clocks:

- the program's tracer (ceph_tpu/common/tracer.py, `now`) stamps spans,
  and the times its span sites record as tags (an EncodeService batch's
  `device_start`), with time.time(): seconds since the Unix epoch on the
  host's wall clock;
- the jax.profiler trace, as xplane.extract reduces it, holds its events
  in seconds on the profiler's own clock, which starts near zero when the
  profiling session begins.

The harness reads one pair of anchors at the start of the profiled
seconds: `ctx.profiled[0]`, time.time() read just after its bench_window
annotation opened, and `ctx.trace["window"][0]`, that annotation's start
on the profiler's clock. Both clocks count seconds, so a tracer time t
lies at t - profiled[0] + window[0] on the device trace, early by the few
microseconds between the two readings.
"""

from __future__ import annotations

#: the EncodeService's launch spans
BATCH = ("encode_batch", "decode_batch")


def offset(ctx) -> float:
    """Seconds to add to a tracer time to place it on the device trace."""
    return ctx.trace["window"][0] - ctx.profiled[0]


def device_legs(ctx) -> list[tuple[dict, float, float]]:
    """(span, start, end) on the device trace's clock of each launch span
    in ctx.spans whose device leg (the call into the kernel until its
    result is in host memory) lies inside the traced window."""
    off = offset(ctx)
    t0, t1 = ctx.trace["window"]
    out = []
    for s in ctx.spans:
        tags = s.get("tags", {})
        if s["name"] not in BATCH or "device_start" not in tags:
            continue
        start = float(tags["device_start"]) + off
        end = start + float(tags["device_s"])
        if t0 <= start and end <= t1:
            out.append((s, start, end))
    return out
