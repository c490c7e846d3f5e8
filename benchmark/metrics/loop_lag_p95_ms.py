"""Event loop: the 95th percentile of how late the program's event loop ran
a callback that was due, in ms: the durations of the tracer's `loop_lag`
spans (one per 10 ms probe firing) that ended in the profiled seconds,
`statistics.quantiles(n=20)[-1]`. Every callback of the one loop waits
this long behind the others. Nothing to read where the program records no
loop_lag span."""

import statistics


def read(ctx):
    lags = [s["duration"] for s in ctx.spans if s["name"] == "loop_lag"]
    if len(lags) < 2:
        return None
    return 1e3 * statistics.quantiles(lags, n=20)[-1]
