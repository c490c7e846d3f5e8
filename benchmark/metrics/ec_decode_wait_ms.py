"""EncodeService: mean per-op critical-path self time of the
`decode_wait` spans, in ms: a degraded read's wait from handing its
survivors to the service to holding the rebuilt chunks, the batch window
and the launch included (the decode_batch span runs inside it, off the
path). 0 where the program records the span (it
records loop_lag too) and no op in the window decoded; nothing to read
where it records neither."""

from benchmark import spans

NAME = "decode_wait"


def read(ctx):
    if not any(s["name"] == NAME for ss in ctx.traces for s in ss) and (
            not any(s["name"] == "loop_lag" for s in ctx.spans)):
        return None
    return spans.mean_self_ms(ctx.traces, lambda s: s["name"] == NAME)
