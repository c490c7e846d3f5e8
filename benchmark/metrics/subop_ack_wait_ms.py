"""OSD op path and store: mean per-op critical-path self time of the
`subop_ack_wait` spans, in ms: on a shard, the time from an inner op's own
completion to the send of the coalesced reply that carries its ack, held
while its batch-mates finish. 0 where the program records the span (it
records loop_lag too) and no op in the window waited so; nothing to read
where it records neither."""

from benchmark import spans

NAME = "subop_ack_wait"


def read(ctx):
    if not any(s["name"] == NAME for ss in ctx.traces for s in ss) and (
            not any(s["name"] == "loop_lag" for s in ctx.spans)):
        return None
    return spans.mean_self_ms(ctx.traces, lambda s: s["name"] == NAME)
