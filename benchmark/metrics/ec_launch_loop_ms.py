"""EncodeService: the event-loop time one launch holds, in ms: the mean
duration of the encode_batch and decode_batch spans that ended in the
profiled seconds. The flush packs, launches, waits for the result in host
memory and unpacks inside one loop callback, so every other callback
waits this long behind each launch."""

from benchmark import clock


def read(ctx):
    durs = [s["duration"] for s in ctx.spans if s["name"] in clock.BATCH]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
