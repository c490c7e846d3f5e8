"""EncodeService: the event-loop time one CLAY launch holds, in ms: the
mean duration of the encode_batch and decode_batch spans tagged path
clay that ended in the profiled seconds. The flush packs every object's
planes, runs the whole layered decode, waits for the result in host
memory and unpacks inside one loop callback. Nothing to read where no
CLAY launch ran."""

from benchmark import clock


def read(ctx):
    durs = [s["duration"] for s in ctx.spans if s["name"] in clock.BATCH
            and s.get("tags", {}).get("path") == "clay"]
    if not durs:
        return None
    return 1e3 * sum(durs) / len(durs)
