"""EncodeService: objects per CLAY launch, the mean `batch` tag of the
encode_batch and decode_batch spans tagged path clay that ended in the
profiled seconds. Nothing to read where no CLAY launch ran."""

from benchmark import clock


def read(ctx):
    sizes = [float(s["tags"]["batch"]) for s in ctx.spans
             if s["name"] in clock.BATCH
             and s.get("tags", {}).get("path") == "clay"]
    if not sizes:
        return None
    return sum(sizes) / len(sizes)
