"""EncodeService: the padded share of the planar bytes launched, in %:
1 - sum of object_bytes / sum of launch_bytes over the encode_batch and
decode_batch spans that ended in the profiled seconds. A batch is padded
up to a power-of-two bucket so that a few compiled kernels serve every
width; the padding is work the device does for no object. Nothing to read
where no batch span carries both tags."""

from benchmark import clock


def read(ctx):
    obj = launched = 0
    for s in ctx.spans:
        tags = s.get("tags", {})
        if s["name"] in clock.BATCH and "launch_bytes" in tags:
            obj += int(tags["object_bytes"])
            launched += int(tags["launch_bytes"])
    if launched <= 0:
        return None
    return 100.0 * (1.0 - obj / launched)
