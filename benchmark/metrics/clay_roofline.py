"""Kernel: the share of its HBM roofline that CLAY's layered decode (the
one device program of ec/clay.py) reached on the degraded reads of the
traced window, in %.

The numerator is the least time the decoded reads' bytes need at the
chip's HBM bandwidth: each client read (an op_submit span tagged
op=read) that ended in the profiled seconds, of an object with
`erased[name]` data chunks lost, reads k chunks and writes the rebuilt
ones (roofline.ec_bytes), its chunk from the object's size, as
roofline.window_bytes counts them. The denominator is the device's busy
time inside the device legs of the decode_batch spans tagged path clay
(clock.device_legs): the legs hold every op of the program whatever XLA
names it. Nothing to read where no such leg or decoded read is there."""

from benchmark import clock, gf_ref, roofline, xplane


def read(ctx):
    legs = [(a, b) for s, a, b in clock.device_legs(ctx)
            if s["name"] == "decode_batch"
            and s["tags"].get("path") == "clay"]
    devs = ctx.trace["devices"]
    if not legs or not devs:
        return None
    t0, t1 = ctx.trace["window"]
    busy = 0.0
    for ops in devs.values():
        merged = xplane.merged(((st, st + d) for _n, st, d in ops), t0, t1)
        for a, b in legs:
            busy += sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged
                        if s < b and e > a)
    moved = 0
    for s in ctx.spans:
        tags = s.get("tags", {})
        name = tags.get("object")
        if (s["name"] == "op_submit" and tags.get("op") == "read"
                and ctx.erased.get(name, 0)):
            chunk = gf_ref.chunk_size(ctx.k, ctx.sizes[name], ctx.align)
            moved += roofline.ec_bytes("decode", ctx.k, ctx.erased[name],
                                       chunk)
    if busy <= 0 or not moved:
        return None
    return 100.0 * moved / ctx.peaks["hbm_bytes_per_s"] / busy
