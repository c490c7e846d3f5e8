"""Device: the share of the EncodeService's device legs in which the chip
was busy, in %: for each launch span whose leg (from the call into the
kernel to its result in host memory, tags device_start and device_s) lies
inside the traced window, put on the device trace's clock (clock.py), the
union of the device ops' intervals inside it, averaged over the cell's
chips as device_idle_share averages; summed over launches and divided by
the legs' summed length. The rest of a leg is dispatch, transfer and host
work around the kernel. Nothing to read where no batch span carries a
device leg."""

from benchmark import clock, xplane


def read(ctx):
    legs = [(a, b) for _s, a, b in clock.device_legs(ctx)]
    total = sum(b - a for a, b in legs)
    devs = ctx.trace["devices"]
    if total <= 0 or not devs:
        return None
    t0, t1 = ctx.trace["window"]
    busy = 0.0
    for ops in devs.values():
        merged = xplane.merged(((st, st + d) for _n, st, d in ops), t0, t1)
        for a, b in legs:
            busy += sum(max(0.0, min(e, b) - max(s, a)) for s, e in merged
                        if s < b and e > a)
    return 100.0 * busy / len(devs) / total
