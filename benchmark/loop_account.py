"""The event loop's CPU accounts, as the program's tracer reports them.

Every second the tracer's loop probe records a root span `loop_account`
with what the one event loop did since the one before (common/tracer.py
`_LoopProbe`): busy_s and select_s (wall seconds outside and inside the
selector's select), thread_cpu_s (the loop thread's CPU), and per named
account cpu_<name>_s, wall_<name>_s and n_<name> (entries). The accounts
are exclusive: a nested account's CPU is never also its parent's.

The readers take the loop_account spans that began and ended in the
profiled seconds, and count the client ops (root op_submit spans) that
ended in the seconds those spans cover. Where the program records no
loop_account span, as before the probe kept accounts, there is nothing to
read and every reader returns None.
"""

from __future__ import annotations


def spans_of(ctx) -> list[dict]:
    """The loop_account spans held whole by the profiled seconds."""
    t0 = ctx.profiled[0]
    return [s for s in ctx.spans
            if s["name"] == "loop_account" and s["start"] >= t0]


def total(accounts: list[dict], member) -> float:
    """The sum over the spans of every tag whose name satisfies member."""
    return sum(float(v) for s in accounts for k, v in s["tags"].items()
               if member(k))


def ops(ctx, accounts: list[dict]) -> int:
    """Client ops that ended in the seconds the spans cover."""
    lo = min(s["start"] for s in accounts)
    hi = max(s["start"] + s["duration"] for s in accounts)
    return sum(1 for s in ctx.spans
               if s["name"] == "op_submit" and s["parent_id"] is None
               and lo < s["start"] + s["duration"] <= hi)


def ms_per_op(ctx, member) -> float | None:
    """CPU ms per client op of the tags that satisfy member."""
    accounts = spans_of(ctx)
    if not accounts:
        return None
    n = ops(ctx, accounts)
    if not n:
        return None
    return 1e3 * total(accounts, member) / n


#: the legs of one EncodeService launch (osd/encode_service.py)
LAUNCH_LEGS = ("ec_pack", "ec_device", "ec_unpack")


def per_launch_ms(ctx, member) -> float | None:
    """ms per EncodeService launch (an entry of the ec_device account)
    of the tags that satisfy member."""
    accounts = spans_of(ctx)
    launches = total(accounts, lambda k: k == "n_ec_device")
    if not launches:
        return None
    return 1e3 * total(accounts, member) / launches
