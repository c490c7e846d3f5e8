"""Event loop: the share of wall time the one event loop spent outside its
selector's select, in %: over the loop_account spans of the profiled
seconds, the sum of busy_s over the sum of busy_s and select_s. Near 100,
the loop always has a callback ready, and it bounds the ops' rate."""

from benchmark import loop_account


def read(ctx):
    accounts = loop_account.spans_of(ctx)
    busy = loop_account.total(accounts, lambda k: k == "busy_s")
    idle = loop_account.total(accounts, lambda k: k == "select_s")
    if busy + idle <= 0:
        return None
    return 100.0 * busy / (busy + idle)
