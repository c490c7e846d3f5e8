"""Event loop: the share of the loop thread's CPU that the named accounts
cover, in %: over the loop_account spans of the profiled seconds, the sum
of every cpu_<account>_s tag over the sum of thread_cpu_s. What is left
is CPU no account names (asyncio's own steps and transports, the code
between accounted sites), so the per-layer CPU metrics of this event
loop read that much short of host_cpu_ms_per_op."""

from benchmark import loop_account


def read(ctx):
    accounts = loop_account.spans_of(ctx)
    cpu = loop_account.total(accounts, lambda k: k == "thread_cpu_s")
    if cpu <= 0:
        return None
    named = loop_account.total(
        accounts, lambda k: k.startswith("cpu_") and k.endswith("_s"))
    return 100.0 * named / cpu
