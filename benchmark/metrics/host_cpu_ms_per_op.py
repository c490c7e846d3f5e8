"""Event loop: the loop thread's CPU per client op, in ms: the sum of
thread_cpu_s over the loop_account spans of the profiled seconds, over the
client ops that ended in the seconds they cover."""

from benchmark import loop_account


def read(ctx):
    return loop_account.ms_per_op(ctx, lambda k: k == "thread_cpu_s")
