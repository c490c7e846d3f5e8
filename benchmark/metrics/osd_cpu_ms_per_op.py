"""OSD op path and store: the loop's CPU in the OSD's op path and the
object store per client op, in ms: the cpu_osd_s and cpu_store_s tags of
the loop_account spans over the client ops."""

from benchmark import loop_account


def read(ctx):
    return loop_account.ms_per_op(
        ctx, lambda k: k in ("cpu_osd_s", "cpu_store_s"))
