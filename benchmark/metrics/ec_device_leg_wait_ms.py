"""EncodeService: the seconds of a launch's device leg in which the loop
thread used no CPU, per launch, in ms: wall_ec_device_s less
cpu_ec_device_s over the launches. It bounds from above the wait on the
transfers and the device program: it also holds the leg's waits for the
interpreter lock and the host's preemption of the loop thread. The leg's
CPU is in ec_launch_cpu_ms."""

from benchmark import loop_account


def read(ctx):
    wall = loop_account.per_launch_ms(ctx, lambda k: k == "wall_ec_device_s")
    if wall is None:
        return None
    return wall - loop_account.per_launch_ms(
        ctx, lambda k: k == "cpu_ec_device_s")
