"""EncodeService: the loop's CPU per launch, in ms: the cpu tags of the
launch's three accounts (ec_pack: plan and pack; ec_device: the call into
the device program up to its result in host memory; ec_unpack: unpack and
hand each caller its chunks), over the launches (n_ec_device)."""

from benchmark import loop_account

KEYS = tuple(f"cpu_{leg}_s" for leg in loop_account.LAUNCH_LEGS)


def read(ctx):
    return loop_account.per_launch_ms(ctx, lambda k: k in KEYS)
