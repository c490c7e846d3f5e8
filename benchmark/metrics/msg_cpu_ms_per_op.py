"""Messenger: the loop's CPU in the messenger's accounts per client op, in
ms: every cpu_msg_*_s tag of the loop_account spans (frame and payload
encode and decode, CRC, ring writes, the streams' send and receive, the
dispatch of a received frame) over the client ops."""

from benchmark import loop_account


def read(ctx):
    return loop_account.ms_per_op(ctx, lambda k: k.startswith("cpu_msg_"))
