"""Paged decode kernel (``rap_paged_decode_attention``): the least time
the chip needs for the K/V, q and output bytes and the attention FLOPs of
the rows it stepped for real, over the kernel's summed device time in the
trace, in %."""
from bench.metrics._device import paged_attn_roofline


def compute(ctx):
    return paged_attn_roofline(ctx)
