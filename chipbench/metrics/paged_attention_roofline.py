"""Paged decode attention against its roofline: the least time to read
each active slot's K and V at its real length (and q, out) at the peak
rate, over the kernel's summed device time."""
from chipbench import flops
from chipbench import readers as R
from chipbench import trace as TRC

NAME = "paged_attention_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "itl_p50_ms"
SOURCE = "device_trace"


def read(ctx):
    evs = R.kernel_events(ctx, R.PAGED_KERNEL)
    steps = ctx.decode_keys
    if not evs or not steps:
        return None
    n_layers = ctx.model["num_layers"]
    least = R.roofline_sum((flops.paged_attention_cost(ctx.model, keys)
                            for keys in steps), ctx.peaks) * n_layers
    least *= R.per_call_scale(len(evs), n_layers * len(steps))
    return R.share(least, TRC.seconds(evs))
