"""Causal prefill attention against its roofline at the real prompt
lengths, over the flash kernel's summed device time."""
from chipbench import flops
from chipbench import readers as R
from chipbench import trace as TRC

NAME = "flash_attention_roofline"
UNIT = "%"
LAYER = "kernels"
MOVES = "ttft_p85_ms"
SOURCE = "device_trace"


def read(ctx):
    evs = R.kernel_events(ctx, R.FLASH_KERNEL)
    groups = ctx.prefill_groups
    if not evs or not groups:
        return None
    n_layers = ctx.model["num_layers"]
    least = R.roofline_sum((flops.flash_attention_cost(ctx.model, k, L)
                            for k, L in groups), ctx.peaks) * n_layers
    least *= R.per_call_scale(len(evs), n_layers * len(groups))
    return R.share(least, TRC.seconds(evs))
