"""The prefill programs' model FLOPs at the real prompt lengths over
their device time at the bf16 peak."""
from chipbench import flops
from chipbench import readers as R
from chipbench import trace as TRC

NAME = "mfu.prefill"
UNIT = "%"
LAYER = "model step"
MOVES = "ttft_p85_ms"
SOURCE = "device_trace"


def read(ctx):
    evs = R.program_events(ctx, R.PREFILL_PROGRAM)
    groups = ctx.prefill_groups
    if not evs or not groups:
        return None
    work = sum(flops.prefill_flops(ctx.model, k, L) for k, L in groups)
    work *= R.per_call_scale(len(evs), len(groups))
    took = TRC.seconds(evs)
    return R.share(work / ctx.peaks["bf16_flop_s"], took)
