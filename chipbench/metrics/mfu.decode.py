"""The decode step's model FLOPs (2 x parameters touched per token plus
attention at the real lengths) over its device time at the bf16 peak."""
from chipbench import flops
from chipbench import readers as R
from chipbench import trace as TRC

NAME = "mfu.decode"
UNIT = "%"
LAYER = "model step"
MOVES = "itl_p50_ms"
SOURCE = "device_trace"


def read(ctx):
    evs = R.program_events(ctx, R.STEP_PROGRAM)
    steps = ctx.decode_keys
    if not evs or not steps:
        return None
    work = sum(flops.decode_step_flops(ctx.model, k) for k in steps)
    work *= R.per_call_scale(len(evs), len(steps))
    took = TRC.seconds(evs)
    return R.share(work / ctx.peaks["bf16_flop_s"], took)
