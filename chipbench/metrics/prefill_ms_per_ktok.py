"""Device time of the prefill programs per 1,000 prompt tokens."""
from chipbench import readers as R
from chipbench import trace as TRC

NAME = "prefill_ms_per_ktok"
UNIT = "ms/ktok"
LAYER = "model step"
MOVES = "ttft_p85_ms"
SOURCE = "device_trace"


def read(ctx):
    evs = R.program_events(ctx, R.PREFILL_PROGRAM)
    toks = sum(k * L for k, L in ctx.prefill_groups)
    if not evs or not toks:
        return None
    toks *= R.per_call_scale(len(evs), len(ctx.prefill_groups))
    return 1e3 * TRC.seconds(evs) / (toks / 1e3)
