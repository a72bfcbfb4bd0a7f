"""Device time per call of the jitted decode step program."""
from chipbench import readers as R
from chipbench import trace as TRC

NAME = "decode_step_ms"
UNIT = "ms"
LAYER = "model step"
MOVES = "itl_p50_ms"
SOURCE = "device_trace"


def read(ctx):
    evs = R.program_events(ctx, R.STEP_PROGRAM)
    return 1e3 * TRC.seconds(evs) / len(evs) if evs else None
