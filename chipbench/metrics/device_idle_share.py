"""Share of the traced window in which no operation ran on the device."""
NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "output_tok_s"
SOURCE = "device_trace"


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
