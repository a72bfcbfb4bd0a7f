"""Executables compiled or loaded from the persistent cache inside the
measured window (jax.monitoring's backend-compile event); set-up warms
every program, so this reads 0 when the window runs nothing new.  A
compile stalls every slot for a second or more, so it costs tokens in
every cell."""
NAME = "compiles_in_window"
UNIT = "count"
LAYER = "engine"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(ctx):
    return ctx.host["compiles_in_window"]
