"""Tokens committed per decode step in the window: the batch that the
harness's front end admits (whole final lengths reserved in the pool)
and the engine keeps busy."""
NAME = "decode_batch_mean"
UNIT = "slots"
LAYER = "front end"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def read(ctx):
    toks = ctx.host["decode_tokens"]
    return sum(toks) / len(toks) if toks else None
