"""Device: share of the traced window in which no operation ran on the
chip, in %."""

UNIT = "%"
LAYER = "device"
MOVES = "out_tok_s"


def read(ctx):
    return ctx.idle_share()
