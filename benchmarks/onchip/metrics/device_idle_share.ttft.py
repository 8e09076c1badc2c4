"""Device: share of the traced window in which no operation ran on the
chip, in %."""

UNIT = "%"
LAYER = "device"
MOVES = "ttft_p95_ms"


def read(ctx):
    return ctx.idle_share()
