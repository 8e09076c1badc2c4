"""Front end (``serving/engine.py`` step loop): the window over the
engine steps it held, in ms."""

UNIT = "ms"
LAYER = "front end"
MOVES = "ttft_p95_ms"


def read(ctx):
    return ctx.step_ms()
