"""Front end (``serving/engine.py`` step, ``core/tiers.py`` rungs): host
reads of device values (``d2h:*`` spans) per engine step, each of which
waits for the device."""
from harness import program_spans

UNIT = "count"
LAYER = "front end"
MOVES = "out_tok_s"


def read(ctx):
    return program_spans.syncs_per_step(ctx)
