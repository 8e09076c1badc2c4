"""Front end (``serving/engine.py`` step, ``core/tiers.py`` rungs): device
idle time per engine step whose innermost program span is a host read
(``d2h:*``) or upload (``h2d:*``), in ms."""
from harness import program_spans

UNIT = "ms"
LAYER = "front end"
MOVES = "hit_p95_ms"


def read(ctx):
    return program_spans.sync_idle_ms(ctx)
