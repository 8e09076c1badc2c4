"""Ladder (``core/tiers.py``, ``core/cluster.py``): host reads of device
values (``d2h:*`` spans) inside each engine ``lookup`` span: the rungs'
probe results and their per-node loops."""
from harness import program_spans

UNIT = "count"
LAYER = "ladder"
MOVES = "hit_p95_ms"


def read(ctx):
    return program_spans.syncs_per_lookup(ctx)
