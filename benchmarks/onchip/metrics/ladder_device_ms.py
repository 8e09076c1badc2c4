"""Ladder (``core/tiers.py``, ``core/cluster.py``): device time per
engine ``lookup`` span, its rungs' probes included, in ms."""

UNIT = "ms"
LAYER = "ladder"
MOVES = "hit_p95_ms"


def read(ctx):
    return ctx.device_ms_per_span(("lookup", "probe:local", "probe:peer"),
                                  "lookup")
