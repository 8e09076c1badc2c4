"""Descriptor (``core/descriptor.py``): device time per engine step
that runs it, in ms."""

UNIT = "ms"
LAYER = "descriptor"
MOVES = "hit_p95_ms"


def read(ctx):
    return ctx.device_ms_per_span(("descriptor",), "descriptor")
