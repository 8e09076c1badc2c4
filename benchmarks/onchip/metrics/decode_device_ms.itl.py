"""Decode (``models/transformer.py::decode_step`` with
``kernels/paged_attention``): device time per engine ``decode`` span,
in ms."""

UNIT = "ms"
LAYER = "decode"
MOVES = "itl_p95_ms"


def read(ctx):
    return ctx.device_ms_per_span(("decode",), "decode")
