"""Paged attention (``kernels/paged_attention``, Pallas
``paged_attention``) in the decode steps: the least time the KV pages of
the decoded tokens need at the chip's HBM bandwidth over the kernel's
device time, in %.  Decode attention is bound by bytes."""

UNIT = "%"
LAYER = "decode"
MOVES = "out_tok_s"


def read(ctx):
    return ctx.paged_attention_roofline()
