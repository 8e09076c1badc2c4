"""Whole model step: model FLOPs of the tokens the window computed
(descriptor prompts, prefilled prompts, decoded tokens) over the window
and the chip's bf16 peak, in %."""

UNIT = "%"
LAYER = "whole model step"
MOVES = "hit_p95_ms"


def read(ctx):
    return ctx.mfu()
