"""Prefill (``models/transformer.py::prefill_chunk`` through the paged
cache): device time of the admission phase per thousand prompt tokens
computed, in ms."""

UNIT = "ms"
LAYER = "prefill"
MOVES = "ttft_p95_ms"


def read(ctx):
    return ctx.prefill_ms_per_ktok()
