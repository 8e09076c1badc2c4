"""Peaks of the chips, and the operations and bytes each piece of work
needs, computed from shapes.

Peaks (one chip): Google Cloud documentation, "TPU v5e": 197 TFLOP/s in
bfloat16, 819 GB/s of HBM bandwidth.  A device that is not in the table
is an error.

The byte model of paged attention follows
``repro.kernels.paged_attention.ops.attention_kv_bytes_per_step`` (the
in-place ``paged`` case: one read of each mapped page of k and v), per
token and per layer.
"""
from __future__ import annotations

import math

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return PEAKS[device_kind]


def linear_flops_per_token(m: dict, layers: int) -> float:
    """Matrix products of ``layers`` layers for one token (2 per MAC)."""
    D, H, K = m["d_model"], m["num_heads"], m["num_kv_heads"]
    hd, F = m["head_dim"], m["d_ff"]
    mats = 2 if m.get("mlp_kind", "gated_silu") == "gelu" else 3
    per_layer = D * H * hd * 2 + 2 * D * K * hd + mats * D * F
    return 2.0 * per_layer * layers


def attention_flops(m: dict, layers: int, context: int) -> float:
    """Scores and weighted sum of one query token over ``context`` keys."""
    return 4.0 * m["num_heads"] * m["head_dim"] * context * layers


def head_flops(m: dict) -> float:
    return 2.0 * m["d_model"] * m["vocab_size"]


def prompt_flops(m: dict, layers: int, n: int, logits: bool) -> float:
    """A causal pass of ``layers`` layers over an ``n``-token prompt."""
    f = linear_flops_per_token(m, layers) * n
    f += attention_flops(m, layers, 1) * n * (n + 1) / 2
    return f + (head_flops(m) if logits else 0.0)


def decode_flops(m: dict, context: int) -> float:
    """One decoded token at ``context`` keys (itself included)."""
    L = m["num_layers"]
    return (linear_flops_per_token(m, L) + attention_flops(m, L, context)
            + head_flops(m))


def paged_attention_bytes(m: dict, page: int, context: int) -> float:
    """HBM bytes the paged-attention kernel needs for one decoded token
    over ``context`` keys, all layers: every mapped page of k and v read
    once, the query read and the output written."""
    K, H, hd = m["num_kv_heads"], m["num_heads"], m["head_dim"]
    b = 2  # bfloat16
    pages = math.ceil(context / page)
    kv = pages * page * 2 * K * hd * b
    return float((kv + 2 * H * hd * b) * m["num_layers"])

