"""Public wrapper for paged attention: layout, backend selection, byte model.

``paged_attention`` takes queries in the model's (B, C, H, D) layout and
the pool leaves exactly as ``paged_cache_specs`` stores them — no caller
ever builds the gathered ``(B, max_len)`` view.  The wrapper folds the H
query heads into (K, C*G) grouped rows for the kernel (each KV page is
read once per group, not once per head) and unfolds the output.

impl routing mirrors ``kernels/decode_attention``: ``auto`` picks the
Pallas kernel on TPU and the jnp gather oracle elsewhere (this container
is CPU-only; CI exercises the kernel via ``pallas_interpret`` — see
tests/test_kernels.py, which pins bit-exactness coverage for every decode
kernel precisely because auto never runs Pallas off-TPU).

``attention_kv_bytes_per_step`` is the shared HBM byte model the
``kv_reuse`` benchmark and docs table quote: the gathered path pays a pool
gather read + a dense copy write + the attention read of the copy, the
in-place kernel pays one pass over mapped pages only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.paged_attention.kernel import paged_attention_kernel
from repro.kernels.paged_attention.ref import paged_attention_ref


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array, lengths: jax.Array, *,
                    impl: str = "auto") -> jax.Array:
    """In-place paged GQA attention for decode (C == 1) and chunked prefill.

    q: (B, C, H, D) chunk queries at absolute positions ``lengths + c``;
    k/v_pages: (P, K, page, D) head-major physical page pools (H % K == 0);
    block_table: (B, n_pages) int32, entries >= P INVALID (skipped);
    lengths: (B,) int32 per-row fill before this dispatch.
    Returns (B, C, H, D).

    impl: auto | pallas | pallas_interpret | ref
    """
    if impl == "auto":
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
    return _paged_attention(q, k_pages, v_pages, block_table, lengths,
                            impl=impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def _paged_attention(q, k_pages, v_pages, block_table, lengths, *, impl):
    if impl == "ref":
        return paged_attention_ref(q, k_pages, v_pages, block_table, lengths)

    B, C, H, D = q.shape
    K = k_pages.shape[1]
    G = H // K
    # (B, C, H, D) -> (B, K, C*G, D): row c*G + g of group k is chunk
    # offset c of query head g (the kernel recovers c as row // G)
    qg = q.reshape(B, C, K, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, K, C * G, D)
    out = paged_attention_kernel(qg, k_pages, v_pages, block_table, lengths,
                                 gq=G, interpret=(impl == "pallas_interpret"))
    return out.reshape(B, K, C, G, D).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, D)


def attention_kv_bytes_per_step(kv_len, *, page_size: int, max_len: int,
                                kv_heads: int, head_dim: int,
                                dtype_bytes: int, impl: str) -> float:
    """Modeled HBM bytes ONE attention layer's k+v traffic moves in one
    decode dispatch over rows with ``kv_len`` (array-like) valid tokens
    each (idle rows: kv_len 0).

    ``impl="gather"`` is the ``paged_gather_view`` path: the pool gather reads
    every mapped page, XLA writes the dense (B, max_len) copy, and the
    attention matmul reads that copy back — mapped + 2 * B * max_len
    token-rows per leaf.  ``impl="paged"`` is the in-place kernel: one
    read of the mapped pages, nothing materialized.  Strictly fewer bytes
    whenever B >= 1, and the gap widens with pool occupancy headroom
    (short rows in long slots).
    """
    kv_len = np.asarray(kv_len, np.int64)
    row_bytes = 2 * kv_heads * head_dim * dtype_bytes        # k + v per token
    mapped = np.ceil(kv_len / page_size).astype(np.int64) * page_size
    if impl == "gather":
        tokens = int(mapped.sum()) + 2 * kv_len.size * max_len
    elif impl == "paged":
        tokens = int(mapped.sum())
    else:
        raise ValueError(impl)
    return float(tokens * row_bytes)
