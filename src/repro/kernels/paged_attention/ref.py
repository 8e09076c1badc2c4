"""Pure-jnp oracle: the gathered-view paged attention the kernel replaces.

Replicates the model's paged gather view (``paged_gather_view``, which
``models/transformer.py`` reads the pool through) + its fp32-softmax
GQA attention bit for bit: gather ``pool[block_table]`` into a dense per-row
``(B, n_pages * page)`` copy, mask by absolute position, softmax in fp32.
This IS the bytes-hungry path the Pallas kernel deletes — kept as the
bit-exactness oracle (tests) and the off-TPU fallback (``impl="ref"``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def paged_gather_view(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """The model's gathered pool view: a head-major attention pool
    (P, K, page, D) — or a latent pool (P, page, r) — gathered through
    block_table (B, n_pages) into (B, n_pages * page, K, D) — or
    (B, n_pages * page, r).  INVALID entries (>= P) clamp to the last
    page — junk masked by position."""
    view = pool[block_table]                   # (B, n_pages, [K,] page, ..)
    if pool.ndim == 4:
        view = jnp.swapaxes(view, 2, 3)        # (B, n_pages, page, K, D)
    B, n_pages, page = view.shape[:3]
    return view.reshape((B, n_pages * page) + view.shape[3:])


def paged_attention_ref(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                        block_table: jax.Array, lengths: jax.Array
                        ) -> jax.Array:
    """q: (B, C, H, D); k/v_pages: (P, K, page, D); block_table:
    (B, n_pages) int32; lengths: (B,) int32 row fill before the dispatch
    (query row c sits at absolute position lengths + c).  Returns
    (B, C, H, D) — the same math as ``L.gqa_attention`` over the gathered
    dense view with the causal mask ``k_pos <= lengths + c``."""
    B, C, H, D = q.shape
    K = k_pages.shape[1]
    G = H // K
    scale = 1.0 / np.sqrt(D)
    ck = paged_gather_view(k_pages, block_table)         # (B, S, K, D)
    cv = paged_gather_view(v_pages, block_table)
    S = ck.shape[1]
    qpos = lengths[:, None] + jnp.arange(C)[None, :]     # (B, C)
    kpos = jnp.arange(S)[None, :]                        # (1, S)
    mask = kpos[:, None, :] <= qpos[:, :, None]          # (B, C, S)
    qg = q.reshape(B, C, K, G, D)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, ck).astype(jnp.float32) * scale
    m = mask[:, None, None, :, :]                        # (B,1,1,C,S)
    logits = jnp.where(m, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1).astype(cv.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", probs, cv)
    return out.reshape(B, C, H, D).astype(q.dtype)
