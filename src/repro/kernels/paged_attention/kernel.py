"""Pallas TPU paged attention: decode/chunk queries read KV *pages* in place.

The PR 6 paged layout reads the pool through ``pool[block_table]`` — a
gathered per-row copy of up to ``max_len`` tokens that XLA materializes in
HBM before attention ever runs, so the memory-bound decode step moves ~3x
the bytes it needs (pool gather read + copy write + attention read of the
copy).  This kernel deletes the copy: the grid iterates KV pages and the
*scalar-prefetched block table drives the k/v BlockSpec index_map* — each
grid step DMAs one physical page straight from the pool (vLLM-style), and
online softmax (m, l, acc scratch) combines the per-page partials exactly
as flash-decode does.

Page skipping: a block-table entry ``>= num_pages`` (``PagedKVCache.
INVALID``, the out-of-bounds sink) or a page past the row's written length
contributes nothing — the compute body is predicated off and the index_map
clamps the DMA to a resident page (junk that is never read).  A fully
masked row (idle decode slot with an all-INVALID table) finalizes to zeros
through the safe-divide, mirroring the gather path's position-masked junk.

One kernel serves both hot paths: decode is the C == 1 case and chunked
prefill is C > 1, with the causal mask ``k_pos <= lengths + c`` applied
per query row.  All C*G query rows of a KV group ride one (C*G, D) tile,
so each page is read once per group rather than once per head.

The grid ``(B, K, n_pages)`` is static — page occupancy varies only
through the (data) block table and lengths, so one compile covers every
mix of short, long, shared, and idle rows.

TPU layout: the pool is head-major within a page, ``(P, K, page, D)``, so
one grid step's k/v block ``(1, 1, page, D)`` is one contiguous
(page, D) tile — its last two dims are (a multiple of 8, the whole head
dim), which Mosaic tiles.  The per-row softmax state lives in
``(C*G, 1)`` scratch columns, never 1-D vectors.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page: int, gq: int, scale: float,
                  num_pages: int):
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]                      # row fill BEFORE this dispatch
    pid = bt_ref[b, j]
    C = q_ref.shape[2] // gq

    # skip INVALID pages (>= num_pages: the drop/clamp sink) and pages
    # wholly past the last query position length + C - 1
    @pl.when((pid < num_pages) & (j * page <= length + C - 1))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # (C*G, D)
        k = k_ref[0, 0].astype(jnp.float32)              # (page, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # causal mask over absolute positions: query row c*G + g sits at
        # position length + c; the key slot j*page + t holds position
        # j*page + t (linear paged cache)
        kp = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = length + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // gq
        s = jnp.where(kp <= qpos, s, NEG_INF)

        m_prev = m_scr[...]                              # (C*G, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        # fully masked rows keep m == NEG_INF: zero their partials so the
        # final safe-divide yields exact zeros, not exp(0) garbage
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        l = l_scr[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("gq", "interpret"))
def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           lengths: jax.Array, *, gq: int,
                           interpret: bool = False) -> jax.Array:
    """Launch the paged-attention kernel.

    q: (B, K, C*G, D) with ``gq`` query heads per KV group (row = c*gq + g);
    k/v_pages: (P, K, page, D); block_table: (B, n_pages) int32; lengths:
    (B,) int32.  Returns (B, K, C*G, D) in q.dtype.  The block table and
    lengths ride the scalar-prefetch path so the k/v index_maps can resolve
    physical pages before each DMA."""
    B, K, CG, D = q.shape
    P, page = k_pages.shape[0], k_pages.shape[2]
    n_pages = block_table.shape[1]
    scale = 1.0 / np.sqrt(D)

    kernel = functools.partial(_paged_kernel, page=page, gq=gq, scale=scale,
                               num_pages=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, K, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, CG, D), lambda b, h, j, bt, ln: (b, h, 0, 0)),
            # the block table IS the index map: grid step (b, h, j) DMAs
            # physical page bt[b, j] of head h; INVALID entries clamp to a
            # resident page whose (skipped) tile is never read
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, j, bt, ln:
                         (jnp.minimum(bt[b, j], P - 1), h, 0, 0)),
            pl.BlockSpec((1, 1, page, D),
                         lambda b, h, j, bt, ln:
                         (jnp.minimum(bt[b, j], P - 1), h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, CG, D),
                               lambda b, h, j, bt, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, 1), jnp.float32),
            pltpu.VMEM((CG, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, K, CG, D), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pages, v_pages)
