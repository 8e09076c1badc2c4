"""Pallas TPU kernels for the CoIC edge-cache similarity lookup.

Streams the cache key matrix through VMEM in (BLOCK_C, D) tiles while a
(BLOCK_Q, D) query tile stays resident; each step is an MXU matmul
(BLOCK_Q x D) @ (D x BLOCK_C) followed by a running top-k merge.  This
adapts the paper's brute-force edge lookup to the TPU memory hierarchy:
arbitrarily large caches stream HBM->VMEM at matmul arithmetic intensity
instead of the pointer-chasing hash probe a CPU edge box would use.

One scan kernel serves the plain lookup (k == 1), the top-k lookup (N == 1)
and the grouped per-node lookup (N shards, one dispatch); the fused
top-k + LRU-touch kernel adds the metadata epilogue.

TPU layout: every per-slot row (valid mask, LRU metadata) travels as an
int32 ``(1, C)`` row — or ``(N, 1, C)`` for the grouped kernel — so each
block's last two dims are either whole or (1, multiple of 128)-tiled.
Mosaic refuses 1-D int8 blocks and a second-minor block dim of 1 over an
axis of N > 1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _topk_tile(q, kk, valid, carry_s, carry_i, *, block_c: int, k: int,
               c_block_index):
    """Merge one (BQ, D) x (BC, D) score tile into the carried top-k.

    ``valid`` is the (1, BC) int32 slot mask.  Concatenates the carried
    top-k with the new block's scores and re-selects k by iterated masked
    argmax — k is small and static, so this is k VPU reductions per tile,
    no sort.  Candidate order is [carried | new block]; argmax breaks ties
    toward the first occurrence, so equal scores resolve to the lowest
    global cache index — exactly ``lax.top_k`` semantics on the full row.
    Returns (scores (BQ, k), idx (BQ, k)).
    """
    scores = jax.lax.dot_general(
        q, kk, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)             # (BQ, BC)
    scores = jnp.where(valid != 0, scores, NEG_INF)
    bq = scores.shape[0]
    local_idx = (jax.lax.broadcasted_iota(jnp.int32, (bq, block_c), 1)
                 + c_block_index * block_c)

    cand_scores = jnp.concatenate([carry_s, scores], axis=1)
    cand_idx = jnp.concatenate([carry_i, local_idx], axis=1)
    n_cand = cand_scores.shape[1]
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bq, n_cand), 1)
    out_s, out_i = [], []
    for _ in range(k):
        arg = jnp.argmax(cand_scores, axis=1).astype(jnp.int32)
        onehot = lanes == arg[:, None]
        out_s.append(jnp.max(cand_scores, axis=1))
        out_i.append(jnp.sum(jnp.where(onehot, cand_idx, 0), axis=1))
        cand_scores = jnp.where(onehot, -jnp.inf, cand_scores)
    return jnp.stack(out_s, axis=1), jnp.stack(out_i, axis=1)


def _topk_batched_kernel(q_ref, k_ref, valid_ref, idx_ref, score_ref, *,
                         block_c: int, k: int):
    """One (batch, q-block, c-block) grid step: batch entry ``n``'s query
    tile against c-block ``j`` of its OWN key matrix.

    The running (scores, indices) top-k lives in the output blocks, which
    persist across the innermost c-block grid dim (standard Pallas
    revisiting).
    """
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        score_ref[...] = jnp.full_like(score_ref, NEG_INF)
        # iota init: an all-invalid cache yields indices 0..k-1, matching
        # the oracle's tie-break over a constant row
        idx_ref[...] = jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 2)

    s, i = _topk_tile(q_ref[0].astype(jnp.float32),
                      k_ref[0].astype(jnp.float32),
                      valid_ref[0], score_ref[0], idx_ref[0],
                      block_c=block_c, k=k, c_block_index=j)
    score_ref[0] = s
    idx_ref[0] = i


@functools.partial(jax.jit,
                   static_argnames=("k", "block_q", "block_c", "interpret"))
def similarity_topk_batched_kernel(queries: jax.Array, keys: jax.Array,
                                   valid: jax.Array, *, k: int,
                                   block_q: int = 128, block_c: int = 512,
                                   interpret: bool = False):
    """queries: (N, Q, D); keys: (N, C, D); valid: (N, C) bool/int.

    Batch entry ``n``'s queries are scored against key matrix ``n`` only
    (grid over batch).  Returns (idx (N, Q, k) int32, score (N, Q, k) f32),
    scores descending, ties toward the lower cache index.  Q and C must be
    multiples of the block sizes (ops.py pads); k <= block_c; on TPU
    block_c must be C or a multiple of 128.
    """
    N, Q, D = queries.shape
    C = keys.shape[1]
    assert keys.shape[0] == N and valid.shape == (N, C), (
        queries.shape, keys.shape, valid.shape)
    assert Q % block_q == 0 and C % block_c == 0, (Q, C, block_q, block_c)
    assert k <= block_c, (k, block_c)
    grid = (N, Q // block_q, C // block_c)

    kernel = functools.partial(_topk_batched_kernel, block_c=block_c, k=k)
    idx, score = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, block_c, D), lambda n, i, j: (n, j, 0)),
            pl.BlockSpec((1, 1, block_c), lambda n, i, j: (n, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, k), lambda n, i, j: (n, i, 0)),
            pl.BlockSpec((1, block_q, k), lambda n, i, j: (n, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Q, k), jnp.int32),
            jax.ShapeDtypeStruct((N, Q, k), jnp.float32),
        ],
        interpret=interpret,
        name="similarity_topk",
    )(queries, keys, valid.astype(jnp.int32).reshape(N, 1, C))
    return idx, score


def _topk_touch_kernel(clock_ref, q_ref, qmask_ref, k_ref, valid_ref,
                       lu_ref, fr_ref, idx_ref, score_ref, lu_out, fr_out, *,
                       block_c: int, k: int, threshold: float):
    """One (pass, c-block) grid step of the fused top-k + LRU-touch kernel.

    Pass 0 is the top-k scan (running top-k in the output blocks).  Pass 1
    re-walks the c-blocks once with the finished top-1 in VMEM and writes
    the LRU epilogue in place: a slot's ``last_used`` raises to ``clock``
    and its ``freq`` gains the number of above-threshold queries whose best
    index landed in it — the scatter-max / scatter-add of
    ``SemanticCache.apply_probe``, multiplicity included, folded into the
    same launch so the (C,) metadata arrays make ONE HBM round-trip instead
    of a separate gather/scatter dispatch.  ``qmask`` (Q, 1) zeroes padded
    query rows so they can never touch a slot.
    """
    p = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((p == 0) & (j == 0))
    def _init():
        score_ref[...] = jnp.full_like(score_ref, NEG_INF)
        idx_ref[...] = jax.lax.broadcasted_iota(jnp.int32, idx_ref.shape, 1)

    @pl.when(p == 0)
    def _scan():
        s, i = _topk_tile(q_ref[...].astype(jnp.float32),
                          k_ref[...].astype(jnp.float32),
                          valid_ref[...], score_ref[...], idx_ref[...],
                          block_c=block_c, k=k, c_block_index=j)
        score_ref[...] = s
        idx_ref[...] = i

    @pl.when(p == 1)
    def _touch():
        best_i = idx_ref[:, 0:1]                            # (BQ, 1)
        best_s = score_ref[:, 0:1]
        # invalid slots score NEG_INF, so the threshold test subsumes the
        # oracle's take(valid, idx) aliveness check
        hit = (best_s >= threshold) & (qmask_ref[...] != 0)
        slots = j * block_c + jax.lax.broadcasted_iota(
            jnp.int32, (best_i.shape[0], block_c), 1)       # (BQ, BC)
        match = hit & (best_i == slots)
        counts = jnp.sum(match.astype(jnp.int32), axis=0,
                         keepdims=True)                     # (1, BC)
        clock = clock_ref[0]
        lu = lu_ref[...]
        lu_out[...] = jnp.where(counts > 0, jnp.maximum(lu, clock), lu)
        fr_out[...] = fr_ref[...] + counts


@functools.partial(jax.jit, static_argnames=("k", "block_c", "threshold",
                                             "interpret"))
def similarity_topk_touch_kernel(queries: jax.Array, qmask: jax.Array,
                                 keys: jax.Array, valid: jax.Array,
                                 last_used: jax.Array, freq: jax.Array,
                                 clock: jax.Array, *, k: int,
                                 threshold: float, block_c: int = 512,
                                 interpret: bool = False):
    """queries: (Q, D) — ONE query block (ops.py pads Q whole); qmask: (Q,)
    bool/int, 0 for padded rows; keys: (C, D); valid: (C,) bool/int;
    last_used/freq: (C,) int32; clock: scalar int32 (rides SMEM).

    Returns (idx (Q, k) int32, score (Q, k) f32, last_used (C,) int32,
    freq (C,) int32).  Grid (2, C // block_c): the pass dim is outermost so
    the top-k output blocks are final before the touch pass reads them; the
    lu/fr blocks are only mapped on pass 1, so each is read+written exactly
    once."""
    Q, D = queries.shape
    C = keys.shape[0]
    assert C % block_c == 0, (C, block_c)
    assert k <= block_c, (k, block_c)

    kernel = functools.partial(_topk_touch_kernel, block_c=block_c, k=k,
                               threshold=threshold)
    # lu/fr in/out blocks advance only during the touch pass; pinning them
    # to block 0 during pass 0 keeps Pallas from flushing half-done state
    pass1 = lambda p, j: (0, jnp.where(p == 1, j, 0))
    row = lambda x: x.astype(jnp.int32).reshape(1, C)
    idx, score, lu, fr = pl.pallas_call(
        kernel,
        grid=(2, C // block_c),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),              # clock (1,)
            pl.BlockSpec((Q, D), lambda p, j: (0, 0)),
            pl.BlockSpec((Q, 1), lambda p, j: (0, 0)),
            pl.BlockSpec((block_c, D),
                         lambda p, j: (jnp.where(p == 0, j, 0), 0)),
            pl.BlockSpec((1, block_c),
                         lambda p, j: (0, jnp.where(p == 0, j, 0))),
            pl.BlockSpec((1, block_c), pass1),
            pl.BlockSpec((1, block_c), pass1),
        ],
        out_specs=[
            pl.BlockSpec((Q, k), lambda p, j: (0, 0)),
            pl.BlockSpec((Q, k), lambda p, j: (0, 0)),
            pl.BlockSpec((1, block_c), pass1),
            pl.BlockSpec((1, block_c), pass1),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Q, k), jnp.int32),
            jax.ShapeDtypeStruct((Q, k), jnp.float32),
            jax.ShapeDtypeStruct((1, C), jnp.int32),
            jax.ShapeDtypeStruct((1, C), jnp.int32),
        ],
        interpret=interpret,
        name="similarity_topk_touch",
    )(clock.reshape(1).astype(jnp.int32), queries,
      qmask.astype(jnp.int32).reshape(Q, 1), keys, row(valid),
      row(last_used), row(freq))
    return idx, score, lu[0], fr[0]
