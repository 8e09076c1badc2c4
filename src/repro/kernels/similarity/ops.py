"""Jit'd public wrapper for the similarity lookup.

Selects the Pallas TPU kernel on TPU backends and the jnp oracle elsewhere
(this container is CPU-only; the kernel is exercised via interpret=True in
tests).  Handles padding to block multiples.

Each public entry point resolves ``impl="auto"`` host-side, then calls its
jitted body with the resolved name.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.similarity.kernel import (similarity_topk_batched_kernel,
                                             similarity_topk_touch_kernel)
from repro.kernels.similarity.ref import (similarity_lookup_ref,
                                          similarity_topk_batched_ref,
                                          similarity_topk_ref,
                                          similarity_topk_touch_ref)


def _backend_is_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(impl: str) -> str:
    return ("pallas" if _backend_is_tpu() else "ref") if impl == "auto" \
        else impl


def resolve_impl(impl: str) -> str:
    """Resolve ``impl="auto"`` to the backend's concrete implementation.

    Every entry point (here, kernels/ivf_pq, parallel/sharding) calls this
    exactly once in its host-side wrapper and passes the resolved name
    down, so the jitted inner never re-resolves at trace time.
    """
    return _resolve(impl)


def similarity_lookup(queries: jax.Array, keys: jax.Array, valid: jax.Array,
                      *, impl: str = "auto", block_q: int = 128,
                      block_c: int = 512):
    """Batched nearest-neighbour cache lookup.

    queries: (Q, D) unit-norm descriptors; keys: (C, D); valid: (C,) bool.
    Returns (best_idx (Q,) int32, best_score (Q,) f32).

    impl: auto | pallas | pallas_interpret | ref
    """
    return _similarity_lookup(queries, keys, valid, impl=_resolve(impl),
                              block_q=block_q, block_c=block_c)


@functools.partial(jax.jit, static_argnames=("impl", "block_q", "block_c"))
def _similarity_lookup(queries, keys, valid, *, impl, block_q, block_c):
    if impl == "ref":
        return similarity_lookup_ref(queries, keys, valid)
    # the nearest neighbour is the top-1 of the shared scan kernel
    idx, score = _topk_pallas(queries[None], keys[None], valid[None], 1,
                              impl=impl, block_q=block_q, block_c=block_c)
    return idx[0, :, 0], score[0, :, 0]


def similarity_topk(queries: jax.Array, keys: jax.Array, valid: jax.Array,
                    k: int, *, impl: str = "auto", block_q: int = 128,
                    block_c: int = 512):
    """Batched top-k cache lookup (the sharded-cluster merge primitive).

    queries: (Q, D) unit-norm descriptors; keys: (C, D); valid: (C,) bool.
    Returns (idx (Q, k) int32, score (Q, k) f32), scores descending, ties
    toward the lower cache index.  k must be <= C.

    impl: auto | pallas | pallas_interpret | ref
    """
    return _similarity_topk(queries, keys, valid, k=k, impl=_resolve(impl),
                            block_q=block_q, block_c=block_c)


@functools.partial(jax.jit,
                   static_argnames=("k", "impl", "block_q", "block_c"))
def _similarity_topk(queries, keys, valid, *, k, impl, block_q, block_c):
    C = keys.shape[0]
    assert k <= C, (k, C)
    if impl == "ref":
        return similarity_topk_ref(queries, keys, valid, k)

    idx, score = _topk_pallas(queries[None], keys[None], valid[None], k,
                              impl=impl, block_q=block_q, block_c=block_c)
    return idx[0], score[0]


def similarity_topk_touch(queries: jax.Array, keys: jax.Array,
                          valid: jax.Array, k: int, last_used: jax.Array,
                          freq: jax.Array, clock: jax.Array, *,
                          threshold: float, mask: jax.Array = None,
                          impl: str = "auto", block_c: int = 512):
    """Fused top-k lookup + LRU-touch epilogue (one HBM pass over the cache
    metadata instead of lookup-then-gather/scatter).

    queries: (Q, D) unit-norm descriptors; keys: (C, D); valid: (C,) bool;
    last_used/freq: (C,) int32 LRU metadata; clock: scalar int32.  Returns
    (idx (Q, k) int32, score (Q, k) f32, last_used (C,) int32, freq (C,)
    int32): the top-k of ``similarity_topk`` plus the metadata with every
    above-``threshold`` top-1 winner touched (``last_used`` scatter-maxed
    to ``clock``, ``freq`` scatter-added with multiplicity) — exactly
    ``SemanticCache.apply_probe``'s update.  k must be <= C.  ``mask``
    (Q,) bool rows that are False never touch (the engine's padded rows).

    impl: auto | pallas | pallas_interpret | ref
    """
    return _similarity_topk_touch(queries, keys, valid, last_used, freq,
                                  clock, mask, k=k, threshold=threshold,
                                  impl=_resolve(impl), block_c=block_c)


@functools.partial(jax.jit,
                   static_argnames=("k", "threshold", "impl", "block_c"))
def _similarity_topk_touch(queries, keys, valid, last_used, freq, clock,
                           mask, *, k, threshold, impl, block_c):
    C = keys.shape[0]
    assert k <= C, (k, C)
    if impl == "ref":
        return similarity_topk_touch_ref(queries, keys, valid, k, last_used,
                                         freq, clock, threshold, mask=mask)

    Q, D = queries.shape
    bc = max(min(block_c, max(8, C)), k)     # kernel needs k <= block_c
    pad_q = (-Q) % 8                         # single q-block: pad Q whole
    pad_c = (-C) % bc
    qp = jnp.pad(queries, ((0, pad_q), (0, 0)))
    qmask = (jnp.ones((Q,), jnp.int32) if mask is None
             else mask.astype(jnp.int32))
    qmask = jnp.pad(qmask, (0, pad_q))
    kp = jnp.pad(keys, ((0, pad_c), (0, 0)))
    vp = jnp.pad(valid.astype(jnp.int32), (0, pad_c))
    lup = jnp.pad(last_used.astype(jnp.int32), (0, pad_c))
    frp = jnp.pad(freq.astype(jnp.int32), (0, pad_c))
    idx, score, lu, fr = similarity_topk_touch_kernel(
        qp, qmask, kp, vp, lup, frp, clock, k=k, threshold=threshold,
        block_c=bc, interpret=(impl == "pallas_interpret"))
    return idx[:Q], score[:Q], lu[:C], fr[:C]


def similarity_topk_batched(queries: jax.Array, keys: jax.Array,
                            valid: jax.Array, k: int, *, impl: str = "auto",
                            block_q: int = 128, block_c: int = 512):
    """Grouped-query top-k lookup: batch entry ``n`` probes key matrix ``n``
    only — one dispatch for N per-node local-shard lookups (the batched
    engine step's local rung).

    queries: (N, Q, D) unit-norm descriptors; keys: (N, C, D); valid: (N, C)
    bool.  Returns (idx (N, Q, k) int32, score (N, Q, k) f32), scores
    descending, ties toward the lower cache index — the indices of a
    vmapped ``similarity_topk_ref``, scores to f32 rounding (the kernel
    tiles the dot products differently).  k must be <= C.

    impl: auto | pallas | pallas_interpret | ref
    """
    return _similarity_topk_batched(queries, keys, valid, k=k,
                                    impl=_resolve(impl), block_q=block_q,
                                    block_c=block_c)


@functools.partial(jax.jit,
                   static_argnames=("k", "impl", "block_q", "block_c"))
def _similarity_topk_batched(queries, keys, valid, *, k, impl, block_q,
                             block_c):
    C = keys.shape[1]
    assert k <= C, (k, C)
    if impl == "ref":
        return similarity_topk_batched_ref(queries, keys, valid, k)
    return _topk_pallas(queries, keys, valid, k, impl=impl, block_q=block_q,
                        block_c=block_c)


def _topk_pallas(queries, keys, valid, k, *, impl, block_q, block_c):
    """Pad (N, Q, D) queries and (N, C, D) keys to block multiples and run
    the shared scan kernel; padded slots are invalid, padded query rows
    are sliced off."""
    Q = queries.shape[1]
    C = keys.shape[1]
    bq = min(block_q, max(8, Q))
    bc = max(min(block_c, max(8, C)), k)     # kernel needs k <= block_c
    pad_q = (-Q) % bq
    pad_c = (-C) % bc
    qp = jnp.pad(queries, ((0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(keys, ((0, 0), (0, pad_c), (0, 0)))
    vp = jnp.pad(valid.astype(jnp.int32), ((0, 0), (0, pad_c)))
    idx, score = similarity_topk_batched_kernel(
        qp, kp, vp, k=k, block_q=bq, block_c=bc,
        interpret=(impl == "pallas_interpret"))
    return idx[:, :Q], score[:, :Q]
