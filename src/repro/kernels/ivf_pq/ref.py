"""Pure-jnp oracle for the two-stage IVF-PQ digest probe.

The index layout (built by ``core/digest.py::IVFPQIndex``) packs the region
board's advertised rows into ``n_lists`` inverted lists of ``list_cap`` slots:

  centroids   (L, D)  f32   coarse quantizer (one per inverted list)
  cent_valid  (L,)    bool  list has at least one live slot
  codes       (L, cap, S)   per-subspace PQ codes of the residual key -
                            centroid, int in [0, 256)
  slot_valid  (L, cap) bool live slot (tombstoned / padded slots are False)
  slot_owner  (L, cap) i32  owning cluster (probes exclude their own rows)
  codebook    (S, 256, D//S) f32 shared residual codebook

Stage 1 scores every query against every centroid and keeps the top
``n_probe`` lists; stage 2 reconstructs each probed list's keys as
``centroid + decode(codes)`` and runs the usual masked top-k.  Decoding is a
one-hot matmul (``onehot(codes_s) @ codebook[s]``): each output row copies
exactly one codebook entry, so the decode is bitwise identical however the
batch dimensions are blocked — the property the kernel's exact-index test
leans on.

Flat candidate index = ``list * cap + slot``; callers map it through the
index's ``slot_rid`` to recover the global digest row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def decode_pq_codes(codebook: jax.Array, codes: jax.Array) -> jax.Array:
    """codes (..., S) int -> residual vectors (..., D) f32.

    One-hot matmul per subspace: every row of the one-hot has exactly one
    1.0, so the contraction copies codebook entries exactly (no f32
    reassociation) — safe to share between oracle and kernel reasoning.
    """
    S = codebook.shape[0]
    nd = codes.ndim - 1
    parts = []
    for s in range(S):
        onehot = (codes[..., s][..., None]
                  == jnp.arange(256, dtype=jnp.int32)).astype(jnp.float32)
        parts.append(jax.lax.dot_general(
            onehot, codebook[s].astype(jnp.float32),
            (((nd,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    return jnp.concatenate(parts, axis=-1)


def ivf_pq_probe_ref(queries: jax.Array, home: jax.Array,
                     centroids: jax.Array, cent_valid: jax.Array,
                     codes: jax.Array, slot_valid: jax.Array,
                     slot_owner: jax.Array, codebook: jax.Array, *,
                     k: int, n_probe: int):
    """queries (Q, D); home (Q,) owning-cluster id per query (its own rows
    are excluded).  Returns (idx (Q, k) int32 flat slot ids, score (Q, k)
    f32, sel (Q, n_probe) int32 probed list ids), scores descending, ties
    toward the lower flat index.
    """
    L, cap, S = codes.shape
    q = queries.astype(jnp.float32)

    coarse = jax.lax.dot_general(
        q, centroids.astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (Q, L)
    coarse = jnp.where(cent_valid[None, :] != 0, coarse, NEG_INF)
    _, sel = jax.lax.top_k(coarse, n_probe)                 # (Q, n_probe)
    selmask = jnp.any(
        sel[:, :, None] == jnp.arange(L, dtype=jnp.int32)[None, None, :],
        axis=1)                                             # (Q, L)

    decoded = decode_pq_codes(codebook, codes.astype(jnp.int32))
    keys = centroids.astype(jnp.float32)[:, None, :] + decoded  # (L, cap, D)
    scores = jax.lax.dot_general(
        q, keys.reshape(L * cap, -1), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                 # (Q, L*cap)
    ok = ((slot_valid.reshape(-1)[None, :] != 0)
          & (slot_owner.reshape(-1).astype(jnp.int32)[None, :]
             != home.astype(jnp.int32)[:, None])
          & jnp.repeat(selmask, cap, axis=1))
    scores = jnp.where(ok, scores, NEG_INF)
    score, idx = jax.lax.top_k(scores, k)
    return idx.astype(jnp.int32), score, sel.astype(jnp.int32)
