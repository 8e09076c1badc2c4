"""Jit'd public wrapper for the two-stage IVF-PQ digest probe.

Mirrors ``kernels/similarity/ops.py``: the public entry resolves
``impl="auto"`` exactly once host-side, pads the query tile, and runs its
jitted body through ``repro.obs.profile.record_op`` so profiled runs see
``kernel/ivf_pq_probe/<resolved-impl>/...`` metrics (never ``auto``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.ivf_pq.kernel import ivf_pq_probe_kernel
from repro.kernels.ivf_pq.ref import ivf_pq_probe_ref
from repro.kernels.similarity.ops import resolve_impl
from repro.obs.profile import active, ivf_pq_probe_bytes, record_op


def ivf_pq_probe(queries: jax.Array, home: jax.Array, centroids: jax.Array,
                 cent_valid: jax.Array, codes: jax.Array,
                 slot_valid: jax.Array, slot_owner: jax.Array,
                 codebook: jax.Array, *, k: int, n_probe: int,
                 impl: str = "auto"):
    """Two-stage ANN probe over a packed IVF-PQ board index.

    queries: (Q, D) unit-norm descriptors; home: (Q,) int32 owning-cluster
    id per query (a probe never matches its own cluster's rows); index
    arrays as documented in ref.py.  Returns (idx (Q, k) int32 flat
    ``list * cap + slot`` ids, score (Q, k) f32), scores descending, ties
    toward the lower flat index — the indices of ``ivf_pq_probe_ref``,
    scores to f32 rounding.

    impl: auto | pallas | pallas_interpret | ref
    """
    impl = resolve_impl(impl)
    fn = functools.partial(_ivf_pq_probe, k=k, n_probe=n_probe, impl=impl)
    if active() is None:
        return fn(queries, home, centroids, cent_valid, codes, slot_valid,
                  slot_owner, codebook)
    L, cap, S = (int(s) for s in codes.shape)
    return record_op(
        "ivf_pq_probe", impl, fn,
        (queries, home, centroids, cent_valid, codes, slot_valid,
         slot_owner, codebook),
        ivf_pq_probe_bytes(int(queries.shape[0]), L, cap, S,
                           int(queries.shape[1])))


@functools.partial(jax.jit, static_argnames=("k", "n_probe", "impl"))
def _ivf_pq_probe(queries, home, centroids, cent_valid, codes, slot_valid,
                  slot_owner, codebook, *, k, n_probe, impl):
    if impl == "ref":
        idx, score, _ = ivf_pq_probe_ref(
            queries, home, centroids, cent_valid, codes, slot_valid,
            slot_owner, codebook, k=k, n_probe=n_probe)
        return idx, score

    Q = queries.shape[0]
    pad_q = (-Q) % 8
    qp = jnp.pad(queries, ((0, pad_q), (0, 0)))
    # padded rows get home=-1 (matches no owner); their outputs are sliced off
    hp = jnp.pad(home.astype(jnp.int32), (0, pad_q), constant_values=-1)
    idx, score, _ = ivf_pq_probe_kernel(
        qp, hp, centroids, cent_valid, codes, slot_valid, slot_owner,
        codebook, k=k, n_probe=n_probe,
        interpret=(impl == "pallas_interpret"))
    return idx[:Q], score[:Q]
