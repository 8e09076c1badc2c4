"""Logical-axis sharding rules -> NamedSharding (MaxText-style).

Every parameter / cache / activation dimension carries a *logical* name
(``embed``, ``heads``, ``cache_seq``, ...).  A rule set maps logical names to
mesh axes per workload.  ``logical_to_sharding`` applies a rule only when the
dimension is divisible by the mesh-axis product and the mesh axis is not
already used by an earlier dimension of the same tensor — otherwise that
dimension stays replicated (never uneven padding surprises).

Rule sets:

* ``RULES_TRAIN`` — batch over (pod, data); TP dims over model; FSDP storage
  sharding of the ``embed`` param dim over data (ZeRO-3 style: GSPMD inserts
  the gather at use); activations 2D-sharded (batch x embed) inside scans so
  the remat stash stays within HBM at 4k x 256 global batch.
* ``RULES_SERVE`` — batch over (pod, data); TP over model; the KV cache
  shards kv_heads over model when divisible, else ``cache_seq`` over model —
  the seq-sharded layout is exactly flash-decode: GSPMD partitions the
  softmax reductions over the cache axis.

Cache-probe collectives
-----------------------

This module also owns the device-side probes of the cooperative cache
ladder — each one is designed to be a SINGLE dispatch however wide the
tier gets, which is what keeps the engine's per-step ladder bound constant:

* ``cluster_topk_lookup`` — the peer rung as a pooled collective: (all
  nodes' queries) x (all shards) in one ``similarity_topk`` kernel call
  over the pooled shard stack (merge semantics shared with the batched
  kernel path, bit-exact against the pooled oracle).  The
  ladder's rung implementations (``core/tiers.py::LocalRung``/
  ``PeerRung``) issue the equivalent batched probes directly through
  ``similarity_topk_batched`` — one federation-wide dispatch per rung —
  against the pre-step state snapshot in their ``ProbeContext``.
* ``federated_digest_lookup`` (and its ``_quantized`` variant) — the
  remote rung's digest probe: every home cluster's miss batch against
  every OTHER cluster's top-M digest in one kernel call.  The quantized
  variant takes the int8 codes + per-row scales the region actually
  received over the wire (``core/digest.py``) and dequantizes inside the
  same jitted dispatch — no new kernel surface, int8-resident operands.
  Digests are deliberately stale (refreshed every ``digest_interval``
  steps), and staleness only ever *under-reports*: a returned candidate
  is a hint that the caller MUST confirm against the candidate cluster's
  authoritative shards — a failed confirm is counted ``digest_false_hit``
  and falls through to the cloud, so a stale digest can cost a wasted
  probe but never fabricate a hit, and an entry admitted since the last
  refresh is merely invisible until the next one.  Quantization obeys the
  same contract: the confirm runs at full precision, so int8 rounding can
  only demote a near-threshold candidate to a recoverable miss.
* ``federated_digest_lookup_ivfpq`` — the same probe over the board's
  packed two-stage IVF-PQ index (``kernels/ivf_pq``): still ONE dispatch,
  but the scan reads ``n_sub + 2`` bytes per advertised slot instead of a
  full key row, which is what lets a region board advertise 10M+ keys.
  PQ approximation error inherits the int8 contract above: candidates are
  hints, the confirm is authoritative, recall loss only under-reports.
* ``sharded_topk_lookup`` — the same peer-rung collective as a
  ``shard_map`` over a real ``cache`` mesh axis: each device computes its
  local top-k and one all-gather of (k idx, k score) per shard replaces
  shipping whole shards around.  ``sharded_local_topk`` is the local
  rung on that mesh: each device probes only the shard it holds with its
  own node's queries, no communication.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Ordered (logical_axis -> mesh axes) with fallbacks.

    rules maps a logical name to a tuple of *candidate* assignments; the
    first candidate whose mesh axes are free and divide the dim is used.
    Each candidate is a tuple of mesh-axis names (multi-axis sharding).
    """

    rules: Dict[str, Tuple[Tuple[str, ...], ...]]

    def spec_for(self, axes: Sequence[Optional[str]], shape: Sequence[int],
                 mesh: Mesh) -> P:
        used: set = set()
        out = []
        for dim, name in zip(shape, axes):
            chosen = None
            for cand in self.rules.get(name or "", ()):
                cand = tuple(a for a in cand if a in mesh.shape)
                if not cand:
                    continue
                size = int(np.prod([mesh.shape[a] for a in cand]))
                if size <= 1:
                    continue
                if any(a in used for a in cand):
                    continue
                if dim % size != 0:
                    continue
                chosen = cand
                break
            if chosen:
                used.update(chosen)
                out.append(chosen if len(chosen) > 1 else chosen[0])
            else:
                out.append(None)
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def sharding_for(self, axes, shape, mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec_for(axes, shape, mesh))


def _mk(d: Dict[str, Sequence[Sequence[str]]]) -> ShardingRules:
    return ShardingRules({k: tuple(tuple(c) for c in v) for k, v in d.items()})


RULES_TRAIN = _mk({
    "batch": [("pod", "data"), ("data",)],
    "moe_capacity": [("data",)],
    "ssm_heads": [("model",)],
    "vocab": [("model",)],
    "heads": [("model",)],
    "kv_heads": [("model",)],
    # NOTE: a "qk_dim" -> model fallback (head-dim TP for indivisible head
    # counts) was evaluated and REFUTED: it multiplies activation all-reduces
    # (llava train collective 19.7 -> 461.7 s; whisper prefill 0.07 -> 104.8 s).
    # Attention stays replicated over 'model' for indivisible head counts.
    "mlp": [("model",)],
    "experts": [("model",)],
    "ssm_inner": [("model",)],
    "kv_lora": [("model",)],
    # FSDP storage sharding of the non-TP param dim
    "embed": [("data",)],
    # activations (2D): embed over model inside scan bodies
    "act_embed": [("model",)],
})

RULES_SERVE = _mk({
    "batch": [("pod", "data"), ("data",)],
    "moe_capacity": [("data",)],
    "ssm_heads": [("model",)],
    "vocab": [("model",)],
    "heads": [("model",)],
    "kv_heads": [("model",)],
    # NOTE: a "qk_dim" -> model fallback (head-dim TP for indivisible head
    # counts) was evaluated and REFUTED: it multiplies activation all-reduces
    # (llava train collective 19.7 -> 461.7 s; whisper prefill 0.07 -> 104.8 s).
    # Attention stays replicated over 'model' for indivisible head counts.
    "mlp": [("model",)],
    "experts": [("model",)],
    "ssm_inner": [("model",)],
    "kv_lora": [("model",)],
    "embed": [("data",)],          # weight-gathered serving (fits 72B on v5e-256)
    "act_embed": [("model",)],
    # KV cache: kv_heads over model when divisible (rule above), else the
    # cache_seq dim shards over model => GSPMD flash-decode
    "cache_seq": [("model",)],
})

# long_500k: global_batch=1 — nothing to gain from batch sharding; spread the
# cache sequence over everything instead.
RULES_SERVE_LONG = _mk({
    "moe_capacity": [("data",)],
    "ssm_heads": [("model",)],
    "vocab": [("model",)],
    "heads": [("model",)],
    "kv_heads": [("model",)],
    # NOTE: a "qk_dim" -> model fallback (head-dim TP for indivisible head
    # counts) was evaluated and REFUTED: it multiplies activation all-reduces
    # (llava train collective 19.7 -> 461.7 s; whisper prefill 0.07 -> 104.8 s).
    # Attention stays replicated over 'model' for indivisible head counts.
    "mlp": [("model",)],
    "experts": [("model",)],
    "ssm_inner": [("model",)],
    "kv_lora": [("model",)],
    "embed": [("data",)],
    "act_embed": [("model",)],
    "cache_seq": [("pod", "data", "model"), ("data", "model"), ("model",)],
})


def logical_to_sharding(tree_axes: dict, tree_shapes: dict, mesh: Mesh,
                        rules: ShardingRules) -> dict:
    """Flat-dict version: {name: axes} + {name: ShapeDtypeStruct} -> shardings."""
    return {k: rules.sharding_for(tree_axes[k], tree_shapes[k].shape, mesh)
            for k in tree_axes}


# ---------------------------------------------------------------------------
# Activation sharding hook (used inside model scan bodies)
# ---------------------------------------------------------------------------

_ACTIVE_SHARDER = None


@dataclasses.dataclass
class ActivationSharder:
    mesh: Mesh
    rules: ShardingRules

    def constrain(self, x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
        spec = self.rules.spec_for(axes, x.shape, self.mesh)
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))


class set_activation_sharder:
    """Context manager installing the activation-constraint hook."""

    def __init__(self, mesh: Optional[Mesh], rules: Optional[ShardingRules]):
        self.sharder = ActivationSharder(mesh, rules) if mesh is not None else None

    def __enter__(self):
        global _ACTIVE_SHARDER
        self._prev = _ACTIVE_SHARDER
        _ACTIVE_SHARDER = self.sharder
        return self.sharder

    def __exit__(self, *exc):
        global _ACTIVE_SHARDER
        _ACTIVE_SHARDER = self._prev
        return False


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """No-op unless a sharder is installed (single-device tests)."""
    if _ACTIVE_SHARDER is None:
        return x
    return _ACTIVE_SHARDER.constrain(x, axes)


def current_sharder() -> Optional[ActivationSharder]:
    return _ACTIVE_SHARDER


# ---------------------------------------------------------------------------
# Cache-axis sharded cluster lookup (CoIC cooperative edge tier)
# ---------------------------------------------------------------------------


def _merge_shard_topk(shard_idx: jax.Array, shard_scores: jax.Array,
                      out_k: int):
    """Merge per-shard top-k' candidates: (N, Q, k') -> (Q, out_k).

    Candidates are laid out shard-major, which is global-index order for
    contiguous shards, and each shard's list is score-descending with
    index-ordered ties — so ``lax.top_k``'s position tie-break reproduces a
    single ``top_k`` over the full concatenated cache row bit-for-bit.
    """
    n, q, k_local = shard_scores.shape
    cand_s = jnp.moveaxis(shard_scores, 0, 1).reshape(q, n * k_local)
    cand_i = jnp.moveaxis(shard_idx, 0, 1).reshape(q, n * k_local)
    top_s, pos = jax.lax.top_k(cand_s, out_k)
    top_i = jnp.take_along_axis(cand_i, pos, axis=1)
    return top_i.astype(jnp.int32), top_s


@partial(jax.jit, static_argnames=("k", "impl"))
def cluster_topk_lookup(queries: jax.Array, keys: jax.Array,
                        valid: jax.Array, k: int, *, impl: str = "auto"):
    """Cluster-wide lookup over stacked per-node cache shards, one jitted
    call instead of N host round-trips.

    queries: (Q, D) replicated; keys: (N, C, D); valid: (N, C).
    Returns (idx (Q, k) int32 global indices in [0, N*C), score (Q, k) f32)
    — equal to ``similarity_topk`` over the pooled ``keys.reshape(N*C, D)``.
    """
    from repro.kernels.similarity import similarity_topk

    n, c, _ = keys.shape
    local_idx, local_score = jax.vmap(
        lambda kk, vv: similarity_topk(queries, kk, vv, min(k, c), impl=impl)
    )(keys, valid)                                       # (N, Q, k'), k'<=k
    offsets = (jnp.arange(n, dtype=jnp.int32) * c)[:, None, None]
    return _merge_shard_topk(local_idx + offsets, local_score, min(k, n * c))


def federated_digest_lookup(queries: jax.Array, digests: jax.Array,
                            valid: jax.Array, k: int = 1, *,
                            impl: str = "auto"):
    """Cross-cluster digest probe — the federation tier's remote rung,
    ONE dispatch regardless of cluster count.

    queries: (K, B, D) — group k holds home-cluster k's miss batch (pad
    rows are fine: the caller masks them).  digests: (K, M, D) per-cluster
    digest matrices (top-M hottest entry keys, possibly stale); valid:
    (K, M).  Each group probes EVERY cluster's digest EXCEPT its own — a
    home miss already scanned the home cluster's full shards, so a home
    digest row can only be redundant or stale.

    Returns (idx (K, B, k) int32 global digest indices in [0, K*M), score
    (K, B, k) f32): row (h, b) equals ``similarity_topk_batched`` over the
    pooled digest matrix with cluster h's rows masked out — candidate
    cluster = idx // M.  A digest hit is a *hint*: the caller must confirm
    against the candidate cluster's authoritative shards and treat a
    confirm-miss as a digest false hit (stale digest), falling through to
    the cloud.

    Implemented as one ``similarity_topk_batched`` call over the
    home-broadcast pooled digests — the same kernel as the ladder's other
    rungs (Pallas on TPU), so digests add no new kernel surface.  The K^2*M
    broadcast is digest-sized, not cache-sized: that is the point of
    probing digests instead of shards.

    Host wrapper: ``impl="auto"`` resolves exactly ONCE here (never inside
    the trace).
    """
    from repro.kernels.similarity.ops import resolve_impl

    return _federated_digest_lookup(queries, digests, valid, k=k,
                                    impl=resolve_impl(impl))


@partial(jax.jit, static_argnames=("k", "impl"))
def _federated_digest_lookup(queries, digests, valid, *, k, impl):
    from repro.kernels.similarity import similarity_topk_batched

    K, M, D = digests.shape
    pooled = jnp.broadcast_to(digests.reshape(1, K * M, D), (K, K * M, D))
    # per-home validity: mask out the home cluster's digest rows
    not_home = ~jnp.eye(K, dtype=bool)                   # (K_home, K)
    valid_h = (valid[None, :, :] & not_home[:, :, None]).reshape(K, K * M)
    return similarity_topk_batched(queries, pooled, valid_h, k, impl=impl)


def federated_digest_lookup_quantized(queries: jax.Array, codes: jax.Array,
                                      scales: jax.Array, valid: jax.Array,
                                      k: int = 1, *, impl: str = "auto"):
    """``federated_digest_lookup`` over int8-quantized digests.

    codes: (K, M, D) int8 symmetric per-row codes; scales: (K, M) f32
    per-row scales — exactly the wire format the region received
    (``core/digest.py::DigestPublisher``), kept int8-resident and
    dequantized inside this one jitted dispatch.  queries/valid/k as in
    ``federated_digest_lookup``; same home-cluster masking, same kernel,
    same resolve-once host wrapper.
    """
    from repro.kernels.similarity.ops import resolve_impl

    return _federated_digest_lookup_quantized(queries, codes, scales, valid,
                                              k=k, impl=resolve_impl(impl))


@partial(jax.jit, static_argnames=("k", "impl"))
def _federated_digest_lookup_quantized(queries, codes, scales, valid, *, k,
                                       impl):
    digests = codes.astype(jnp.float32) * scales[..., None]
    return _federated_digest_lookup(queries, digests, valid, k=k, impl=impl)


def federated_digest_lookup_ivfpq(queries: jax.Array, index, k: int = 1, *,
                                  n_probe: int, impl: str = "auto"):
    """``federated_digest_lookup`` over the board's packed IVF-PQ sidecar —
    the remote rung's probe once a region board outgrows brute scanning.

    queries: (K, B, D) as in ``federated_digest_lookup``; ``index`` is a
    ``core/digest.py::IVFPQIndex`` (host arrays).  ONE ``ivf_pq_probe``
    kernel dispatch covers all K home batches: the home-cluster exclusion
    runs inside the kernel (``slot_owner != home``), replacing the pooled
    broadcast masking of the brute probes, and the two-stage scan reads
    ``n_sub + 2`` bytes/slot instead of a full digest row.

    Returns (idx (K, B, k) int32 GLOBAL digest row ids in [0, K*M) — the
    kernel's flat slot winners mapped through ``slot_rid`` — and score
    (K, B, k) f32 of the PQ-APPROXIMATED similarity).  Candidates from
    empty slots carry id -1 and NEG_INF scores, so any caller-side score
    threshold removes them.  Approximation is under-report-safe: every
    candidate still passes the caller's authoritative confirm, so a PQ
    error can only demote a hit to a recoverable miss, never fabricate.
    """
    from repro.kernels.similarity.ops import resolve_impl

    return _federated_digest_lookup_ivfpq(
        queries, jnp.asarray(index.centroids),
        jnp.asarray(index.cent_valid), jnp.asarray(index.codes),
        jnp.asarray(index.slot_valid), jnp.asarray(index.slot_owner),
        jnp.asarray(index.codebook), jnp.asarray(index.slot_rid), k=k,
        n_probe=n_probe, impl=resolve_impl(impl))


@partial(jax.jit, static_argnames=("k", "n_probe", "impl"))
def _federated_digest_lookup_ivfpq(queries, centroids, cent_valid, codes,
                                   slot_valid, slot_owner, codebook,
                                   slot_rid, *, k, n_probe, impl):
    from repro.kernels.ivf_pq.ops import _ivf_pq_probe

    K, B, D = queries.shape
    home = jnp.repeat(jnp.arange(K, dtype=jnp.int32), B)
    idx, score = _ivf_pq_probe(queries.reshape(K * B, D), home, centroids,
                               cent_valid, codes, slot_valid, slot_owner,
                               codebook, k=k, n_probe=n_probe, impl=impl)
    rid = jnp.take(slot_rid.reshape(-1), idx)            # flat slot -> rid
    return rid.reshape(K, B, k), score.reshape(K, B, k)


def sharded_topk_lookup(queries: jax.Array, keys: jax.Array,
                        valid: jax.Array, k: int, mesh: Mesh,
                        axis_name: str = "cache", *, impl: str = "auto"):
    """shard_map version of ``cluster_topk_lookup``: each device owns one
    cache shard, computes its local top-k, and one all-gather of (k idx,
    k score) per shard replaces shipping whole shards around.

    queries: (Q, D) replicated; keys: (N, C, D) sharded over ``axis_name``
    on dim 0; valid: (N, C) likewise.  N must equal the mesh axis size.
    Returns replicated (idx (Q, k), score (Q, k)), identical to the
    single-device ``cluster_topk_lookup`` result.
    """
    from jax.sharding import PartitionSpec as P

    from repro.kernels.similarity import similarity_topk

    n, c, _ = keys.shape
    assert n == mesh.shape[axis_name], (n, dict(mesh.shape))
    k_local = min(k, c)

    def body(q, k_shard, v_shard):
        kk, vv = k_shard[0], v_shard[0]                  # (1,C,D) -> (C,D)
        idx, score = similarity_topk(q, kk, vv, k_local, impl=impl)
        idx = idx + jax.lax.axis_index(axis_name).astype(jnp.int32) * c
        g_idx = jax.lax.all_gather(idx, axis_name)       # (N, Q, k')
        g_score = jax.lax.all_gather(score, axis_name)
        return _merge_shard_topk(g_idx, g_score, min(k, n * c))

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(axis_name), P(axis_name)),
        out_specs=(P(), P()),
        check_vma=False,
    )(queries, keys, valid)


def sharded_local_topk(queries: jax.Array, keys: jax.Array,
                       valid: jax.Array, k: int, mesh: Mesh,
                       axis_name: str = "cache", *, impl: str = "auto"):
    """The local rung over a ``cache`` mesh: queries (N, B, D), keys
    (N, C, D) and valid (N, C) sharded over ``axis_name`` on dim 0, node
    ``n``'s queries scored against shard ``n`` on the device that holds
    it.  Returns (idx (N, B, k), score (N, B, k)) sharded likewise, equal
    to ``similarity_topk_batched`` over the stacks (a Pallas kernel
    cannot be partitioned by the compiler, so each device runs it on its
    own block)."""
    from repro.kernels.similarity import similarity_topk_batched

    assert keys.shape[0] == mesh.shape[axis_name], (
        keys.shape, dict(mesh.shape))
    spec = P(axis_name)
    return jax.shard_map(
        lambda q, kk, vv: similarity_topk_batched(q, kk, vv, k, impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=(spec, spec),
        check_vma=False,
    )(queries, keys, valid)


def regroup_surviving_shards(keys: jax.Array, valid: jax.Array,
                             alive: np.ndarray):
    """Compact the shard axis onto the surviving shard set (membership
    change: nodes left/crashed).  keys (N, C, D) / valid (N, C) / alive
    (N,) bool -> (keys (A, C, D), valid (A, C), shard_ids (A,) int32) where
    ``shard_ids[a]`` is the original shard id of compacted row ``a``.
    Entries on dead shards simply do not appear — lost, never phantom."""
    alive = np.asarray(alive, bool)
    assert alive.shape == (keys.shape[0],), (alive.shape, keys.shape)
    ids = np.nonzero(alive)[0].astype(np.int32)
    sel = jnp.asarray(ids)
    return keys[sel], valid[sel], ids


def surviving_topk_lookup(queries: jax.Array, keys: jax.Array,
                          valid: jax.Array, alive: np.ndarray, k: int,
                          mesh: Optional[Mesh] = None,
                          axis_name: str = "cache", *, impl: str = "auto"):
    """``sharded_topk_lookup`` regrouped over the surviving shard set.

    The cache axis reshards live on membership change: the lookup runs
    over only the ``alive`` shards (compacted, so dead shards cost no
    FLOPs and can never serve), and returned global indices are mapped
    back to the ORIGINAL [0, N*C) index space so callers' owner = idx //
    C arithmetic is membership-agnostic.  When ``mesh`` is given and its
    ``axis_name`` size equals the survivor count the probe runs as the
    shard_map collective; otherwise it falls back to the single-dispatch
    pooled probe (identical results).  With no survivors, returns idx -1
    / score -inf (every query misses).
    """
    n, c, _ = keys.shape
    q = queries.shape[0]
    keys_a, valid_a, ids = regroup_surviving_shards(keys, valid, alive)
    a = len(ids)
    if a == 0:
        return (jnp.full((q, k), -1, jnp.int32),
                jnp.full((q, k), -jnp.inf, jnp.float32))
    if mesh is not None and dict(mesh.shape).get(axis_name) == a:
        idx, score = sharded_topk_lookup(queries, keys_a, valid_a, k, mesh,
                                         axis_name, impl=impl)
    else:
        idx, score = cluster_topk_lookup(queries, keys_a, valid_a, k,
                                         impl=impl)
    # compacted shard a -> original shard ids[a], preserving the slot
    idx = jnp.asarray(ids)[idx // c] * c + idx % c
    return idx.astype(jnp.int32), score
