"""Cooperative multi-node edge cache tier — the paper's actual thesis.

The paper argues for "caching and sharing computation-intensive IC results on
the edge" *across* applications and users; a single isolated ``SemanticCache``
per engine never shares anything.  ``CooperativeEdgeCluster`` runs N edge
nodes, each owning one ``SemanticCache`` shard, behind the unified ladder
protocol (``core/tiers.py``):

  1. local  — the serving node's own shard (``LocalRung``, one batched
              dispatch over every node's shard)
  2. peer   — on a local miss the descriptor is broadcast to the other
              shards over the edge<->edge link; the whole cluster probe is
              ONE pooled dispatch (``PeerRung``; ``sharded_topk_lookup`` on
              a real ``cache``-axis mesh) instead of N host round-trips
  3. cloud  — the caller forwards the remaining misses and inserts results
              back into the serving node's shard

Peer hits refresh the owning shard's LRU/LFU state (``SemanticCache.touch``)
and are optionally re-admitted into the serving node's shard
(``admission="always"``, or on the second peer hit with
``admission="second_hit"``), so hot items replicate toward their consumers —
eCAR/CloudAR-style cooperative sharing.

This class is the *storage + policy* owner (shards, admission bookkeeping,
peer-serve mechanics); the rung walking itself is the shared
``TierLadder``, which the cross-cluster federation reuses over K of these
clusters with the same rung objects — no per-layer rung code, no probe
injection.  ``CooperativeEdgeCluster`` is itself a ``CacheTier``: an
engine can compose it directly with a cloud tier in one ladder.

Request paths (both through the same ladder):

* ``lookup(node, queries)`` — one node's batch (pow2-padded, no retraces).
* ``lookup_grouped(queries, mask)`` — requests from ALL nodes at once as a
  ``(num_nodes, B, D)`` grouped-query batch: the batched engine step's
  amortized ladder, two device dispatches per step regardless of node
  count or batch size.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core.policies import EvictionPolicy
from repro.core.semantic_cache import SemanticCache, SemanticCacheState
from repro.core.tiers import (TIER_LOCAL, TIER_MISS, TIER_NAMES, TIER_PEER,
                              LocalRung, PeerRung, TierLadder,
                              TierProbeResult, build_probe_context, pow2,
                              route_flat)

# canonical codes/names re-exported from core/tiers.py: cluster results use
# the same TIER_LOCAL=0 / TIER_PEER=1 / TIER_MISS=3 codes as every layer
# (TIER_REMOTE=2 never appears in a standalone cluster's results)
__all__ = ["TIER_LOCAL", "TIER_PEER", "TIER_MISS", "TIER_NAMES",
           "ClusterConfig", "ClusterLookupResult", "CooperativeEdgeCluster",
           "admission_filter", "pow2"]


def admission_filter(kind: str, slots: np.ndarray, owner_state,
                     node_state, policy, seen: Dict[tuple, int],
                     key_prefix: tuple) -> np.ndarray:
    """Which remotely-served cache ``slots`` (entries of ``owner_state`` just
    served to another node or cluster) get re-admitted into the requester's
    shard (``node_state``).  Shared by the peer tier and the federation
    tier's remote rung:

      never         — none
      always        — all
      second_hit    — on the 2nd remote hit of the same entry incarnation,
                      tracked in ``seen`` under ``key_prefix + (slot,
                      inserted_at)`` (one-hit wonders never replicate)
      freq_weighted — only when the entry's observed hit count at its owner
                      (as of the probe snapshot) strictly beats the
                      requester shard's coldest victim's count (free slots
                      count 0), so replication never displaces an entry
                      hotter than the newcomer
    """
    n = len(slots)
    if kind == "never":
        return np.zeros((n,), bool)
    if kind == "always":
        return np.ones((n,), bool)
    if kind == "second_hit":
        ins = np.asarray(owner_state.inserted_at)
        admit = np.zeros((n,), bool)
        for i, slot in enumerate(np.asarray(slots)):
            key = key_prefix + (int(slot), int(ins[slot]))
            seen[key] = seen.get(key, 0) + 1
            admit[i] = seen[key] >= 2
        return admit
    assert kind == "freq_weighted", kind
    # argmin ties to the lower slot, matching insert()'s top_k(-pri) victim
    pri = np.asarray(policy.priority(node_state))
    victim = int(np.argmin(pri))
    vfreq = (int(np.asarray(node_state.freq)[victim])
             if bool(np.asarray(node_state.valid)[victim]) else 0)
    owner_freq = np.asarray(owner_state.freq)[np.asarray(slots)]
    return owner_freq > vfreq


@dataclasses.dataclass(frozen=True)
class ClusterConfig:
    num_nodes: int = 4
    node_capacity: int = 1024
    key_dim: int = 256
    payload_dim: int = 64
    threshold: float = 0.85
    payload_dtype: str = "float32"
    policy: EvictionPolicy = EvictionPolicy("lru")
    lookup_impl: str = "auto"
    # peer-hit re-admission into the serving node's shard:
    #   always        — every peer hit is copied locally
    #   never         — peer hits are served remotely, never copied
    #   second_hit    — copy on the 2nd peer hit of the same cached entry at
    #                   the same node (one-hit wonders never replicate)
    #   freq_weighted — copy only when the entry's hit count at its owner
    #                   beats the local shard's coldest victim's count
    admission: str = "always"
    share: bool = True               # False: isolated nodes (no peer tier)

    def __post_init__(self):
        assert self.admission in ("always", "never", "second_hit",
                                  "freq_weighted"), self.admission
        assert self.num_nodes >= 1, self.num_nodes


class ClusterLookupResult(NamedTuple):
    hit: np.ndarray          # (...,) bool — local or peer
    tier: np.ndarray         # (...,) int8 — canonical TIER_LOCAL | TIER_PEER
                             # | TIER_MISS codes (core/tiers.py)
    owner: np.ndarray        # (...,) int32 — serving node, -1 on miss
    score: np.ndarray        # (...,) f32 — best score at the serving tier
    value: np.ndarray        # (..., P) payload (zeros on miss)


class CooperativeEdgeCluster:
    """N cooperating edge nodes, one ``SemanticCache`` shard each.

    ``mesh`` (optional): a Mesh with a ``cache`` axis of size ``num_nodes``;
    when given, the peer rung runs as a shard_map collective with one
    all-gather of (idx, score) per shard.  Without it the probe is a single
    batched device call over the stacked shards — same results, same math.
    """

    name, code = "edge", TIER_LOCAL      # CacheTier identity (org-level)

    def __init__(self, cfg: ClusterConfig, mesh=None, cache_axis: str = "cache",
                 metrics=None, tracer=None):
        self.cfg = cfg
        self.mesh = mesh
        self.cache_axis = cache_axis
        if mesh is not None:
            assert dict(mesh.shape)[cache_axis] == cfg.num_nodes, (
                dict(mesh.shape), cfg.num_nodes)
        self.cache = SemanticCache(
            capacity=cfg.node_capacity, key_dim=cfg.key_dim,
            payload_dim=cfg.payload_dim, threshold=cfg.threshold,
            payload_dtype=cfg.payload_dtype, policy=cfg.policy,
            lookup_impl=cfg.lookup_impl)
        self.states: List[SemanticCacheState] = [
            self.cache.init() for _ in range(cfg.num_nodes)]
        self.peer_hits = np.zeros((cfg.num_nodes,), np.int64)   # served-for-others
        self.peer_fills = np.zeros((cfg.num_nodes,), np.int64)  # admitted-from-peer
        self.node_alive = np.ones((cfg.num_nodes,), bool)       # membership view
        self._keys_stack = None      # cached (N, C, D) stack; None = dirty
        # second-hit admission: per-node count of peer hits per cached entry
        # incarnation (owner, slot, inserted_at)
        self._peer_seen: List[Dict[Tuple[int, int, int], int]] = [
            {} for _ in range(cfg.num_nodes)]
        self.ladder = TierLadder([LocalRung(), PeerRung()],
                                 metrics=metrics, tracer=tracer)
        self.metrics = self.ladder.metrics

    # ------------------------------------------------------------------
    @property
    def probe_dispatches(self) -> int:
        """Similarity probes sent to the device (ladder-counted)."""
        return self.ladder.probe_dispatches

    # ------------------------------------------------------------------
    def _stacks(self):
        """(keys (N, C, D), valid (N, C)) device stacks.  Keys are cached
        across probes and invalidated on insert (keys only change there);
        the valid stack is cheap and rebuilt each time so TTL expiry stays
        correct.  Also returns the per-node alive masks for bookkeeping.

        Dead nodes (``node_alive`` False — membership control plane) are
        masked out wholesale: their entries never match a probe, so a
        crashed shard's data is lost, never phantom-served.

        With a cache-axis ``mesh`` both stacks are placed over it, shard
        ``g`` on the mesh's device ``g`` — the layout the collective peer
        probe expects."""
        if self._keys_stack is None:
            self._keys_stack = self._on_mesh(
                jnp.stack([s.keys for s in self.states]))
        alive = [self.cache.policy.expire(s, s.clock)
                 if self.node_alive[g] else
                 jnp.zeros((self.cfg.node_capacity,), bool)
                 for g, s in enumerate(self.states)]
        return self._keys_stack, self._on_mesh(jnp.stack(alive)), alive

    def _on_mesh(self, stack: jax.Array) -> jax.Array:
        if self.mesh is None:
            return stack
        return jax.device_put(stack, NamedSharding(
            self.mesh, PartitionSpec(self.cache_axis)))

    # ------------------------------------------------------------------
    def kill_node(self, node: int) -> None:
        """Membership: node ``node`` crashed.  Its shard's contents are
        gone (lost-not-phantom) — the state is reset cold so a revive
        starts empty, and admission bookkeeping pointing at the dead
        incarnation is dropped."""
        if not self.node_alive[node]:
            return
        self.node_alive[node] = False
        self.states[node] = self.cache.init()
        self._keys_stack = None
        self._peer_seen[node] = {}
        for seen in self._peer_seen:     # counters keyed by the dead owner
            for k in [k for k in seen if k[0] == node]:
                del seen[k]

    def revive_node(self, node: int) -> None:
        """Membership: node ``node`` rejoined — cold (its cache died with
        it)."""
        self.node_alive[node] = True

    def wipe(self) -> None:
        """Membership: the whole cluster crashed.  Every shard restarts
        cold; cumulative counters survive (they are observability, not
        state)."""
        self.states = [self.cache.init() for _ in range(self.cfg.num_nodes)]
        self._keys_stack = None
        self._peer_seen = [{} for _ in range(self.cfg.num_nodes)]

    # ------------------------------------------------------------------
    def _admission_filter(self, node: int, owner: int, slots: np.ndarray,
                          owner_state: SemanticCacheState) -> np.ndarray:
        """Which of ``slots`` (peer hits served by ``owner`` for ``node``)
        get re-admitted into ``node``'s shard, per ``cfg.admission``.
        ``owner_state`` is the owner shard as of the probe (pre-step
        snapshot in the grouped path)."""
        admit = admission_filter(
            self.cfg.admission, slots, owner_state, self.states[node],
            self.cache.policy, self._peer_seen[node], (owner,))
        if (len(self._peer_seen[node])
                > 4 * self.cfg.num_nodes * self.cfg.node_capacity):
            self._prune_peer_seen(node)
        return admit

    def _prune_peer_seen(self, node: int) -> None:
        """Drop counters whose entry incarnation was evicted (its slot's
        inserted_at no longer matches) — bounds host memory under churn."""
        ins = {p: np.asarray(s.inserted_at) for p, s in enumerate(self.states)}
        self._peer_seen[node] = {
            k: v for k, v in self._peer_seen[node].items()
            if int(ins[k[0]][k[1]]) == k[2]}

    # ------------------------------------------------------------------
    def serve_peer_hits(self, node: int, queries: jax.Array,
                        miss_rows: np.ndarray, g_idx: np.ndarray,
                        g_score: np.ndarray, hit, tier, owner, score, value,
                        snapshot: Optional[List[SemanticCacheState]] = None
                        ) -> int:
        """Fold a cluster-wide probe of ``node``'s local misses into the
        result arrays: serve rows whose best global match is an
        above-threshold peer entry, touch the owners, apply admission.
        Called by ``PeerRung`` — this is the peer tier's serve mechanics,
        kept on the cluster because it owns the shards and the admission
        bookkeeping.  Returns the number of peer-served rows (for the
        local-miss rebate).

        ``miss_rows`` indexes the result arrays; ``g_idx``/``g_score`` are
        the global top-1 per miss row.  The local shard already reported a
        sub-threshold best for these rows, so a cluster-wide top-1 above
        threshold always lives on a peer.

        ``snapshot``: the shard states the probe ran against.  The grouped
        path MUST pass its pre-step snapshot — intra-step admissions can
        evict/overwrite an owner slot a later group's probe result points
        into, and payloads must come from the probed state, not the
        mutated one.  Touches/admissions still apply to the live states.
        """
        cfg = self.cfg
        probed = self.states if snapshot is None else snapshot
        peer_hit = g_score >= cfg.threshold
        owners = (g_idx // cfg.node_capacity).astype(np.int32)
        slots = (g_idx % cfg.node_capacity).astype(np.int32)
        n_peer_served = 0
        for p in range(cfg.num_nodes):
            sel = peer_hit & (owners == p)
            if not sel.any() or p == node:
                continue
            rows = miss_rows[sel]
            vals = np.asarray(probed[p].values)[slots[sel]]
            value[rows] = vals
            score[rows] = g_score[sel]
            tier[rows] = TIER_PEER
            owner[rows] = p
            hit[rows] = True
            n_peer_served += int(sel.sum())
            self.peer_hits[p] += int(sel.sum())
            self.states[p] = self.cache.touch(
                self.states[p], jnp.asarray(slots[sel]),
                jnp.ones((int(sel.sum()),), bool))
            admit = self._admission_filter(node, p, slots[sel], probed[p])
            if admit.any():
                # de-duplicate entries within the batch: one admission per
                # distinct cached entry (a sequential stream would hit the
                # fresh local copy on the repeat instead of re-admitting)
                _, first = np.unique(slots[sel][admit], return_index=True)
                arows = rows[admit][np.sort(first)]
                avals = vals[admit][np.sort(first)]
                self.states[node] = self.cache.insert(
                    self.states[node], queries[jnp.asarray(arows)],
                    jnp.asarray(avals))
                self.peer_fills[node] += len(arows)
                self._keys_stack = None
        return n_peer_served

    # ------------------------------------------------------------------
    def probe(self, queries: np.ndarray, mask: np.ndarray, ctx=None):
        """CacheTier protocol: one grouped ladder walk over (1, N, B, D)
        (the leading cluster dim is 1 — the federation composes the same
        rungs over K > 1 clusters).  Accepts (N, B, D) and broadcasts."""
        queries = np.asarray(queries, np.float32)
        if queries.ndim == 3:
            queries = queries[None]
            mask = None if mask is None else np.asarray(mask, bool)[None]
        if mask is None:
            mask = np.ones(queries.shape[:3], bool)
        pctx = build_probe_context([self])
        res = self.ladder.probe(queries, mask, pctx,
                                self.cfg.payload_dim,
                                self.cfg.payload_dtype)
        return TierProbeResult(*res, dispatches=self.ladder.last_dispatches)

    # ------------------------------------------------------------------
    def lookup_grouped(self, queries: jax.Array,
                       mask: Optional[np.ndarray] = None
                       ) -> ClusterLookupResult:
        """The batched engine step's ladder: queries (num_nodes, B, D) —
        group g holds the request batch that arrived at edge node g; mask
        (num_nodes, B) bool selects real rows (groups are padded to a common
        width).  Returns a ClusterLookupResult with (num_nodes, B) leading
        dims; padding rows report miss/zero and leave no state trace.

        One ``LocalRung`` dispatch + at most one ``PeerRung`` dispatch per
        call, whatever N or B — per-request semantics identical to
        ``lookup`` called per node (modulo clock granularity: one tick per
        step instead of one per call).
        """
        res = self.probe(np.asarray(queries, np.float32), mask)
        return ClusterLookupResult(hit=res.hit[0], tier=res.tier[0],
                                   owner=res.owner[0], score=res.score[0],
                                   value=res.value[0])

    # ------------------------------------------------------------------
    def lookup(self, node: int, queries: jax.Array) -> ClusterLookupResult:
        """queries: (Q, D) unit descriptors arriving at ``node`` — the
        per-request path, routed through the same grouped ladder with a
        single-group mask (pow2-padded so jitted probes don't retrace).

        Clock semantics: a ladder walk advances EVERY shard's logical
        clock by one (the grouped path always did; this path now shares
        it), so ``EvictionPolicy.ttl`` counts ladder steps — uniform
        across shards — rather than per-owning-shard lookups."""
        queries = np.asarray(queries, np.float32)
        res = route_flat(self, queries, node, 0)
        return ClusterLookupResult(hit=res.hit, tier=res.tier,
                                   owner=res.owner, score=res.score,
                                   value=res.value)

    # ------------------------------------------------------------------
    def insert(self, node: int, keys: jax.Array, values: jax.Array) -> None:
        """Insert cloud results into the serving node's shard.  Inserts to
        a dead node are dropped (the RPC would fail in deployment; callers
        route around dead nodes via the membership plane first)."""
        if not self.node_alive[node]:
            return
        self.states[node] = self.cache.insert(
            self.states[node], jnp.asarray(keys), jnp.asarray(values))
        self._keys_stack = None

    def insert_home(self, cluster_id: int, node: int, keys, values) -> None:
        """Org-generic insert (cluster orgs ignore ``cluster_id``; a
        degenerate node axis ignores ``node``, matching ``pack_flat``'s
        routing rule for the solo cache)."""
        self.insert(0 if self.cfg.num_nodes == 1 else node, keys, values)

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        per_node = [self.cache.stats(s) for s in self.states]
        for p, s in enumerate(per_node):
            s["peer_hits_served"] = int(self.peer_hits[p])
            s["peer_fills"] = int(self.peer_fills[p])
        # per-node misses exclude peer-served requests (the peer rung
        # rebates them), so hits + misses == requests and hit_rate is
        # "served at any edge tier"
        total_hits = sum(s["hits"] for s in per_node)
        total_misses = sum(s["misses"] for s in per_node)
        tot = total_hits + total_misses
        return {
            "nodes": per_node,
            "capacity": self.cfg.num_nodes * self.cfg.node_capacity,
            "occupancy": sum(s["occupancy"] for s in per_node),
            "hits": total_hits,
            "misses": total_misses,
            "hit_rate": (total_hits / tot) if tot else 0.0,
            "probe_dispatches": self.probe_dispatches,
            "ladder": self.ladder.stats(),
        }
