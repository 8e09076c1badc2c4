"""Unified tier-ladder protocol — ONE rung-walking loop for every cache tier.

The lookup ladder (local shard -> peer shards -> remote-cluster digests ->
cloud) used to be hand-rolled per layer: ``cluster.py`` walked rungs 1-2,
``federation.py`` re-walked them plus the digest rung via a probe-injection
contract, and ``coic.py`` / ``serving/engine.py`` each re-derived the
per-tier latency charging with an if/elif chain over tier codes.  This
module extracts the shared shape:

* ``CacheTier`` — the probe protocol.  A tier is anything with a ``name``,
  a canonical ``code``, and ``probe(queries, mask, ctx) ->
  TierProbeResult``: given the step's grouped ``(K, N, B, D)`` query tensor
  and the mask of rows still unserved, serve what you can, report per-row
  scores/payloads/owners and how many device dispatches you issued.
  Implementations exist at two granularities, both conforming here:

    - rung-level: ``LocalRung`` / ``PeerRung`` (this module) and the
      federation's ``RemoteDigestRung`` — the device-dispatch-bounded rungs
      composed *inside* ``CooperativeEdgeCluster`` / ``FederatedEdgeTier``.
      A rung may swap its probe *format* without changing the walk or the
      dispatch ledger: ``RemoteDigestRung`` selects brute-fp32, brute-int8
      or the two-stage IVF-PQ ANN probe by board size (``ann_mode``) —
      each is still exactly one digest dispatch plus one confirm, so the
      ladder bounds below are format-independent;
    - org-level: ``CooperativeEdgeCluster``, ``FederatedEdgeTier`` and the
      ``CoICEngine`` cloud fallback are themselves ``CacheTier``s, so an
      engine's whole serving path is one ``TierLadder([edge_org, cloud])``.

* ``TierLadder`` — the one generic walker: probes rungs in order over the
  shrinking miss mask, folds each rung's hits into one ``LadderResult``,
  and owns the dispatch counters that pin the batched bounds (<= 2
  dispatches for a cluster step, <= 4 for a federation step, regardless of
  node/cluster count).  A rung whose mask is already empty is never probed,
  so the "skip the peer probe when rung 1 served everything" behaviour
  falls out of the walk instead of being re-implemented per tier.

Tier codes are canonical across every layer (``local=0, peer=1, remote=2,
miss=3``) — the federation and cluster result tensors are now directly
comparable, which is what lets the engines charge latency from one
data-driven table (``TwoTierRouter.tier_latency``) instead of per-layer
if/elif chains.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, List, NamedTuple, Optional, Protocol, Sequence

import jax.numpy as jnp
import numpy as np

from repro.kernels.similarity import similarity_topk_batched
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, to_host

TIER_LOCAL, TIER_PEER, TIER_REMOTE, TIER_MISS = 0, 1, 2, 3
TIER_NAMES = ("local", "peer", "remote", "miss")


def pow2(n: int, lo: int = 1) -> int:
    """Next power of two >= max(n, lo) — the shared pad-bucket policy that
    keeps jitted probe/prefill shapes from retracing per distinct count."""
    n = max(n, lo)
    return 1 << (n - 1).bit_length()


class TierProbeResult(NamedTuple):
    """One rung's answer for the rows it was asked about.

    All arrays are ``(K, N, B)``-leading (``value`` adds the payload dim);
    ``hit`` must be a subset of the probed mask.  ``dispatches`` is the
    number of device dispatches this probe issued — the ladder sums them
    into the per-step bound counters.
    """

    hit: np.ndarray
    tier: np.ndarray         # canonical code per served row
    cluster: np.ndarray      # serving cluster, -1 where not served
    owner: np.ndarray        # serving node, -1 where not served
    score: np.ndarray
    value: np.ndarray
    dispatches: int


class LadderResult(NamedTuple):
    """The folded walk: per-row serving tier (``TIER_MISS`` when no rung
    served it), serving (cluster, node), score and payload."""

    hit: np.ndarray          # (K, N, B) bool — served by any probed tier
    tier: np.ndarray         # (K, N, B) int8 canonical codes
    cluster: np.ndarray      # (K, N, B) int32, -1 on miss
    owner: np.ndarray        # (K, N, B) int32, -1 on miss
    score: np.ndarray        # (K, N, B) f32
    value: np.ndarray        # (K, N, B, P)


class CacheTier(Protocol):
    """The probe protocol every rung/org/cloud tier implements."""

    name: str
    code: int

    def probe(self, queries: np.ndarray, mask: np.ndarray,
              ctx: Any) -> Optional[TierProbeResult]:
        """Serve what this tier can of the ``mask``-selected rows.  May
        mutate tier-owned state (touches, admissions, stat counters).
        Returns None for "nothing to do, zero dispatches"."""
        ...


@dataclasses.dataclass
class ProbeContext:
    """Per-step shared state for the intra-org rungs: the pre-step shard
    snapshot every rung's probe and payload read resolves against (so an
    earlier rung's admissions never change what a later rung serves), plus
    the stacked key/valid tensors the batched kernels scan, and the
    tracer the rungs' host reads report to."""

    clusters: List                  # CooperativeEdgeCluster per cluster
    pre_states: List[List]          # (K, N) SemanticCacheState snapshot
    keys: jnp.ndarray               # (K, N, C, D)
    valid: jnp.ndarray              # (K, N, C)
    alive: List[List]               # (K, N) TTL-expiry masks
    trace: Any                      # tracer of the org's ladder


def build_probe_context(clusters: Sequence, tracer) -> ProbeContext:
    stacks = [cl._stacks() for cl in clusters]
    return ProbeContext(
        clusters=list(clusters),
        pre_states=[list(cl.states) for cl in clusters],
        keys=jnp.stack([s[0] for s in stacks]),
        valid=jnp.stack([s[1] for s in stacks]),
        alive=[s[2] for s in stacks], trace=tracer)


def empty_probe_arrays(queries: np.ndarray, payload_dim: int,
                       payload_dtype) -> tuple:
    """All-miss (hit, tier, cluster, owner, score, value) arrays for a
    (K, N, B, D) query tensor — the shared starting block every tier
    implementation fills in."""
    K, N, B, _ = queries.shape
    return (np.zeros((K, N, B), bool),
            np.full((K, N, B), TIER_MISS, np.int8),
            np.full((K, N, B), -1, np.int32),
            np.full((K, N, B), -1, np.int32),
            np.zeros((K, N, B), np.float32),
            np.zeros((K, N, B, payload_dim), np.dtype(payload_dtype)))


class LocalRung:
    """Rung 1: every node's own shard, ONE batched dispatch across all
    ``K * N`` shards.  Applies the probe through
    ``SemanticCache.apply_probe`` so hit/miss counters, LRU/LFU touches and
    the TTL clock advance exactly as a standalone lookup would."""

    name, code = "local", TIER_LOCAL

    def probe(self, queries, mask, ctx: ProbeContext):
        clusters = ctx.clusters
        cfg = clusters[0].cfg
        K, N, B, D = queries.shape
        C = cfg.node_capacity
        if K == 1 and getattr(clusters[0], "mesh", None) is not None:
            # shards live one per device of a cache mesh: each device
            # probes its own shard, no communication
            from repro.parallel.sharding import sharded_local_topk
            l_idx, l_score = sharded_local_topk(
                jnp.asarray(queries[0]), ctx.keys[0], ctx.valid[0], 1,
                clusters[0].mesh, clusters[0].cache_axis,
                impl=cfg.lookup_impl)
        else:
            l_idx, l_score = similarity_topk_batched(
                jnp.asarray(queries).reshape(K * N, B, D),
                ctx.keys.reshape(K * N, C, D),
                ctx.valid.reshape(K * N, C), 1, impl=cfg.lookup_impl)
        tr = ctx.trace
        l_idx = to_host(tr, "probe_idx", l_idx)[..., 0].reshape(K, N, B)
        l_score = to_host(tr, "probe_score", l_score)[..., 0].reshape(
            K, N, B)

        hit, tier, cluster, owner, score, value = empty_probe_arrays(
            queries, cfg.payload_dim, cfg.payload_dtype)
        for k, cl in enumerate(clusters):
            for g in range(N):
                cl.states[g], res = cl.cache.apply_probe(
                    cl.states[g], jnp.asarray(l_idx[k, g]),
                    jnp.asarray(l_score[k, g]),
                    mask=jnp.asarray(mask[k, g]), alive=ctx.alive[k][g])
                hit[k, g] = to_host(tr, "hit", res.hit)
                score[k, g] = to_host(tr, "score", res.score)
                value[k, g] = to_host(tr, "value", res.value)
            owner[k][hit[k]] = np.nonzero(hit[k])[0].astype(np.int32)
            cluster[k][hit[k]] = k
        tier[hit] = self.code
        return TierProbeResult(hit, tier, cluster, owner, score, value,
                               dispatches=1)


class PeerRung:
    """Rung 2: each cluster's pooled shards, ONE batched dispatch spanning
    every shard of every cluster.  Serves from the pre-step snapshot (an
    earlier group's admission must not change a later group's payload),
    touches the owning shard, applies the admission policy, and rebates the
    home shard's miss counter for served rows so hits + misses ==
    requests."""

    name, code = "peer", TIER_PEER

    def probe(self, queries, mask, ctx: ProbeContext):
        clusters = ctx.clusters
        cfg = clusters[0].cfg
        K, N, B, D = queries.shape
        C = cfg.node_capacity
        if not (cfg.share and N > 1 and mask.any()):
            return None
        tr = ctx.trace
        if K == 1 and getattr(clusters[0], "mesh", None) is not None:
            # real cache-axis mesh: one shard_map collective (an all-gather
            # of (idx, score) per shard), same merged result
            from repro.parallel.sharding import sharded_topk_lookup
            g_idx, g_score = sharded_topk_lookup(
                jnp.asarray(queries).reshape(N * B, D), ctx.keys[0],
                ctx.valid[0], 1, clusters[0].mesh, clusters[0].cache_axis,
                impl=cfg.lookup_impl)
            g_idx = to_host(tr, "probe_idx", g_idx)[:, 0].reshape(K, N, B)
            g_score = to_host(tr, "probe_score", g_score)[:, 0].reshape(
                K, N, B)
        else:
            g_idx, g_score = similarity_topk_batched(
                jnp.asarray(queries).reshape(K, N * B, D),
                ctx.keys.reshape(K, N * C, D),
                ctx.valid.reshape(K, N * C), 1, impl=cfg.lookup_impl)
            g_idx = to_host(tr, "probe_idx", g_idx)[..., 0].reshape(K, N, B)
            g_score = to_host(tr, "probe_score", g_score)[..., 0].reshape(
                K, N, B)

        hit, tier, cluster, owner, score, value = empty_probe_arrays(
            queries, cfg.payload_dim, cfg.payload_dtype)
        for k, cl in enumerate(clusters):
            qk = jnp.asarray(queries[k])
            for g in range(N):
                miss_rows = np.nonzero(mask[k, g])[0]
                if not miss_rows.size:
                    continue
                n_served = cl.serve_peer_hits(
                    g, qk[g], miss_rows, g_idx[k, g][miss_rows],
                    g_score[k, g][miss_rows], hit[k, g], tier[k, g],
                    owner[k, g], score[k, g], value[k, g],
                    snapshot=ctx.pre_states[k], tracer=tr)
                if n_served:
                    cl.states[g] = dataclasses.replace(
                        cl.states[g],
                        misses=cl.states[g].misses - n_served)
            cluster[k][hit[k]] = k
        return TierProbeResult(hit, tier, cluster, owner, score, value,
                               dispatches=1)


class TierLadder:
    """The generic rung walker + the dispatch-bound counters.

    ``probe`` walks the rungs in order over the shrinking miss mask; a rung
    with nothing left to serve is skipped (zero dispatches).  Counters:
    ``last_dispatches`` / ``max_dispatches`` pin the per-step bound,
    ``rung_dispatches`` splits the total by rung, ``tier_counts`` counts
    served rows by final canonical tier, ``last_probe_ms`` holds each
    rung's wall time for the engines' latency amortization.

    All counters live in a ``MetricsRegistry`` under ``prefix`` (a private
    one when the caller plumbs none — back-compat for standalone ladders);
    the legacy attribute names remain as read-only views.  ``tracer``
    (default ``NULL_TRACER``) gets one ``probe:<rung>`` span per probed
    rung, tagged with the canonical tier code and a running dispatch id.
    """

    def __init__(self, rungs: Sequence[CacheTier],
                 metrics: Optional[MetricsRegistry] = None,
                 prefix: str = "ladder", tracer=None):
        self.rungs = list(rungs)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.prefix = prefix
        self.trace = tracer if tracer is not None else NULL_TRACER
        m, p = self.metrics, prefix
        self._tier_counts = {n: m.counter(f"{p}/tier_counts/{n}")
                             for n in TIER_NAMES}
        self._rung_dispatches = {
            r.name: m.counter(f"{p}/rung_dispatches/{r.name}")
            for r in self.rungs}
        self._probe_dispatches = m.counter(f"{p}/probe_dispatches")
        self._last_dispatches = m.gauge(f"{p}/last_ladder_dispatches")
        self._max_dispatches = m.gauge(f"{p}/max_ladder_dispatches")
        self._probe_ms = {r.name: m.histogram(f"{p}/probe_ms/{r.name}")
                          for r in self.rungs}
        self.last_probe_ms = {r.name: 0.0 for r in self.rungs}

    # ------------------------------------------------------------------
    # legacy counter views (same names/shapes the seed exposed as plain
    # attributes — now thin reads of the registry counters)
    @property
    def tier_counts(self) -> dict:
        return {n: c.value for n, c in self._tier_counts.items()}

    @property
    def rung_dispatches(self) -> dict:
        return {n: c.value for n, c in self._rung_dispatches.items()}

    @property
    def probe_dispatches(self) -> int:
        return self._probe_dispatches.value

    @property
    def last_dispatches(self) -> int:
        return self._last_dispatches.value

    @property
    def max_dispatches(self) -> int:
        return self._max_dispatches.value

    # ------------------------------------------------------------------
    def probe(self, queries: np.ndarray, mask: np.ndarray, ctx: Any,
              payload_dim: int, payload_dtype) -> LadderResult:
        queries = np.asarray(queries, np.float32)
        hit, tier, cluster, owner, score, value = empty_probe_arrays(
            queries, payload_dim, payload_dtype)
        remaining = np.asarray(mask, bool).copy()
        trace = self.trace
        last = 0
        for rung in self.rungs:
            self.last_probe_ms[rung.name] = 0.0
            if not remaining.any():
                break
            if trace.enabled:
                trace.begin(f"probe:{rung.name}", cat="ladder",
                            args={"tier_code": rung.code,
                                  "dispatch_id":
                                      self._probe_dispatches.value + last})
            t0 = time.perf_counter()
            res = rung.probe(queries, remaining, ctx)
            dt = (time.perf_counter() - t0) * 1e3
            if trace.enabled:
                trace.end()
            self.last_probe_ms[rung.name] = dt
            if res is None:
                continue
            self._probe_ms[rung.name].observe(dt)
            self._rung_dispatches[rung.name].inc(res.dispatches)
            last += res.dispatches
            served = res.hit & remaining
            if served.any():
                hit[served] = True
                tier[served] = res.tier[served]
                cluster[served] = res.cluster[served]
                owner[served] = res.owner[served]
                score[served] = res.score[served]
                value[served] = res.value[served]
                remaining &= ~served
        self._last_dispatches.set(last)
        self._probe_dispatches.inc(last)
        self._max_dispatches.max(last)
        mask_np = np.asarray(mask, bool)
        for code, name in enumerate(TIER_NAMES):
            n = int(((tier == code) & mask_np).sum())
            if n:
                self._tier_counts[name].inc(n)
        return LadderResult(hit, tier, cluster, owner, score, value)

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        """The uniform per-tier stats shape every layer exposes (the
        federation, the cluster, and both engines report this same dict
        under a ``"ladder"`` key)."""
        return {
            "tier_counts": dict(self.tier_counts),
            "rung_dispatches": dict(self.rung_dispatches),
            "probe_dispatches": self.probe_dispatches,
            "last_ladder_dispatches": self.last_dispatches,
            "max_ladder_dispatches": self.max_dispatches,
        }


# ---------------------------------------------------------------------------
# Flat-batch routing: the engines' one code path onto any ladder org
# ---------------------------------------------------------------------------


def org_grid(org) -> tuple:
    """(K clusters, N nodes) of a ladder org (cluster orgs are K=1)."""
    cfg = org.cfg
    if hasattr(cfg, "num_clusters"):
        return cfg.num_clusters, cfg.cluster.num_nodes
    return 1, cfg.num_nodes


def pack_flat(desc: np.ndarray, nodes, clusters, K: int, N: int):
    """Scatter a flat (n, D) descriptor batch into the grouped
    (K, N, Bmax, D) tensor + mask the ladder probes, padding group widths
    to a shared power of two so jitted probes don't retrace per count.
    Returns (queries, mask, rows_of) where ``rows_of[k][g]`` lists the flat
    rows routed to (cluster k, node g).

    A degenerate axis ignores its ids (a solo cache accepts any
    node/cluster id, as it always has); otherwise out-of-range ids are an
    error, not a silent wrap."""
    n, D = desc.shape
    nodes = [0] * n if N == 1 else [int(g) for g in nodes]
    clusters = [0] * n if K == 1 else [int(k) for k in clusters]
    assert all(0 <= g < N for g in nodes), (nodes, N)
    assert all(0 <= k < K for k in clusters), (clusters, K)
    rows_of = [[[] for _ in range(N)] for _ in range(K)]
    for i, (g, k) in enumerate(zip(nodes, clusters)):
        rows_of[k][g].append(i)
    Bmax = pow2(max(len(r) for kr in rows_of for r in kr))
    queries = np.zeros((K, N, Bmax, D), np.float32)
    mask = np.zeros((K, N, Bmax), bool)
    for k in range(K):
        for g in range(N):
            rows = rows_of[k][g]
            queries[k, g, :len(rows)] = desc[rows]
            mask[k, g, :len(rows)] = True
    return queries, mask, rows_of


def unpack_flat(res: LadderResult, rows_of, n: int) -> LadderResult:
    """Gather a grouped LadderResult back to flat (n,)-leading arrays in
    the original submission order."""
    out = [np.zeros((n,) + f.shape[3:], f.dtype) for f in res]
    for k, kr in enumerate(rows_of):
        for g, rows in enumerate(kr):
            if rows:
                for o, f in zip(out, res):
                    o[rows] = f[k, g, :len(rows)]
    return LadderResult(*out)


def route_flat(org, desc: np.ndarray, nodes, clusters) -> LadderResult:
    """One flat request batch through an org's grouped ladder: pack, probe,
    unpack.  ``nodes``/``clusters`` may be scalars (whole batch at one
    edge node) or per-row sequences; ``pack_flat`` ignores the ids of a
    degenerate axis and rejects out-of-range ids otherwise."""
    desc = np.asarray(desc, np.float32)
    n = desc.shape[0]
    if np.ndim(nodes) == 0:
        nodes = [int(nodes)] * n
    if np.ndim(clusters) == 0:
        clusters = [int(clusters)] * n
    K, N = org_grid(org)
    queries, mask, rows_of = pack_flat(desc, nodes, clusters, K, N)
    res = org.probe(queries, mask, None)
    return unpack_flat(LadderResult(res.hit, res.tier, res.cluster,
                                    res.owner, res.score, res.value),
                       rows_of, n)
