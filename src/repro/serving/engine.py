"""Batched serving engine with continuous batching and the CoIC edge cache
in front of the model — the deployment shape of the paper's Figure 1.

Request lifecycle (one lookup ladder per engine STEP, not per request):

  submit  -> enqueue only (no device work); carries an optional per-request
             ``priority`` and frame ``deadline_ms`` (motion-to-photon budget
             relative to submission)
  step:
    schedule — drain pending requests into ONE jitted descriptor extraction
               over length-bucketed prompt pads and ONE grouped cluster
               lookup spanning requests from all nodes
               (hit -> result immediately, charged the modeled network +
                probe latency; miss -> admission queue)
    admit    — the admission queue is ordered earliest-deadline-first
               (``queue_policy="edf"``: deadline-bearing requests jump bulk
               requests, higher priority jumps within a class, ties broken
               FIFO; ``"fifo"`` is the head-of-line-blocking baseline),
               then drained by bucketed batched prefill: all queued
               requests with free slots prefill in ONE dispatch per step,
               padded to (pow2 batch, pow2 length) buckets so admission
               compiles once per bucket instead of once per prompt length.
               Prompts longer than ``prefill_chunk`` take the CHUNKED
               admission path instead: they reserve a slot and trickle
               ``prefill_chunk`` tokens per step through
               ``model.prefill_chunk``, so one huge prompt never inflates
               the shared prefill bucket or stalls the admissions behind it
               (bit-identical prefill state to the one-shot path — the
               test_layer_reuse equivalence, now at engine scope)
    decode   — one decode_step over the whole active batch
    retire   — EOS or max_new_tokens -> result + batched CoIC insert
               (descriptors are cached from schedule time: zero extra
               extraction dispatches)

Deadline accounting: a request's completion time is its queueing delay in
engine steps (``step_ms`` models the wall duration of one step in a paced
simulation; 0 falls back to measured wall time) plus the modeled hit
latency (cache hits) or the modeled network terms around the engine's own
compute (cloud path).  Misses against ``deadline_ms`` are counted per
serving tier in ``self.deadline`` (``core/router.py::DeadlineStats``) and
stamped on each ``ServedResult``.  An already-expired deadline is still
served — and counted as a miss — never dropped.

``scheduling="sequential"`` drains ONE request per step through the same
bucketed machinery — the per-request-ladder baseline the batched mode is
measured against (benchmarks/cooperative_hit_rate.py --batched).

``kv_page > 0`` swaps the slotted batch cache for a PAGED one
(``kv_cache.PagedKVCache``): per-slot block tables over a refcounted
physical page pool, vLLM-style.  Admission becomes continuous batching —
every queued request maps its index-resident prompt-prefix pages
(cross-user KV sharing, CoIC's workload redundancy one layer below the
descriptor cache) and joins a single batched ``prefill_chunk`` dispatch
that advances ALL mid-prefill rows together, interleaved with the batched
decode over the active rows.  The lookup-ladder bound is untouched: paged
mode changes how misses compute, not how the ladder routes.

All device work has static shapes (B slots, max_len cache, pow2 buckets);
scheduling is host-side, as in vLLM-class systems.  The CoIC front is a
ladder org from ``core/tiers.py`` — a ``CooperativeEdgeCluster`` (1-node
for the solo cache) or a ``FederatedEdgeTier`` — driven through ONE
``route_flat`` call per step; per-tier latency is charged through
``TwoTierRouter.tier_latency`` over canonical tier codes (no per-tier
if/elif here).  The per-step ladder bound survives both scheduling
policies and chunked prefill: at most one descriptor dispatch + one
grouped lookup per step, and the org's internal ``TierLadder`` stays <= 4
device dispatches regardless of cluster count (each rung is one
federation-wide batched dispatch; stale/quantized digests only ever
under-report — a confirmed miss falls to this engine's own
prefill/decode path, never a phantom cache payload).  ``max_step_ladder``
tracks the observed per-step maximum.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cluster import ClusterConfig, CooperativeEdgeCluster
from repro.core.coic import SOURCE_OF, CoICConfig
from repro.core.descriptor import NgramSketchDescriptor, PrefixDescriptor
from repro.core.federation import FederatedEdgeTier, FederationConfig
from repro.core.network import NetworkModel
from repro.core.router import (DeadlineStats, LatencyBreakdown, PayloadSizes,
                               TwoTierRouter)
from repro.core.tiers import (TIER_LOCAL, TIER_MISS, TIER_NAMES, TIER_PEER,
                              TIER_REMOTE, pow2 as _pow2, route_flat)
from repro.obs.metrics import CounterDict, LazyCounterGroup, MetricsRegistry
from repro.obs.trace import NULL_TRACER, to_host
from repro.obs.views import digest_block, ladder_block, org_stats
from repro.serving.kv_cache import (PagedKVCache, batch_cache_scatter,
                                    init_batch_cache, init_paged_pool)


# modeled-latency term names for the trace's request track, in the same
# order LatencyBreakdown.total_ms sums them
_TERM_FIELDS = ("descriptor_ms", "uplink_ms", "lookup_ms", "peer_net_ms",
                "remote_net_ms", "cloud_net_ms", "cloud_compute_ms",
                "downlink_ms")


def _latency_terms(lat: LatencyBreakdown, skip=()):
    """(name, ms) pairs of a breakdown's nonstructural terms — the child
    spans of one request's modeled timeline."""
    return [(f[:-3], getattr(lat, f)) for f in _TERM_FIELDS if f not in skip]


class PromptTooLongError(ValueError):
    """Raised by ``submit()`` when a prompt exceeds the engine's per-slot
    cache capacity (``max_len``) and ``on_overflow="reject"``.  The old
    behavior — silently truncating in ``_pad_prompts``/the chunked path and
    returning tokens conditioned on a prompt the caller never sent — is
    gone: overflow is either an error at the door or an explicit
    ``ServedResult.truncated`` flag."""


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    max_batch: int = 8
    max_len: int = 512               # cache capacity per slot
    max_new_tokens: int = 32
    eos_id: int = -1                 # -1: no EOS, always run to max_new
    coic: Optional[CoICConfig] = None
    scheduling: str = "batched"      # batched | sequential (one req/step)
    min_bucket: int = 8              # smallest length/width pad bucket
    # admission ordering: "edf" (earliest-deadline-first; deadline-bearing
    # requests jump bulk, priority breaks class ties, FIFO breaks the rest —
    # degenerates to FIFO when no request carries a deadline) or "fifo"
    # (submission order, the head-of-line-blocking baseline)
    queue_policy: str = "edf"
    # chunked-prefill admission: prompts longer than this many tokens
    # reserve a slot and prefill ``prefill_chunk`` tokens per step through
    # model.prefill_chunk instead of joining the shared bucketed prefill
    # (0 disables; auto-disabled for SWA/recurrent caches, which need the
    # exact-length one-shot path)
    prefill_chunk: int = 0
    # priority-aware chunk pacing: when engine slots sit idle (free decode
    # slots and an empty admission queue) an in-flight long prompt may
    # advance up to this many chunks per step instead of the fixed
    # one-chunk trickle; the EDF queue key picks who gets the budget first.
    # 1 == the original fixed trickle.  Pacing never changes decoded
    # tokens — only how many steps the prefill takes.
    chunk_pacing: int = 1
    # modeled wall-clock duration of one engine step, for deadline
    # accounting in paced simulations (frame workloads); 0 uses measured
    # wall time for the cloud path and modeled-latency-only for hits
    step_ms: float = 0.0
    # paged KV cache: page size in tokens (0 = the original slotted
    # layout).  With kv_page > 0 every admission takes the chunked path
    # against a refcounted physical page pool, and cross-request prompt
    # prefixes are SHARED page-granular through a descriptor-keyed prefix
    # index instead of re-prefilled (kv_cache.PagedKVCache)
    kv_page: int = 0
    kv_pages: int = 0                # pool size (0 = 2x max_batch span)
    # attention read over the paged pool: "gather" materializes the dense
    # per-row view (paged_gather_view — the bytes-hungry oracle), "paged"
    # reads KV pages in place via the fused kernels/paged_attention op (Pallas
    # on TPU, jnp oracle elsewhere), "paged_interpret" forces the Pallas
    # interpreter (CI bit-exactness).  Requires kv_page > 0.
    attn_impl: str = "gather"        # gather | paged | paged_interpret
    prefix_share: bool = True        # probe/publish the prefix index
    prefix_mode: str = "exact"       # exact | semantic (n-gram sketch)
    # prompts longer than max_len: "reject" raises PromptTooLongError at
    # submit(); "truncate" serves the max_len head and stamps
    # ServedResult.truncated
    on_overflow: str = "reject"

    def __post_init__(self):
        assert self.scheduling in ("batched", "sequential"), self.scheduling
        assert self.queue_policy in ("edf", "fifo"), self.queue_policy
        assert self.prefill_chunk >= 0, self.prefill_chunk
        assert self.chunk_pacing >= 1, self.chunk_pacing
        assert self.on_overflow in ("reject", "truncate"), self.on_overflow
        assert self.kv_page >= 0, self.kv_page
        assert self.attn_impl in ("gather", "paged", "paged_interpret"), \
            self.attn_impl
        if self.attn_impl != "gather":
            assert self.kv_page > 0, \
                "attn_impl=%r needs a paged cache (kv_page > 0)" % self.attn_impl
        if self.kv_page:
            assert self.max_len % self.kv_page == 0, \
                (self.max_len, self.kv_page)
            assert self.prefix_mode in ("exact", "semantic"), self.prefix_mode


@dataclasses.dataclass
class _Active:
    req_id: int
    slot: int
    generated: list
    t_admit: float


@dataclasses.dataclass
class _Chunking:
    """A prompt mid chunked prefill.  Dense path: owns a reserved slot and
    a B=1 prefill cache that is scattered into the batch cache once the
    last chunk lands.  Paged path: ``cache`` is None (chunks write the
    shared pool through the slot's block table) and ``filled`` starts at
    the prefix-shared token count — mapped pages are prefill the row never
    runs."""
    req_id: int
    slot: int
    prompt: np.ndarray
    cache: Optional[dict]
    filled: int = 0                  # prompt tokens consumed so far
    shared_pages: int = 0            # prefix pages mapped, not computed


@dataclasses.dataclass
class ServedResult:
    req_id: int
    tokens: np.ndarray
    source: str                      # edge | peer | remote | cloud
    latency_s: float                 # submit -> result, measured wall s
    decode_steps: int
    breakdown: Optional[LatencyBreakdown] = None   # modeled terms (hits)
    priority: int = 0
    deadline_ms: Optional[float] = None   # budget relative to submission
    completion_ms: float = 0.0       # queueing delay + modeled/measured ms
    deadline_miss: bool = False      # completion_ms > deadline_ms (if set)
    submit_step: int = 0             # engine step count at submit()
    finish_step: int = 0             # engine step count at completion
    truncated: bool = False          # prompt cut to max_len (on_overflow)


class ServingEngine:
    def __init__(self, model, params, cfg: ServingConfig,
                 network: Optional[NetworkModel] = None,
                 tracer=None, metrics: Optional[MetricsRegistry] = None,
                 membership=None):
        self.model = model
        self.params = params
        self.cfg = cfg
        # telemetry: ONE registry for every counter the engine and its
        # cache org mutate; NULL_TRACER costs one attribute check per span
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.trace = tracer if tracer is not None else NULL_TRACER
        self.pending: deque = deque()    # (rid, prompt, node) — pre-lookup
        self.queue: deque = deque()      # (rid, prompt) — lookup missed
        self.active: Dict[int, _Active] = {}
        self.chunking: Dict[int, _Chunking] = {}      # mid chunked prefill
        self.free_slots = list(range(cfg.max_batch))
        self.results: List[ServedResult] = []
        self._req_counter = 0
        self._prompts: Dict[int, np.ndarray] = {}
        self._desc_of: Dict[int, np.ndarray] = {}     # schedule-time reuse
        self._t_submit: Dict[int, float] = {}
        # deadline bookkeeping (EDF scheduling + per-tier miss accounting)
        self._priority: Dict[int, int] = {}
        self._n_priority = 0             # in-flight nonzero-priority count
        self._deadline: Dict[int, Optional[float]] = {}   # relative budget
        self._abs_deadline: Dict[int, float] = {}     # EDF sort key (paced)
        self._submit_step: Dict[int, int] = {}
        self.step_count = 0
        self.deadline = DeadlineStats(self.metrics)
        # device dispatches by kind — the batching win is visible here:
        # one descriptor + one lookup per step regardless of batch size
        # (prefill_chunk: per-chunk trickle dispatches of long prompts).
        # The dict shape is a registry view: "descriptor" lives at
        # engine/dispatches/descriptor etc., and += routes into the counter
        self.dispatches = CounterDict(self.metrics, "engine/dispatches",
                                      ("descriptor", "lookup", "prefill",
                                       "prefill_chunk", "decode"))
        self._completed = self.metrics.counter("engine/completed")
        self._hits = LazyCounterGroup(self.metrics, "engine/hits")
        self._decode_ms = self.metrics.histogram("engine/decode_ms")
        # per-step ladder bound: descriptor + lookup dispatches this step
        # (must stay <= 2 under any queue policy / chunking combination)
        self._last_step_ladder = self.metrics.gauge("engine/last_step_ladder")
        self._max_step_ladder = self.metrics.gauge("engine/max_step_ladder")

        B = cfg.max_batch
        # recurrent (SSM/conv) prefill states absorb right-pad tokens, and
        # sliding-window ring caches rotate by the PADDED length, so those
        # models only batch admissions of identical prompt length with no
        # length padding (full attention caches take the full buckets)
        self._exact_prefill = (
            getattr(getattr(model, "cfg", None), "sliding_window", 0) > 0
            or any(k.endswith("/conv") or k.endswith("/state")
                   for k in model.cache_specs(1, cfg.max_len)))
        # paged KV: block-table batch cache over a refcounted page pool.
        # Needs the linear-cache chunked path (pages are written through
        # valid-masked chunk scatters), so SWA/recurrent models must keep
        # the slotted layout
        self._paged = cfg.kv_page > 0
        if self._paged and (self._exact_prefill
                            or not hasattr(model, "paged_cache_specs")):
            raise ValueError("kv_page > 0 needs linear attention caches "
                             "(no SWA ring / recurrent state) and a model "
                             "with paged_cache_specs")
        self.kv: Optional[PagedKVCache] = None
        if self._paged:
            self.kv = PagedKVCache(model, B, cfg.max_len, cfg.kv_page,
                                   num_pages=cfg.kv_pages,
                                   prefix_share=cfg.prefix_share,
                                   prefix_mode=cfg.prefix_mode,
                                   metrics=self.metrics)
            self.cache = init_paged_pool(model, self.kv.num_pages,
                                         cfg.kv_page)
            # every paged admission is chunked; without an explicit chunk
            # width one max_len-wide chunk covers any prompt in one step
            self._chunk_width = cfg.prefill_chunk or cfg.max_len
        else:
            self.cache = init_batch_cache(model, B, cfg.max_len)
        self.lengths = jnp.zeros((B,), jnp.int32)
        self.tokens = jnp.zeros((B,), jnp.int32)
        self.row_active = np.zeros((B,), bool)
        # prefill-token accounting for the KV-reuse benchmark: computed =
        # tokens that ran the model, shared = page-aligned prompt tokens
        # served by mapping another request's pages (registry counters
        # behind the attribute API — see the class-level properties)
        self._prefill_computed = self.metrics.counter(
            "engine/prefill_tokens_computed")
        self._prefill_shared = self.metrics.counter(
            "engine/prefill_tokens_shared")
        self._truncated: set = set()

        # every jit is a named function: the name is the program's name
        # in a device trace, so each operation says which layer it serves
        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))

        def argmax_lengths(logits, ln):
            # a decode's next tokens and its new row lengths as one
            # (2, B) array: the step reads both with a single transfer
            return jnp.stack([jnp.argmax(logits, -1), ln])

        self._argmax_lengths = jax.jit(argmax_lengths)

        def prefill(p, t, ln):
            return model.prefill(p, t, max_len=cfg.max_len, lengths=ln)

        self._prefill = jax.jit(prefill)
        if self._paged:
            # map the serving-level knob onto the kernel wrapper's impl
            # strings; "gather" keeps the dense-view oracle path
            _impl = {"gather": "gather", "paged": "auto",
                     "paged_interpret": "pallas_interpret"}[cfg.attn_impl]

            def prefill_chunk_paged(p, t, c, ln, w, bt):
                return model.prefill_chunk(p, t, c, ln, w, block_table=bt,
                                           attn_impl=_impl)

            def decode_paged(p, c, t, ln, bt):
                return model.decode_step(p, c, t, ln, block_table=bt,
                                         attn_impl=_impl)

            self._chunk_paged = jax.jit(prefill_chunk_paged,
                                        donate_argnums=(2,))
            self._decode_paged = jax.jit(decode_paged, donate_argnums=(1,))
        # chunked prefill needs linear caches: SWA rings rotate by padded
        # length and recurrent conv/state prefill absorbs pads, so those
        # models keep the exact one-shot path (prefill_chunk is ignored)
        self._can_chunk = (cfg.prefill_chunk > 0
                           and hasattr(model, "prefill_chunk")
                           and not self._exact_prefill)
        if self._can_chunk:
            # widths-carrying wrapper: every chunk dispatch is the STATIC
            # (1, prefill_chunk) shape with the true width passed as data,
            # so the tail chunk of any prompt length reuses one compile
            # instead of retracing per remainder width
            def prefill_chunk(p, t, c, ln, w):
                return model.prefill_chunk(p, t, c, ln, w)

            self._chunk_fn = jax.jit(prefill_chunk, donate_argnums=(2,))

        # CoIC front: one ladder org (core/tiers.py) — a cooperative
        # cluster (1-node for the solo cache) or a cross-cluster federation
        # when coic.num_clusters > 1; each serving replica fronts one edge
        # node.  The engine's own prefill/decode path is the ladder's
        # cloud fall-through.
        self.coic_cfg = cfg.coic
        self.semantic = None
        self.sem_org = None
        self.sem_cluster = None
        self.sem_fed = None
        self._req_node: Dict[int, int] = {}
        self._req_cluster: Dict[int, int] = {}
        if cfg.coic is not None:
            c = cfg.coic
            if c.descriptor == "prefix":
                self._descriptor = PrefixDescriptor(model, k_layers=c.k_layers)
                key_dim = model.cfg.d_model

                def descriptor(p, t):
                    return self._descriptor(p, t)
            else:
                sk = NgramSketchDescriptor(dim=c.descriptor_dim)
                key_dim = c.descriptor_dim

                def descriptor(p, t):
                    return sk(t)
            self._desc_fn = jax.jit(descriptor)
            self.key_dim = key_dim
            cluster_cfg = ClusterConfig(
                num_nodes=c.num_nodes, node_capacity=c.capacity,
                key_dim=key_dim, payload_dim=cfg.max_new_tokens,
                threshold=c.threshold, payload_dtype="int32",
                policy=c.policy, lookup_impl=c.lookup_impl,
                admission=c.admission, share=c.share)
            if c.num_clusters > 1:
                self.sem_fed = FederatedEdgeTier(FederationConfig(
                    num_clusters=c.num_clusters, cluster=cluster_cfg,
                    digest_size=c.digest_size,
                    digest_interval=c.digest_interval,
                    digest_quant=c.digest_quant,
                    digest_refresh=c.digest_refresh, share=c.federate,
                    ann_mode=c.digest_ann,
                    ann_min_rows=c.digest_ann_min_rows,
                    ann_lists=c.digest_ann_lists,
                    ann_sub=c.digest_ann_sub,
                    ann_probe=c.digest_ann_probe),
                    metrics=self.metrics, tracer=self.trace)
                self.sem_org = self.sem_fed
                self.semantic = self.sem_fed.clusters[0].cache
            else:
                self.sem_cluster = CooperativeEdgeCluster(
                    cluster_cfg, metrics=self.metrics, tracer=self.trace)
                self.sem_org = self.sem_cluster
                self.semantic = self.sem_cluster.cache
            self._peer_on = c.share and c.num_nodes > 1
            self._region_on = (self.sem_fed is not None and c.federate
                               and c.num_clusters > 1)
            # satellite: cache-served requests are charged the modeled
            # network + probe latency instead of the old latency_s=0.0
            self.network = network or NetworkModel()
            self.router = TwoTierRouter(self.network, PayloadSizes(
                input_bytes=cfg.max_len * 4,
                descriptor_bytes=key_dim * 4,
                result_bytes=cfg.max_new_tokens * 4))

        # membership control plane (core/membership.py): requests whose
        # target cluster/node died reroute deterministically at schedule
        # time; the federation tombstones digests and re-elects pins on
        # detected deaths.  None == static grid.
        self.membership = membership
        if membership is not None:
            if self.sem_fed is not None:
                self.sem_fed.attach_membership(membership)
            elif self.sem_cluster is not None:
                membership.add_listener(self._on_cluster_membership_event)

    # ------------------------------------------------------------------
    def _on_cluster_membership_event(self, ev) -> None:
        """Single-cluster engines wire node churn straight to the shard
        masks (the federation path has its own listener)."""
        if ev.kind == "node_dead":
            self.sem_cluster.kill_node(ev.node)
        elif ev.kind == "node_alive":
            self.sem_cluster.revive_node(ev.node)
        elif ev.kind in ("cluster_dead", "cluster_alive"):
            self.sem_cluster.wipe()
            if ev.kind == "cluster_alive":
                self.sem_cluster.node_alive[:] = True

    # ------------------------------------------------------------------
    # registry-backed attribute API (the legacy names, mutated with +=/
    # max() by the scheduling code and read by tests and benchmarks)
    @property
    def prefill_tokens_computed(self) -> int:
        return self._prefill_computed.value

    @prefill_tokens_computed.setter
    def prefill_tokens_computed(self, v: int) -> None:
        self._prefill_computed.set(int(v))

    @property
    def prefill_tokens_shared(self) -> int:
        return self._prefill_shared.value

    @prefill_tokens_shared.setter
    def prefill_tokens_shared(self, v: int) -> None:
        self._prefill_shared.set(int(v))

    @property
    def last_step_ladder(self) -> int:
        return self._last_step_ladder.value

    @last_step_ladder.setter
    def last_step_ladder(self, v: int) -> None:
        self._last_step_ladder.set(int(v))

    @property
    def max_step_ladder(self) -> int:
        return self._max_step_ladder.value

    @max_step_ladder.setter
    def max_step_ladder(self, v: int) -> None:
        self._max_step_ladder.set(int(v))

    # ------------------------------------------------------------------
    def submit(self, prompt: np.ndarray, node_id: int = 0,
               cluster_id: int = 0, priority: int = 0,
               deadline_ms: Optional[float] = None) -> int:
        """prompt: (S,) int32 arriving at edge ``node_id`` of cluster
        ``cluster_id`` (ignored without a cluster/federation).  Enqueue-only:
        the lookup ladder runs at the next ``step()`` for the whole pending
        batch at once.  Returns request id (result arrives via ``step()``
        -> self.results).

        ``deadline_ms``: motion-to-photon budget relative to now (frame
        traffic); ``None`` marks bulk traffic.  Under
        ``queue_policy="edf"`` deadline-bearing requests are admitted
        earliest-deadline-first ahead of all bulk requests; ``priority``
        breaks ties within a class (higher first), submission order breaks
        the rest.  An expired deadline is still served (and counted as a
        miss), never dropped.

        Prompts longer than ``max_len`` overflow the per-slot cache:
        ``on_overflow="reject"`` raises ``PromptTooLongError`` here (no rid
        is consumed), ``"truncate"`` serves the ``max_len`` head and stamps
        ``ServedResult.truncated``."""
        prompt = np.asarray(prompt, np.int32)
        truncated = False
        if len(prompt) > self.cfg.max_len:
            if self.cfg.on_overflow == "reject":
                raise PromptTooLongError(
                    f"prompt length {len(prompt)} exceeds max_len "
                    f"{self.cfg.max_len}; truncating would silently change "
                    "the request (set on_overflow='truncate' to opt in)")
            prompt = prompt[:self.cfg.max_len]
            truncated = True
        rid = self._req_counter
        self._req_counter += 1
        if truncated:
            self._truncated.add(rid)
        self._t_submit[rid] = time.perf_counter()
        self._priority[rid] = priority
        if priority:
            self._n_priority += 1
        self._deadline[rid] = deadline_ms
        self._submit_step[rid] = self.step_count
        if deadline_ms is not None:
            # absolute deadline on the paced clock (step_ms=0 collapses to
            # the relative budget, which still orders same-step arrivals)
            self._abs_deadline[rid] = (self.step_count * self.cfg.step_ms
                                       + deadline_ms)
        self.pending.append((rid, prompt, node_id, cluster_id))
        return rid

    # ------------------------------------------------------------------
    def _queue_key(self, entry):
        """Admission order: EDF over absolute deadlines (bulk == +inf), then
        priority (higher first), then FIFO (rid is submission order)."""
        rid = entry[0]
        if self.cfg.queue_policy == "fifo":
            return (rid,)
        dl = self._abs_deadline.get(rid, np.inf)
        return (dl, -self._priority.get(rid, 0), rid)

    def _order_queue(self) -> None:
        # pure-bulk fast path: with no deadline and no nonzero priority in
        # flight every EDF key is (inf, 0, rid) — already FIFO, skip the
        # per-step O(Q log Q) sort a deep backlog would otherwise pay
        if (self.cfg.queue_policy == "fifo" or len(self.queue) < 2
                or (not self._abs_deadline and not self._n_priority)):
            return
        self.queue = deque(sorted(self.queue, key=self._queue_key))

    # ------------------------------------------------------------------
    def _complete(self, rid: int, source: str, modeled_ms: float,
                  wall_s: float, waited: int) -> Tuple[float, bool]:
        """Completion accounting for ``rid`` served by ``source``: queueing
        delay (``waited`` paced steps when ``step_ms`` > 0, else measured
        wall time) plus the modeled per-tier terms; records the per-tier
        deadline outcome.  Returns (completion_ms, deadline_miss)."""
        if self.cfg.step_ms > 0:
            completion_ms = waited * self.cfg.step_ms + modeled_ms
        elif modeled_ms > 0:
            completion_ms = modeled_ms
        else:
            completion_ms = wall_s * 1e3
        miss = self.deadline.observe(source, completion_ms,
                                     self._deadline.get(rid))
        return completion_ms, miss

    def _finalize(self, rid: int, *, tokens: np.ndarray, source: str,
                  latency_s: float, decode_steps: int,
                  breakdown: Optional[LatencyBreakdown] = None,
                  modeled_ms: float = 0.0, wall_s: float = 0.0,
                  terms: Optional[list] = None) -> None:
        """Shared completion bookkeeping for the hit path and ``_retire``:
        deadline outcome, priority-counter release, the ``ServedResult``
        record, and — when tracing — the request's modeled timeline
        (``terms``: (name, ms) spans that, with the queueing delay, sum to
        ``completion_ms``)."""
        sub_step = self._submit_step.pop(rid, self.step_count)
        completion_ms, missed = self._complete(rid, source, modeled_ms,
                                               wall_s,
                                               self.step_count - sub_step)
        prio = self._priority.pop(rid, 0)
        if prio:
            self._n_priority -= 1
        self._completed.inc()
        self._hits.inc(source)
        self.results.append(ServedResult(
            req_id=rid, tokens=tokens, source=source, latency_s=latency_s,
            decode_steps=decode_steps, breakdown=breakdown, priority=prio,
            deadline_ms=self._deadline.pop(rid, None),
            completion_ms=completion_ms, deadline_miss=missed,
            submit_step=sub_step, finish_step=self.step_count,
            truncated=rid in self._truncated))
        self._truncated.discard(rid)
        self._abs_deadline.pop(rid, None)
        tr = self.trace
        if tr.enabled:
            # engine track: the serving step this request finished in
            tr.begin(f"request:{rid}", cat="request",
                     args={"tier": source, "completion_ms": completion_ms,
                           "decode_steps": decode_steps})
            tr.end()
            # request track: modeled spans laid end-to-end on the paced
            # clock, reconstructing completion_ms exactly
            tl = list(terms or [])
            wait_ms = ((self.step_count - sub_step) * self.cfg.step_ms
                       if self.cfg.step_ms > 0 else 0.0)
            if wait_ms > 0:
                # cloud requests spend their steps computing, hits waiting
                tl.insert(0, ("engine_steps" if source == "cloud"
                              else "queue_wait", wait_ms))
            resid = completion_ms - sum(t[1] for t in tl)
            if resid > 1e-9:
                tl.append(("serve_wall", resid))
            tr.request_timeline(rid, ts_ms=sub_step * self.cfg.step_ms,
                                tier=source, terms=tl,
                                completion_ms=completion_ms,
                                args={"deadline_miss": missed})

    # ------------------------------------------------------------------
    def _pad_prompts(self, prompts: List[np.ndarray], fill: int,
                     exact: bool = False):
        """Right-pad ``prompts`` with ``fill`` into a (pow2-B, pow2-S)
        bucket (``exact``: no length padding — recurrent-state prefill).
        Returns (tokens (Bb, Sb) int32, lengths (n,) int32)."""
        n = len(prompts)
        lens = np.array([len(p) for p in prompts], np.int32)
        Sb = (int(lens.max()) if exact else
              min(_pow2(int(lens.max()), self.cfg.min_bucket),
                  self.cfg.max_len))
        Bb = _pow2(n)
        toks = np.full((Bb, Sb), fill, np.int32)
        for i, p in enumerate(prompts):
            toks[i, :len(p)] = p[:Sb]
        return toks, np.minimum(lens, Sb)

    def _extract_descriptors(self, prompts: List[np.ndarray]) -> np.ndarray:
        """ONE jitted descriptor extraction over the length-bucketed pad.
        Returns (n, D) np descriptors and the wall ms of the dispatch."""
        toks, _ = self._pad_prompts(prompts, fill=-1)
        tr = self.trace
        if tr.enabled:
            tr.begin("descriptor", cat="engine",
                     args={"batch": len(prompts)})
        t0 = time.perf_counter()
        desc = self._desc_fn(self.params, jnp.asarray(toks))
        desc.block_until_ready()
        if tr.enabled:
            tr.end()
        self.dispatches["descriptor"] += 1
        return (to_host(tr, "descriptor", desc)[:len(prompts)],
                (time.perf_counter() - t0) * 1e3)

    # ------------------------------------------------------------------
    def _schedule(self) -> None:
        """Drain pending requests through the batched lookup ladder: one
        descriptor dispatch + one grouped cluster lookup for ALL pending
        requests (or one request in sequential mode)."""
        if not self.pending:
            return
        n_drain = 1 if self.cfg.scheduling == "sequential" else len(self.pending)
        batch = [self.pending.popleft() for _ in range(n_drain)]
        if self.membership is not None:
            # degraded routing: resolve each request's target against
            # CURRENT liveness (not submit-time liveness) — a dead target
            # remaps to the nearest alive (cluster, node) by deterministic
            # upward scan, so the ladder below only sees live targets
            rerouted = []
            for rid, prompt, node, clu in batch:
                clu, node = self.membership.route(clu, node)
                rerouted.append((rid, prompt, node, clu))
            batch = rerouted
        prompts = [b[1] for b in batch]
        nodes = [b[2] for b in batch]
        clusters = [b[3] for b in batch]

        if self.semantic is None:                 # no CoIC front
            for rid, prompt, node, clu in batch:
                self._req_node[rid] = node
                self._req_cluster[rid] = clu
                self.queue.append((rid, prompt))
            return

        desc, desc_ms = self._extract_descriptors(prompts)
        n = len(batch)

        # ONE route through the org's TierLadder, whatever the config
        # (solo 1-node cluster / cooperative cluster / federation); the
        # org ladder shares this engine's tracer, so per-rung probe spans
        # nest under this lookup span
        tr = self.trace
        if tr.enabled:
            tr.begin("lookup", cat="engine", args={"batch": n})
        t0 = time.perf_counter()
        res = route_flat(self.sem_org, desc, nodes, clusters)
        self.dispatches["lookup"] += 1
        lookup_ms = (time.perf_counter() - t0) * 1e3
        if tr.enabled:
            tr.end()
        tier, value = res.tier, res.value
        hit = tier != TIER_MISS

        # every local miss (peer hit or cloud miss) shares ONE peer
        # descriptor broadcast — per CLUSTER: each metro's LAN broadcast
        # carries only its own misses; everything escalating past the peer
        # tier shares that home cluster's ONE metro->region digest message;
        # local hits share the step's single descriptor + lookup dispatch
        clus_np = np.asarray(clusters)
        lm = {k: int(((tier != TIER_LOCAL) & (clus_np == k)).sum())
              for k in set(clusters)}
        esc = {k: int(((tier >= TIER_REMOTE) & (clus_np == k)).sum())
               for k in set(clusters)} if self._region_on else {}
        for i, (rid, prompt, node, clu) in enumerate(batch):
            if hit[i]:
                toks = np.asarray(value[i], np.int32)
                t = int(tier[i])
                name = TIER_NAMES[t]
                src = SOURCE_OF[name]
                amort = {TIER_LOCAL: n, TIER_PEER: max(1, lm[clu]),
                         TIER_REMOTE: max(1, esc.get(clu, 0))}[t]
                lat = self.router.tier_latency(
                    name, desc_ms / n, lookup_ms / n, batch=amort,
                    peer_net_ms=(self.router.peer_broadcast_ms(lm[clu])
                                 if t == TIER_REMOTE and self._peer_on
                                 else 0.0))
                t_sub = self._t_submit.pop(rid)
                lat.deadline_ms = self._deadline.get(rid)
                modeled_ms = lat.total_ms
                skip = ()
                if self.cfg.step_ms > 0:
                    # paced simulation: device compute rides the step
                    # clock; keep only the modeled network terms — the
                    # measured desc/lookup wall time includes first-call
                    # jit compiles, which are not motion-to-photon signal
                    modeled_ms -= lat.descriptor_ms + lat.lookup_ms
                    skip = ("descriptor_ms", "lookup_ms")
                self._finalize(rid, tokens=toks, source=src,
                               latency_s=time.perf_counter() - t_sub,
                               decode_steps=0,
                               breakdown=lat, modeled_ms=modeled_ms,
                               wall_s=lat.total_ms / 1e3,
                               terms=(_latency_terms(lat, skip)
                                      if tr.enabled else None))
            else:
                self._req_node[rid] = node
                self._req_cluster[rid] = clu
                self._desc_of[rid] = desc[i]
                self.queue.append((rid, prompt))

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        """Deadline-ordered admission: the queue is sorted by the EDF key
        (FIFO under ``queue_policy="fifo"`` or when nothing carries a
        deadline), then drained front-to-back — long prompts peel off into
        the chunked path (one reserved slot, one ``prefill_chunk``-token
        dispatch per step), everything else joins ONE bucketed batched
        prefill dispatch (sequential mode: one request per step).

        Paged mode (``kv_page > 0``) replaces all of that with continuous
        batching against the page pool: every queued request with a free
        slot maps its shareable prefix pages and joins the chunking set,
        then ONE batched ``prefill_chunk`` dispatch advances every
        mid-prefill row together — newly admitted rows ride the same
        dispatch as rows admitted steps ago, and their remainders land
        while other rows decode."""
        if self._paged:
            self._admit_paged()
            return
        self._advance_chunks()
        self._order_queue()
        # sequential mode is the per-request one-shot baseline: chunking
        # stays out of it so batched-vs-sequential comparisons measure
        # scheduling, not admission shape
        chunking_on = self._can_chunk and self.cfg.scheduling != "sequential"
        while self.queue and self.free_slots:
            if chunking_on and \
                    len(self.queue[0][1]) > self.cfg.prefill_chunk:
                rid, prompt = self.queue.popleft()
                slot = self.free_slots.pop()
                st = _Chunking(req_id=rid, slot=slot,
                               prompt=prompt[:self.cfg.max_len],
                               cache=init_batch_cache(self.model, 1,
                                                      self.cfg.max_len))
                self.chunking[rid] = st
                self._advance_chunk(st)       # first chunk rides this step
                continue
            m = min(len(self.queue), len(self.free_slots))
            if self.cfg.scheduling == "sequential":
                m = 1
            elif self._exact_prefill:
                # equal-length front run only: no right-pad for SSM states
                # or SWA ring rotation
                L0 = len(self.queue[0][1])
                run = 1
                while run < m and len(self.queue[run][1]) == L0:
                    run += 1
                m = run
            if chunking_on:
                # the bucketed dispatch takes only the front run of short
                # prompts: a long prompt mid-queue must not inflate the
                # shared (pow2 B, pow2 S) pad bucket
                run = 1
                while run < m and \
                        len(self.queue[run][1]) <= self.cfg.prefill_chunk:
                    run += 1
                m = run
            taken = [self.queue.popleft() for _ in range(m)]
            prompts = [p for _, p in taken]
            toks, lens = self._pad_prompts(prompts, fill=0,
                                           exact=self._exact_prefill)
            Bb = toks.shape[0]
            lens_pad = np.zeros((Bb,), np.int32)
            lens_pad[:m] = lens
            tr = self.trace
            if tr.enabled:
                tr.begin("prefill", cat="engine",
                         args={"rows": m, "bucket": int(toks.shape[1])})
            logits, many_cache, _ = self._prefill(
                self.params, jnp.asarray(toks), jnp.asarray(lens_pad))
            if tr.enabled:
                tr.end()
            self.dispatches["prefill"] += 1
            self.prefill_tokens_computed += int(lens.sum())
            slots = [self.free_slots.pop() for _ in range(m)]
            self.cache = batch_cache_scatter(
                self.cache, {k: v[:, :m] for k, v in many_cache.items()},
                jnp.asarray(slots, jnp.int32))
            nxt = to_host(tr, "argmax",
                          jnp.argmax(logits, -1).astype(jnp.int32))[:m]
            if tr.enabled:
                tr.begin("h2d:row_state", cat="sync")
            self.lengths = self.lengths.at[jnp.asarray(slots)].set(
                jnp.asarray(lens))
            self.tokens = self.tokens.at[jnp.asarray(slots)].set(
                jnp.asarray(nxt))
            if tr.enabled:
                tr.end()
            now = time.perf_counter()
            for i, ((rid, prompt), slot) in enumerate(zip(taken, slots)):
                self.row_active[slot] = True
                self.active[slot] = _Active(req_id=rid, slot=slot,
                                            generated=[int(nxt[i])],
                                            t_admit=now)
                self._prompts[rid] = prompt

    # ------------------------------------------------------------------
    def _admit_paged(self) -> None:
        """Continuous-batching admission against the paged pool: EDF-drain
        the queue into the chunking set (each admission probes the prefix
        index — mapped pages start ``filled`` past zero), then advance
        every mid-prefill row in ONE batched chunk dispatch.  Admitting
        before advancing means a request's first chunk rides the step it
        was admitted on."""
        self._order_queue()
        while self.queue and self.free_slots:
            rid, prompt = self.queue.popleft()
            slot = self.free_slots.pop()
            shared_tok = self.kv.admit(slot, prompt)
            self.prefill_tokens_shared += shared_tok
            self.chunking[rid] = _Chunking(
                req_id=rid, slot=slot, prompt=prompt, cache=None,
                filled=shared_tok,
                shared_pages=shared_tok // self.cfg.kv_page)
        self._advance_chunks_paged()
        for _ in range(self.cfg.chunk_pacing - 1):
            # idle pacing, as in the dense path: extra batched advances
            # only when no admission or decode slot is waiting on us
            if not self.chunking or self.queue or not self.free_slots:
                break
            self._advance_chunks_paged()

    def _advance_chunks_paged(self) -> None:
        """ONE (pow2 rows, chunk_width) ``prefill_chunk`` dispatch over
        every mid-prefill row: per-row lengths, true widths, and
        block-table rows; pad rows carry width 0 and an all-INVALID table,
        so their writes drop.  Rows whose last chunk lands activate for
        decode and publish their computed full pages to the prefix
        index."""
        if not self.chunking:
            return
        tr = self.trace
        if tr.enabled:
            tr.begin("chunk_prep", cat="engine",
                     args={"rows": len(self.chunking)})
        sts = sorted(self.chunking.values(),
                     key=lambda st: self._queue_key((st.req_id,)))
        C = self._chunk_width
        Bb = _pow2(len(sts))
        toks = np.zeros((Bb, C), np.int32)
        lens = np.zeros((Bb,), np.int32)
        widths = np.zeros((Bb,), np.int32)
        bt = np.full((Bb, self.kv.pages_per_slot), PagedKVCache.INVALID,
                     np.int32)
        for i, st in enumerate(sts):
            n = min(C, len(st.prompt) - st.filled)
            toks[i, :n] = st.prompt[st.filled:st.filled + n]
            lens[i] = st.filled
            widths[i] = n
            bt[i] = self.kv.block_table[st.slot]
        if tr.enabled:
            tr.end()
            tr.begin("prefill_chunk", cat="engine",
                     args={"rows": len(sts), "width": C})
            tr.begin("h2d:chunk", cat="sync")
        toks_d, lens_d = jnp.asarray(toks), jnp.asarray(lens)
        widths_d, bt_d = jnp.asarray(widths), jnp.asarray(bt)
        if tr.enabled:
            tr.end()
        logits, self.cache, _ = self._chunk_paged(
            self.params, toks_d, self.cache, lens_d, widths_d, bt_d)
        if tr.enabled:
            tr.end()
        self.dispatches["prefill_chunk"] += 1
        self.prefill_tokens_computed += int(widths.sum())
        nxt = to_host(tr, "argmax", jnp.argmax(logits, -1))
        now = time.perf_counter()
        for i, st in enumerate(sts):
            st.filled += int(widths[i])
            if st.filled < len(st.prompt):
                continue
            rid, slot = st.req_id, st.slot
            del self.chunking[rid]
            self.kv.register(slot, st.prompt, from_page=st.shared_pages)
            if tr.enabled:
                tr.begin("h2d:row_state", cat="sync")
            self.lengths = self.lengths.at[slot].set(len(st.prompt))
            self.tokens = self.tokens.at[slot].set(int(nxt[i]))
            if tr.enabled:
                tr.end()
            self.row_active[slot] = True
            self.active[slot] = _Active(req_id=rid, slot=slot,
                                        generated=[int(nxt[i])],
                                        t_admit=now)
            self._prompts[rid] = st.prompt

    # ------------------------------------------------------------------
    def _advance_chunks(self) -> None:
        """One ``prefill_chunk``-token dispatch per in-flight long prompt
        per step — the trickle that lets other admissions interleave.
        With ``chunk_pacing > 1`` and an otherwise-idle engine (free decode
        slots, empty admission queue) each prompt may advance up to
        ``chunk_pacing`` chunks this step, most-urgent (EDF key) first —
        idle steps finish long prompts sooner without ever delaying an
        admission or changing decoded tokens."""
        # EDF order so any extra pacing budget goes to the most urgent
        sts = sorted(self.chunking.values(),
                     key=lambda st: self._queue_key((st.req_id,)))
        for st in sts:
            self._advance_chunk(st)
        if self.cfg.chunk_pacing <= 1:
            return
        for st in sts:
            for _ in range(self.cfg.chunk_pacing - 1):
                if (st.req_id not in self.chunking or self.queue
                        or not self.free_slots):
                    break
                self._advance_chunk(st)

    def _advance_chunk(self, st: _Chunking) -> None:
        """Feed the next chunk of ``st``'s prompt through
        ``model.prefill_chunk``; on the last chunk, scatter the B=1 cache
        into the reserved slot and activate the row (bit-identical state to
        the one-shot prefill — the chunk path writes the same positions
        with the same values, just across steps).

        The dispatch shape is the STATIC (1, prefill_chunk): a short tail
        chunk is zero-padded and its true width passed as data, so the
        model masks the pad instead of the engine retracing the jit once
        per distinct remainder length."""
        C = self.cfg.prefill_chunk
        n = min(C, len(st.prompt) - st.filled)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :n] = st.prompt[st.filled:st.filled + n]
        tr = self.trace
        if tr.enabled:
            tr.begin("prefill_chunk", cat="engine",
                     args={"rid": st.req_id, "width": n})
            tr.begin("h2d:chunk", cat="sync")
        chunk_d = jnp.asarray(chunk)
        filled_d = jnp.asarray([st.filled], jnp.int32)
        n_d = jnp.asarray([n], jnp.int32)
        if tr.enabled:
            tr.end()
        logits, st.cache, _ = self._chunk_fn(
            self.params, chunk_d, st.cache, filled_d, n_d)
        if tr.enabled:
            tr.end()
        self.dispatches["prefill_chunk"] += 1
        self.prefill_tokens_computed += n
        st.filled += n
        if st.filled < len(st.prompt):
            return
        rid, slot = st.req_id, st.slot
        del self.chunking[rid]
        self.cache = batch_cache_scatter(
            self.cache, st.cache, jnp.asarray([slot], jnp.int32))
        nxt = int(to_host(tr, "argmax", jnp.argmax(logits[0])))
        L = len(st.prompt)
        if tr.enabled:
            tr.begin("h2d:row_state", cat="sync")
        self.lengths = self.lengths.at[slot].set(L)
        self.tokens = self.tokens.at[slot].set(nxt)
        if tr.enabled:
            tr.end()
        self.row_active[slot] = True
        self.active[slot] = _Active(req_id=rid, slot=slot, generated=[nxt],
                                    t_admit=time.perf_counter())
        self._prompts[rid] = st.prompt

    def _retire(self, slot: int) -> None:
        a = self.active.pop(slot)
        tr = self.trace
        if tr.enabled:
            tr.begin("retire", cat="engine",
                     args={"rid": a.req_id, "slot": slot})
        toks = np.asarray(a.generated[:self.cfg.max_new_tokens], np.int32)
        t_sub = self._t_submit.pop(a.req_id, a.t_admit)
        wall_s = time.perf_counter() - t_sub
        modeled_ms = 0.0
        terms = None
        if self.cfg.step_ms > 0 and self.semantic is not None:
            # paced simulation: the engine's own compute is counted in
            # steps; add only the modeled network terms around it
            lat = self.router.miss_latency(0.0, 0.0, 0.0)
            modeled_ms = lat.total_ms
            if tr.enabled:
                terms = _latency_terms(lat)
        self._finalize(a.req_id, tokens=toks, source="cloud",
                       latency_s=wall_s, decode_steps=len(a.generated),
                       modeled_ms=modeled_ms, wall_s=wall_s, terms=terms)
        self.row_active[slot] = False
        self.free_slots.append(slot)
        if self._paged:
            # refcount-- on every mapped page; pages at zero join the free
            # list but stay probe-able until recycled, so this request's
            # prefix keeps serving future admissions
            self.kv.free_slot(slot)
        node = self._req_node.pop(a.req_id, 0)
        clu = self._req_cluster.pop(a.req_id, 0)
        if self.membership is not None:
            # the home shard may have died while this request computed:
            # insert into the live reroute target instead (and
            # cluster.insert drops writes to dead nodes regardless)
            clu, node = self.membership.route(clu, node)
        prompt = self._prompts.pop(a.req_id, None)
        if self.semantic is not None and prompt is not None:
            # reuse the schedule-time descriptor (every miss cached one in
            # _schedule): no extra extraction dispatch, ever
            desc = self._desc_of.pop(a.req_id)
            pad = np.zeros((self.cfg.max_new_tokens,), np.int32)
            pad[:len(toks)] = toks
            self.sem_org.insert_home(clu, node, jnp.asarray(desc[None, :]),
                                     jnp.asarray(pad[None, :]))
        if tr.enabled:
            tr.end()

    # ------------------------------------------------------------------
    def step(self) -> None:
        """One engine iteration: schedule (batched lookup ladder) + admit
        (EDF-ordered bucketed/chunked prefill) + one batched decode step."""
        self.step_count += 1
        tr = self.trace
        if not tr.enabled:                  # the untraced hot path
            self._step_inner()
            return
        tr.begin("step", cat="engine", args={"step": self.step_count})
        try:
            self._step_inner()
        finally:
            tr.end()

    def _step_inner(self) -> None:
        tr = self.trace
        ladder0 = self.dispatches["descriptor"] + self.dispatches["lookup"]
        if tr.enabled:
            tr.begin("schedule", cat="engine",
                     args={"pending": len(self.pending)})
        self._schedule()
        if tr.enabled:
            tr.end()
        self.last_step_ladder = (self.dispatches["descriptor"]
                                 + self.dispatches["lookup"] - ladder0)
        self.max_step_ladder = max(self.max_step_ladder,
                                   self.last_step_ladder)
        if tr.enabled:
            tr.begin("admit", cat="engine", args={"queued": len(self.queue)})
        self._admit()
        if tr.enabled:
            tr.end()
        if not self.active:
            return
        if tr.enabled:
            tr.begin("decode", cat="engine",
                     args={"active": int(self.row_active.sum())})
        t0 = time.perf_counter()
        if self._paged:
            # mid-prefill and free rows ride the batched decode with an
            # all-INVALID table row: their junk write drops instead of
            # landing in a live or half-filled page
            if tr.enabled:
                tr.begin("h2d:decode_table", cat="sync")
            bt = jnp.asarray(self.kv.decode_table(self.row_active))
            if tr.enabled:
                tr.end()
            logits, self.cache, self.lengths = self._decode_paged(
                self.params, self.cache, self.tokens, self.lengths, bt)
        else:
            logits, self.cache, self.lengths = self._decode(
                self.params, self.cache, self.tokens, self.lengths)
        self.dispatches["decode"] += 1
        # the lengths stay on the device; the done test reads the host
        # copy that comes back with the argmax, once per step
        nxt, lens = to_host(tr, "argmax",
                            self._argmax_lengths(logits, self.lengths))
        self._decode_ms.observe((time.perf_counter() - t0) * 1e3)
        if tr.enabled:
            tr.end()
            tr.begin("emit", cat="engine", args={"rows": len(self.active)})
        for slot in list(self.active):
            a = self.active[slot]
            a.generated.append(int(nxt[slot]))
            done = (len(a.generated) >= self.cfg.max_new_tokens
                    or (self.cfg.eos_id >= 0 and nxt[slot] == self.cfg.eos_id)
                    or lens[slot] >= self.cfg.max_len - 1)
            if done:
                self._retire(slot)
        if tr.enabled:
            tr.end()
            tr.begin("h2d:tokens", cat="sync")
        self.tokens = jnp.asarray(nxt)
        if tr.enabled:
            tr.end()

    def run_until_drained(self, max_steps: int = 10_000) -> List[ServedResult]:
        steps = 0
        while (self.pending or self.queue or self.chunking
               or self.active) and steps < max_steps:
            self.step()
            steps += 1
        return self.results

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        # every number here is a view over self.metrics — snapshot() on the
        # registry reproduces this dict's counters bit-for-bit
        out = {
            "completed": self._completed.value,
            "edge_hits": self._hits.get("edge"),
            "peer_hits": self._hits.get("peer"),
            "remote_hits": self._hits.get("remote"),
            "cloud": self._hits.get("cloud"),
            "dispatches": dict(self.dispatches),
            "max_step_ladder": self.max_step_ladder,
            "deadline": self.deadline.as_dict(),
            "prefill_tokens": {"computed": self.prefill_tokens_computed,
                               "shared": self.prefill_tokens_shared},
        }
        if self._paged:
            out["kv"] = self.kv.stats_dict()
        if self.sem_org is not None:
            # the shared stats formatter (obs/views.py): the cache-org
            # block + the uniform per-tier dispatch/digest block, same
            # shapes for solo / cluster / federation configs
            out["semantic"] = org_stats(self.sem_fed, self.sem_cluster,
                                        self.semantic)
            out["ladder"] = ladder_block(self.sem_org)
            out["digest"] = digest_block(self.sem_fed)
        if self.membership is not None:
            out["membership"] = self.membership.stats()
        return out
