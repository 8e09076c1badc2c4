"""Cooperative edge cluster: sharded top-k lookup exactness, pooled-cache
equivalence, and per-node eviction invariants.

Property-style tests run seeded-random sequences directly (no ``hypothesis``
dependency — the container may not ship it, and these invariants must always
be exercised, not skipped)."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.cluster import ClusterConfig, CooperativeEdgeCluster
from repro.core.policies import EvictionPolicy
from repro.core.semantic_cache import SemanticCache
from repro.kernels.similarity import similarity_topk
from repro.parallel.sharding import cluster_topk_lookup


def _unit(rng, n, d):
    x = rng.standard_normal((n, d)).astype(np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def _dyadic(rng, n, d):
    """Vectors of eighths in [-1/2, 1/2]: every partial dot product is
    exact in f32, so scores agree bit for bit whatever the summation order
    of the matmul shapes compared, and ties are frequent."""
    return (rng.integers(-4, 5, size=(n, d)) / 8).astype(np.float32)


# ---------------------------------------------------------------------------
# sharded/tiled top-k vs the single-device jnp oracle
# ---------------------------------------------------------------------------


class TestTopK:
    @pytest.mark.parametrize("q,c,d,k", [(4, 32, 16, 4), (100, 1000, 48, 8),
                                         (7, 513, 128, 3), (1, 8, 256, 8),
                                         (16, 64, 32, 1)])
    def test_tiled_kernel_matches_ref(self, q, c, d, k, nprng):
        qs, ks = _unit(nprng, q, d), _unit(nprng, c, d)
        ks[min(5, c - 1)] = qs[0]                      # guaranteed exact hit
        valid = nprng.random(c) > 0.3
        valid[min(5, c - 1)] = True
        i_ref, s_ref = similarity_topk(jnp.asarray(qs), jnp.asarray(ks),
                                       jnp.asarray(valid), k, impl="ref")
        i_pal, s_pal = similarity_topk(jnp.asarray(qs), jnp.asarray(ks),
                                       jnp.asarray(valid), k,
                                       impl="pallas_interpret",
                                       block_q=32, block_c=64)
        np.testing.assert_allclose(np.asarray(s_ref), np.asarray(s_pal),
                                   rtol=2e-5, atol=2e-5)
        real = np.asarray(s_ref) > -1e29
        assert np.array_equal(np.asarray(i_ref)[real], np.asarray(i_pal)[real])

    @pytest.mark.parametrize("n,c,q,d,k", [(4, 64, 8, 32, 4), (2, 16, 5, 16, 3),
                                           (3, 8, 2, 8, 8), (8, 128, 16, 64, 2)])
    def test_cluster_lookup_bitexact_vs_pooled_oracle(self, n, c, q, d, k):
        """The vmapped cluster-wide lookup over stacked shards must match a
        single jnp top-k over the pooled key matrix BIT-exactly — scores and
        indices — including tie-breaks (dyadic inputs: the matmul shapes
        differ, so only exact partial sums make bit-equality a property of
        the selection, not of the host's summation order)."""
        rng = np.random.default_rng(n * 1000 + c)
        keys = _dyadic(rng, n * c, d).reshape(n, c, d)
        qs = _dyadic(rng, q, d)
        valid = rng.random((n, c)) > 0.3
        gi, gs = cluster_topk_lookup(jnp.asarray(qs), jnp.asarray(keys),
                                     jnp.asarray(valid), k)
        oi, os_ = similarity_topk(jnp.asarray(qs),
                                  jnp.asarray(keys.reshape(n * c, d)),
                                  jnp.asarray(valid.reshape(-1)), k, impl="ref")
        assert np.array_equal(np.asarray(gs), np.asarray(os_))
        assert np.array_equal(np.asarray(gi), np.asarray(oi))

    def test_duplicate_scores_tiebreak_to_lowest_index(self):
        """Identical keys on different shards: the merged top-k must prefer
        the lower global index, like ``lax.top_k`` over the pooled row."""
        d = 16
        rng = np.random.default_rng(0)
        key = _unit(rng, 1, d)[0]
        keys = np.tile(key, (3, 4, 1)).astype(np.float32)   # all 12 identical
        valid = np.ones((3, 4), bool)
        gi, gs = cluster_topk_lookup(jnp.asarray(key[None]), jnp.asarray(keys),
                                     jnp.asarray(valid), 5)
        assert np.array_equal(np.asarray(gi)[0], np.arange(5))

    @pytest.mark.slow
    def test_shard_map_lookup_bitexact(self):
        """shard_map over a real 4-device ``cache`` mesh == pooled oracle,
        bit-exact on dyadic inputs (see ``_dyadic``; subprocess: XLA locks
        host device count at first init)."""
        import os
        import subprocess
        import sys
        import textwrap

        src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
        code = textwrap.dedent("""
            import numpy as np, jax, jax.numpy as jnp
            from repro.parallel.sharding import sharded_topk_lookup
            from repro.kernels.similarity import similarity_topk
            mesh = jax.make_mesh((4,), ("cache",))
            rng = np.random.default_rng(2)
            n, c, q, d, k = 4, 32, 6, 16, 5
            keys = (rng.integers(-4, 5, size=(n, c, d)) / 8).astype(np.float32)
            qs = (rng.integers(-4, 5, size=(q, d)) / 8).astype(np.float32)
            valid = rng.random((n, c)) > 0.3
            si, ss = sharded_topk_lookup(jnp.asarray(qs), jnp.asarray(keys),
                                         jnp.asarray(valid), k, mesh)
            oi, os_ = similarity_topk(jnp.asarray(qs),
                                      jnp.asarray(keys.reshape(n*c, d)),
                                      jnp.asarray(valid.reshape(-1)), k,
                                      impl="ref")
            assert np.array_equal(np.asarray(ss), np.asarray(os_))
            assert np.array_equal(np.asarray(si), np.asarray(oi))
            print("SHARDED_TOPK_OK")
        """)
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300)
        assert "SHARDED_TOPK_OK" in r.stdout, r.stdout + r.stderr

    @pytest.mark.slow
    def test_mesh_cluster_serves_like_pooled_cluster(self):
        """A cluster on a 4-device ``cache`` mesh keeps shard g on device g
        (local rung per device, peer rung a collective) and serves what
        the same cluster pooled on one device serves (subprocess: XLA
        locks host device count at first init)."""
        import os
        import subprocess
        import sys
        import textwrap

        src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
        code = textwrap.dedent("""
            import numpy as np, jax.numpy as jnp
            from repro.core.cluster import ClusterConfig, CooperativeEdgeCluster
            from repro.core.tiers import TIER_PEER
            from repro.launch.mesh import make_cache_mesh
            rng = np.random.default_rng(4)
            n, d, p = 4, 32, 4
            pool = rng.standard_normal((24, d)).astype(np.float32)
            pool /= np.linalg.norm(pool, axis=-1, keepdims=True)
            pay = rng.standard_normal((24, p)).astype(np.float32)
            cfg = ClusterConfig(num_nodes=n, node_capacity=16, key_dim=d,
                                payload_dim=p, threshold=0.9)
            cls = [CooperativeEdgeCluster(cfg, mesh=make_cache_mesh(n)),
                   CooperativeEdgeCluster(cfg)]
            for g in range(n):
                ids = rng.integers(0, 24, size=5)
                for cl in cls:
                    cl.insert(g, jnp.asarray(pool[ids]), jnp.asarray(pay[ids]))
            keys, _, _ = cls[0]._stacks()
            assert sorted(s.device.id for s in keys.addressable_shards) == \\
                [0, 1, 2, 3]
            q = jnp.asarray(pool[rng.integers(0, 24, size=(n, 6))])
            got, want = (cl.lookup_grouped(q) for cl in cls)
            for name in ("hit", "tier", "owner", "value"):
                np.testing.assert_array_equal(getattr(got, name),
                                              getattr(want, name))
            np.testing.assert_allclose(got.score, want.score, atol=1e-6)
            assert (np.asarray(got.tier) == TIER_PEER).any()
            print("MESH_CLUSTER_OK")
        """)
        env = dict(os.environ)
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, env=env, timeout=300)
        assert "MESH_CLUSTER_OK" in r.stdout, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# cooperative cluster == one pooled cache (admission on, no eviction pressure)
# ---------------------------------------------------------------------------


class TestPooledEquivalence:
    @pytest.mark.parametrize("seed", range(6))
    def test_cluster_results_equal_pooled_cache(self, seed):
        """Random interleaved multi-node traffic: with peer admission on and
        capacity sized so nothing evicts, every lookup's (hit, value) must
        equal a single pooled SemanticCache seeing the same request stream.

        Scenes are random unit vectors (near-orthogonal at d=32), so at most
        one cached key sits above threshold for any query and local-first
        serving order cannot change the returned value."""
        rng = np.random.default_rng(seed)
        n_nodes, d, p, tau = 4, 32, 4, 0.8
        pool = _unit(rng, 24, d)
        payloads = rng.standard_normal((24, p)).astype(np.float32)

        # node capacity absorbs own misses + admissions: <= pool size total
        cluster = CooperativeEdgeCluster(ClusterConfig(
            num_nodes=n_nodes, node_capacity=64, key_dim=d, payload_dim=p,
            threshold=tau, admission="always"))
        pooled = SemanticCache(capacity=4 * 64, key_dim=d, payload_dim=p,
                               threshold=tau)
        pstate = pooled.init()

        for _ in range(30):
            node = int(rng.integers(n_nodes))
            ids = rng.integers(0, 24, size=int(rng.integers(1, 6)))
            q = jnp.asarray(pool[ids])

            cres = cluster.lookup(node, q)
            pstate, pres = pooled.lookup(pstate, q)
            p_hit = np.asarray(pres.hit)

            assert np.array_equal(cres.hit, p_hit), (cres.tier, p_hit)
            if cres.hit.any():
                np.testing.assert_allclose(
                    cres.value[cres.hit], np.asarray(pres.value)[p_hit],
                    rtol=1e-6)
            miss = ~cres.hit
            if miss.any():
                keys = q[jnp.asarray(np.nonzero(miss)[0])]
                vals = jnp.asarray(payloads[ids[miss]])
                cluster.insert(node, keys, vals)
                pstate = pooled.insert(pstate, keys, vals)

    def test_no_share_cluster_misses_what_peers_hold(self):
        """Control: with the peer tier off, a key cached on another node is a
        miss — sharing is what buys the equivalence above."""
        rng = np.random.default_rng(0)
        d = 32
        keys = _unit(rng, 4, d)
        for share, want_hit in ((True, True), (False, False)):
            cl = CooperativeEdgeCluster(ClusterConfig(
                num_nodes=2, node_capacity=16, key_dim=d, payload_dim=4,
                threshold=0.9, share=share))
            cl.insert(1, jnp.asarray(keys),
                      jnp.ones((4, 4), jnp.float32))
            res = cl.lookup(0, jnp.asarray(keys))
            assert bool(res.hit.all()) == want_hit


# ---------------------------------------------------------------------------
# per-node eviction invariants under random interleaved insert/lookup
# ---------------------------------------------------------------------------


class _CacheMirror:
    """Pure-python mirror of SemanticCache's slot mechanics (no TTL)."""

    def __init__(self, capacity, policy):
        self.capacity = capacity
        self.policy = policy
        self.valid = [False] * capacity
        self.last_used = [0] * capacity
        self.inserted_at = [0] * capacity
        self.freq = [0] * capacity
        self.key_of = [None] * capacity
        self.clock = 0

    def _priority(self, i):
        if not self.valid[i]:
            return -1e30
        if self.policy == "lru":
            return float(self.last_used[i])
        if self.policy == "lfu":
            return self.freq[i] * 1e6 + float(self.last_used[i])
        if self.policy == "fifo":
            return float(self.inserted_at[i])
        raise ValueError(self.policy)

    def lookup(self, key_ids):
        hits = []
        for kid in key_ids:
            hit = kid in self.key_of
            if hit:
                i = self.key_of.index(kid)
                self.last_used[i] = max(self.last_used[i], self.clock)
                self.freq[i] += 1
            hits.append(hit)
        self.clock += 1
        return hits

    def insert(self, key_ids):
        # distinct victims: Q lowest-priority slots, ties to the lower index
        order = sorted(range(self.capacity),
                       key=lambda i: (self._priority(i), i))
        for kid, i in zip(key_ids, order):
            self.valid[i] = True
            self.key_of[i] = kid
            self.last_used[i] = self.clock
            self.inserted_at[i] = self.clock
            self.freq[i] = 1
        self.clock += 1

    def live_keys(self):
        return {k for i, k in enumerate(self.key_of) if self.valid[i]}

    def occupancy(self):
        return sum(self.valid)


@pytest.mark.parametrize("policy", ["lru", "lfu", "fifo"])
@pytest.mark.parametrize("seed", range(4))
def test_eviction_matches_python_mirror(policy, seed):
    """Random interleaved insert/lookup: the device cache's live-key set must
    track a python mirror of the policy exactly — capacity bound, victim
    choice, and LRU/LFU recency/frequency ordering included."""
    rng = np.random.default_rng(seed)
    capacity, d = 8, 32
    universe = _unit(rng, 24, d)
    cache = SemanticCache(capacity=capacity, key_dim=d, payload_dim=2,
                          threshold=0.99, policy=EvictionPolicy(policy))
    state = cache.init()
    mirror = _CacheMirror(capacity, policy)
    inserted = set()

    for _ in range(40):
        ids = rng.integers(0, 24, size=int(rng.integers(1, 4)))
        if rng.random() < 0.5 and inserted:
            # lookup a mix of known and unknown keys
            state, res = cache.lookup(state, jnp.asarray(universe[ids]))
            hits = mirror.lookup(list(ids))
            got = [bool(h) for h in np.asarray(res.hit)]
            assert got == hits, (got, hits)
        else:
            # batch insert with de-duplicated ids (a batch of distinct keys)
            ids = np.unique(ids)
            state = cache.insert(state, jnp.asarray(universe[ids]),
                                 jnp.zeros((len(ids), 2), jnp.float32))
            mirror.insert(list(ids))
            inserted.update(int(i) for i in ids)

        occ = int(np.asarray(state.valid).sum())
        assert occ <= capacity
        assert occ == mirror.occupancy()
        # membership check: every mirror-live key must hit, evicted must
        # miss.  The probe discards the returned state, so neither side's
        # clock/recency advances.
        probe = jnp.asarray(universe)
        _, res = cache.lookup(state, probe)            # throwaway state
        live = mirror.live_keys()
        for kid in range(24):
            assert bool(np.asarray(res.hit)[kid]) == (kid in live), (
                policy, seed, kid, live)


# ---------------------------------------------------------------------------
# cluster invariants under multi-node traffic (admission + peer touches on)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ["lru", "lfu"])
def test_cluster_node_invariants_under_interleaving(policy):
    rng = np.random.default_rng(7)
    n_nodes, d, p = 3, 32, 4
    cap = 8
    pool = _unit(rng, 40, d)
    payloads = rng.standard_normal((40, p)).astype(np.float32)
    cl = CooperativeEdgeCluster(ClusterConfig(
        num_nodes=n_nodes, node_capacity=cap, key_dim=d, payload_dim=p,
        threshold=0.9, policy=EvictionPolicy(policy), admission="always"))

    for step in range(60):
        node = int(rng.integers(n_nodes))
        ids = rng.integers(0, 40, size=4)
        res = cl.lookup(node, jnp.asarray(pool[ids]))
        miss = ~res.hit
        if miss.any():
            cl.insert(node, jnp.asarray(pool[ids[miss]]),
                      jnp.asarray(payloads[ids[miss]]))
        for s in cl.states:
            valid = np.asarray(s.valid)
            assert valid.sum() <= cap
            freq = np.asarray(s.freq)
            lu = np.asarray(s.last_used)
            clock = int(s.clock)
            assert (freq[valid] >= 1).all()            # live slots were used
            assert (lu <= clock).all()                 # recency bounded
        # peer-hit values always equal the ground-truth payload
        if res.hit.any():
            np.testing.assert_allclose(res.value[res.hit],
                                       payloads[ids[res.hit]], rtol=1e-5)


# ---------------------------------------------------------------------------
# engine integration: local -> peer -> cloud tiers
# ---------------------------------------------------------------------------


def test_coic_engine_cluster_tiers(tiny_model, nprng):
    from repro.core import CoICConfig, CoICEngine
    from repro.core.coic import recognition_cloud_fn

    model, params = tiny_model
    cloud = recognition_cloud_fn(model, params, num_classes=64)
    eng = CoICEngine(model, params,
                     CoICConfig(capacity=32, threshold=0.98, payload_dim=64,
                                num_nodes=3, admission="always"),
                     cloud_fn=cloud, miss_bucket=4)
    reqs = nprng.integers(0, model.cfg.vocab_size, size=(4, 32)).astype(np.int32)

    first = eng.process_batch(reqs, node_id=0)
    assert all(r.source == "cloud" for r in first)
    peer = eng.process_batch(reqs, node_id=1)
    assert all(r.source == "peer" for r in peer)
    local = eng.process_batch(reqs, node_id=1)         # admitted on node 1
    assert all(r.source == "edge" for r in local)
    for a, b in zip(first, peer):
        np.testing.assert_allclose(a.payload, b.payload, rtol=1e-5)
    # modeled network components (wall-clock lookup_ms excluded — jit
    # compile time would make total_ms ordering flaky): the peer tier pays
    # the LAN broadcast but never the WAN or cloud compute
    assert peer[0].coic.peer_net_ms > 0.0
    assert peer[0].coic.cloud_net_ms == 0.0 == peer[0].coic.cloud_compute_ms
    assert local[0].coic.peer_net_ms == 0.0
    assert first[0].coic.cloud_net_ms > peer[0].coic.peer_net_ms
    s = eng.stats()
    assert s["hits"] >= 8 and len(s["nodes"]) == 3


def test_benchmark_cooperative_strictly_beats_isolated():
    """The acceptance scenario: on the 4-node rotated-Zipf workload the
    cooperative cluster's global hit rate strictly exceeds isolated nodes,
    and the pooled cache upper-bounds both."""
    from benchmarks.cooperative_hit_rate import run

    rows = run(steps=30, pool=64, node_capacity=16)
    rates = {}
    lats = {}
    for name, _, derived in rows:
        parts = dict(kv.split("=") for kv in derived.split(";"))
        rates[name] = float(parts["hit_rate"])
        lats[name] = float(parts["mean_latency_ms"])
    assert rates["coop_cooperative"] > rates["coop_isolated"], rates
    assert rates["coop_pooled"] >= rates["coop_cooperative"], rates
    assert lats["coop_cooperative"] < lats["coop_isolated"], lats


def test_serving_engine_cluster_peer_hits(tiny_model, nprng):
    from repro.core.coic import CoICConfig
    from repro.serving.engine import ServingConfig, ServingEngine

    model, params = tiny_model
    cfg = ServingConfig(max_batch=4, max_len=64, max_new_tokens=4,
                        coic=CoICConfig(capacity=16, threshold=0.98,
                                        descriptor="sketch",
                                        num_nodes=2, admission="always"))
    eng = ServingEngine(model, params, cfg)
    prompt = nprng.integers(0, model.cfg.vocab_size, size=(16,)).astype(np.int32)

    eng.submit(prompt, node_id=0)
    eng.run_until_drained()
    assert eng.results[-1].source == "cloud"
    eng.submit(prompt, node_id=1)                      # peer shard holds it
    eng.run_until_drained()
    assert eng.results[-1].source == "peer"
    assert eng.results[-1].decode_steps == 0           # served from cache
    assert eng.results[-1].latency_s > 0.0             # modeled LAN cost
    assert eng.results[-1].breakdown.peer_net_ms > 0.0
    eng.submit(prompt, node_id=1)                      # admitted locally
    eng.run_until_drained()
    assert eng.results[-1].source == "edge"
    np.testing.assert_array_equal(eng.results[0].tokens, eng.results[1].tokens)
    assert eng.stats()["peer_hits"] == 1
