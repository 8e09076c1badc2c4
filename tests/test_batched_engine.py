"""Batched cross-node request scheduling: batched-kernel bit-exactness,
grouped-ladder equivalence vs the per-node ladder, and the engine-level
property that a batched step produces the same results as N sequential
submits (rotated-Zipf workload, seeded, both submission orders)."""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.cluster import (TIER_LOCAL, TIER_PEER, ClusterConfig,
                                CooperativeEdgeCluster)
from repro.data.workload import ZipfWorkload
from repro.kernels.similarity import (similarity_topk_batched,
                                      similarity_topk_batched_ref)


# a few f32 ULPs of a unit-norm dot product
SCORE_ATOL = 1e-6


def _unit(rng, *shape):
    x = rng.standard_normal(shape).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


# ---------------------------------------------------------------------------
# batched kernel vs the vmapped jnp oracle (exact indices, tie-breaks
# included; scores to SCORE_ATOL)
# ---------------------------------------------------------------------------


class TestBatchedTopK:
    @pytest.mark.parametrize("n,q,c,d,k", [(4, 8, 64, 32, 4), (3, 7, 33, 16, 3),
                                           (1, 1, 8, 8, 8), (2, 100, 513, 48, 5),
                                           (6, 16, 128, 128, 1)])
    def test_batched_kernel_matches_vmapped_oracle(self, n, q, c, d, k, nprng):
        qs, ks = _unit(nprng, n, q, d), _unit(nprng, n, c, d)
        ks[0, min(3, c - 1)] = qs[0, 0]               # guaranteed exact hit
        valid = nprng.random((n, c)) > 0.3
        valid[0, min(3, c - 1)] = True
        ri, rs = similarity_topk_batched_ref(jnp.asarray(qs), jnp.asarray(ks),
                                             jnp.asarray(valid), k)
        pi, ps = similarity_topk_batched(jnp.asarray(qs), jnp.asarray(ks),
                                         jnp.asarray(valid), k,
                                         impl="pallas_interpret",
                                         block_q=32, block_c=64)
        # scores: within a few f32 ULPs of a unit-norm dot product (the
        # kernel's tiled matmul accumulates in another order than the
        # oracle's einsum, in interpret mode and on the MXU alike);
        # indices: exact
        np.testing.assert_allclose(np.asarray(ps), np.asarray(rs),
                                   rtol=0, atol=SCORE_ATOL)
        real = np.asarray(rs) > -1e29
        assert np.array_equal(np.asarray(ri)[real], np.asarray(pi)[real])

    def test_batch_entries_probe_their_own_keys(self, nprng):
        """Entry n must score against key matrix n only: planting entry 0's
        query among entry 1's keys must not leak into entry 0's result."""
        d = 16
        qs = _unit(nprng, 2, 1, d)
        ks = _unit(nprng, 2, 8, d)
        ks[1, 3] = qs[0, 0]                           # wrong batch entry
        valid = np.ones((2, 8), bool)
        _, s = similarity_topk_batched(jnp.asarray(qs), jnp.asarray(ks),
                                       jnp.asarray(valid), 1,
                                       impl="pallas_interpret",
                                       block_q=8, block_c=8)
        assert float(s[0, 0, 0]) < 0.999              # no cross-batch leak
        _, s1 = similarity_topk_batched(jnp.asarray(qs[:1]),
                                        jnp.asarray(ks[1:]),
                                        jnp.asarray(valid[:1]), 1,
                                        impl="pallas_interpret",
                                        block_q=8, block_c=8)
        assert float(s1[0, 0, 0]) > 0.999             # right entry does hit

    def test_duplicate_scores_tiebreak_to_lowest_index(self):
        d = 16
        rng = np.random.default_rng(0)
        key = _unit(rng, 1, d)[0]
        keys = np.tile(key, (2, 6, 1)).astype(np.float32)
        valid = np.ones((2, 6), bool)
        qs = np.tile(key, (2, 1, 1)).astype(np.float32)
        i, _ = similarity_topk_batched(jnp.asarray(qs), jnp.asarray(keys),
                                       jnp.asarray(valid), 4,
                                       impl="pallas_interpret",
                                       block_q=8, block_c=8)
        for n in range(2):
            assert np.array_equal(np.asarray(i)[n, 0], np.arange(4))


# ---------------------------------------------------------------------------
# grouped ladder == per-node ladder on identical starting state
# ---------------------------------------------------------------------------


class TestGroupedClusterLookup:
    @pytest.mark.parametrize("admission", ["never", "always", "second_hit"])
    def test_grouped_matches_per_node_lookup(self, admission):
        """One lookup_grouped call over (N, B, D) must reproduce N
        ``lookup(node, ...)`` calls bit-for-bit: hit, tier, owner, and
        payload values (given identical pre-call cache state)."""
        rng = np.random.default_rng(3)
        n, d, p, cap = 4, 32, 4, 64
        pool = _unit(rng, 24, d)
        pay = rng.standard_normal((24, p)).astype(np.float32)

        def mk():
            return CooperativeEdgeCluster(ClusterConfig(
                num_nodes=n, node_capacity=cap, key_dim=d, payload_dim=p,
                threshold=0.8, admission=admission))

        cl_g, cl_s = mk(), mk()
        for g in range(n):
            ids = rng.integers(0, 24, size=5)
            for cl in (cl_g, cl_s):
                cl.insert(g, jnp.asarray(pool[ids]), jnp.asarray(pay[ids]))

        B = 6
        qids = rng.integers(0, 24, size=(n, B))
        queries = pool[qids]
        res_g = cl_g.lookup_grouped(jnp.asarray(queries))
        for g in range(n):
            res_s = cl_s.lookup(g, jnp.asarray(queries[g]))
            assert np.array_equal(res_g.hit[g], res_s.hit)
            assert np.array_equal(res_g.tier[g], res_s.tier)
            assert np.array_equal(res_g.owner[g], res_s.owner)
            np.testing.assert_array_equal(res_g.value[g][res_g.hit[g]],
                                          res_s.value[res_s.hit])
        assert (res_g.tier == TIER_PEER).any()        # the peer rung fired

    def test_grouped_mask_rows_leave_no_trace(self):
        rng = np.random.default_rng(1)
        n, d, p = 2, 16, 2
        pool = _unit(rng, 8, d)
        cl = CooperativeEdgeCluster(ClusterConfig(
            num_nodes=n, node_capacity=16, key_dim=d, payload_dim=p,
            threshold=0.9))
        cl.insert(0, jnp.asarray(pool[:4]), jnp.zeros((4, p), jnp.float32))
        queries = np.zeros((n, 4, d), np.float32)
        queries[0, 0] = pool[0]
        mask = np.zeros((n, 4), bool)
        mask[0, 0] = True
        res = cl.lookup_grouped(jnp.asarray(queries), mask)
        assert bool(res.hit[0, 0]) and not res.hit[~mask].any()
        s = cl.stats()
        assert s["hits"] == 1 and s["misses"] == 0    # pad rows uncounted

    def test_grouped_serves_probe_snapshot_under_eviction(self):
        """Regression: an earlier group's peer admission can evict/overwrite
        an owner slot a later group's probe result points into; the later
        group must be served the PROBED entry's payload, not whatever the
        admission wrote over it."""
        rng = np.random.default_rng(5)
        d, p = 32, 4
        e0, e1 = _unit(rng, 2, d)
        pay0 = np.full((1, p), 7.0, np.float32)
        pay1 = np.full((1, p), 9.0, np.float32)
        cl = CooperativeEdgeCluster(ClusterConfig(
            num_nodes=3, node_capacity=1, key_dim=d, payload_dim=p,
            threshold=0.9, admission="always"))
        cl.insert(0, jnp.asarray(e0[None]), jnp.asarray(pay0))  # node 0: E0
        cl.insert(1, jnp.asarray(e1[None]), jnp.asarray(pay1))  # node 1: E1

        # group 0 requests E1 (peer hit on node 1 -> admitted into node 0,
        # evicting E0 from its only slot); group 2 requests E0, whose
        # probe-time top-1 is node 0's now-overwritten slot
        queries = np.zeros((3, 1, d), np.float32)
        queries[0, 0] = e1
        queries[2, 0] = e0
        mask = np.array([[True], [False], [True]])
        res = cl.lookup_grouped(jnp.asarray(queries), mask)
        assert bool(res.hit[0, 0]) and res.tier[0, 0] == TIER_PEER
        assert bool(res.hit[2, 0]) and res.tier[2, 0] == TIER_PEER
        np.testing.assert_array_equal(res.value[0, 0], pay1[0])
        np.testing.assert_array_equal(res.value[2, 0], pay0[0])  # not pay1

    def test_second_hit_admission_defers_replication(self):
        """admission="second_hit": the first peer hit is served remotely
        (no local copy), the second replicates it to the requesting node."""
        rng = np.random.default_rng(0)
        d, p = 32, 4
        keys = _unit(rng, 4, d)
        cl = CooperativeEdgeCluster(ClusterConfig(
            num_nodes=2, node_capacity=16, key_dim=d, payload_dim=p,
            threshold=0.9, admission="second_hit"))
        cl.insert(1, jnp.asarray(keys), jnp.ones((4, p), jnp.float32))

        r1 = cl.lookup(0, jnp.asarray(keys[:1]))
        assert r1.tier[0] == TIER_PEER and cl.peer_fills[0] == 0
        r2 = cl.lookup(0, jnp.asarray(keys[:1]))
        assert r2.tier[0] == TIER_PEER and cl.peer_fills[0] == 1
        r3 = cl.lookup(0, jnp.asarray(keys[:1]))
        assert r3.tier[0] == TIER_LOCAL               # now cached locally


# ---------------------------------------------------------------------------
# engine property: batched step == N sequential submits (rotated Zipf)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def fp32_model():
    # fp32: bf16 near-ties can flip argmax between bucketed batch widths
    # (different reduction order), which is numerics, not scheduling
    from repro.configs import get_config
    from repro.models import build_model

    cfg = dataclasses.replace(get_config("coic-paper"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


NODES, USERS, ROUNDS, POOL, PLEN, MAXNEW = 3, 4, 4, 10, 12, 16


def _drive(model, params, vocab, scheduling, admission, order, seed=0):
    """Submit the rotated-Zipf stream round by round (round size <= max_new
    so no request's lookup can see an intra-round retire-insert in either
    mode) and drain.  Returns (engine, {req_id: (source, tokens)})."""
    from repro.core.coic import CoICConfig
    from repro.serving.engine import ServingConfig, ServingEngine

    wl = ZipfWorkload(num_nodes=NODES, pool_size=POOL, seed=seed)
    prompts = wl.token_prompts(vocab, PLEN)
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=16, max_len=PLEN + MAXNEW + 8, max_new_tokens=MAXNEW,
        scheduling=scheduling,
        coic=CoICConfig(capacity=64, threshold=0.98, descriptor="sketch",
                        descriptor_dim=128, num_nodes=NODES,
                        admission=admission)))
    served = {}
    for round_ in wl.stream_ids(ROUNDS, USERS, seed=seed + 1):
        subs = [(node, i) for node, ids in round_ for i in ids]
        if order == "reversed":
            subs = subs[::-1]
        rid_of = {}
        for node, i in subs:
            rid_of[eng.submit(prompts[i], node_id=node)] = i
        eng.run_until_drained()
        for r in eng.results[len(served):]:
            served[r.req_id] = (rid_of[r.req_id], r.source,
                                tuple(int(t) for t in r.tokens))
    return eng, served


def _membership(eng):
    """Per-node sets of cached descriptor rows, order-independent."""
    out = []
    for s in eng.sem_cluster.states:
        valid = np.asarray(s.valid)
        keys = np.asarray(s.keys)[valid]
        out.append(keys[np.lexsort(keys.T)] if len(keys) else keys)
    return out


@pytest.mark.parametrize("order", ["forward", "reversed"])
def test_batched_step_equals_sequential_submits(fp32_model, order):
    """The acceptance property: over a seeded rotated-Zipf multi-node
    workload, the batched engine (one descriptor dispatch + one grouped
    cluster lookup per step) must produce the same per-request sources,
    tokens, hit/miss decisions, and final cache contents as the sequential
    engine (one ladder per request).  admission="never" keeps within-step
    peer-admission interleaving out of play; the admission="always" variant
    below covers it."""
    cfg, model, params = fp32_model
    eng_b, res_b = _drive(model, params, cfg.vocab_size, "batched",
                          "never", order)
    eng_s, res_s = _drive(model, params, cfg.vocab_size, "sequential",
                          "never", order)
    assert res_b == res_s                             # scene, source, tokens
    assert {s for _, s, _ in res_b.values()} >= {"edge", "peer", "cloud"}

    mb, ms = _membership(eng_b), _membership(eng_s)
    for kb, ks in zip(mb, ms):
        np.testing.assert_array_equal(kb, ks)
    sb, ss = eng_b.sem_cluster.stats(), eng_s.sem_cluster.stats()
    for key in ("hits", "misses", "occupancy"):
        assert sb[key] == ss[key], (key, sb[key], ss[key])
    # the batching win: both engines did identical work with wildly
    # different dispatch counts
    n_req = len(res_b)
    assert eng_s.dispatches["lookup"] == n_req
    assert eng_b.dispatches["lookup"] <= ROUNDS + 1


def test_batched_equals_sequential_with_admission(fp32_model):
    """admission="always": a peer hit admitted mid-stream can upgrade a
    later same-node duplicate from "peer" to "edge" in the sequential
    order, so tiers may differ — but which requests are cache-served, the
    tokens they get, and the final cache contents must still agree
    (grouped admission de-duplicates within the step)."""
    cfg, model, params = fp32_model
    eng_b, res_b = _drive(model, params, cfg.vocab_size, "batched",
                          "always", "forward")
    eng_s, res_s = _drive(model, params, cfg.vocab_size, "sequential",
                          "always", "forward")
    assert res_b.keys() == res_s.keys()
    for rid in res_b:
        scene_b, src_b, toks_b = res_b[rid]
        scene_s, src_s, toks_s = res_s[rid]
        assert scene_b == scene_s and toks_b == toks_s
        assert (src_b == "cloud") == (src_s == "cloud"), (rid, src_b, src_s)
    for kb, ks in zip(_membership(eng_b), _membership(eng_s)):
        np.testing.assert_array_equal(kb, ks)


def test_one_lookup_ladder_per_engine_step(fp32_model):
    """Dispatch-counter acceptance: 4 nodes x 64 concurrent users drain
    through ONE descriptor extraction and ONE cluster lookup per engine
    step (the sequential path pays one of each per request)."""
    from repro.core.coic import CoICConfig
    from repro.serving.engine import ServingConfig, ServingEngine

    cfg, model, params = fp32_model
    nodes, users = 4, 64
    wl = ZipfWorkload(num_nodes=nodes, pool_size=32, seed=2)
    prompts = wl.token_prompts(cfg.vocab_size, PLEN)
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=16, max_len=PLEN + 8, max_new_tokens=4,
        scheduling="batched",
        coic=CoICConfig(capacity=64, threshold=0.98, descriptor="sketch",
                        descriptor_dim=128, num_nodes=nodes)))
    for node, ids in next(iter(wl.stream_ids(1, users, seed=3))):
        for i in ids:
            eng.submit(prompts[i], node_id=node)
    eng.step()
    assert eng.dispatches["descriptor"] == 1
    assert eng.dispatches["lookup"] == 1
    assert eng.dispatches["prefill"] == 1
    assert not eng.pending                            # all 256 drained
    # cluster-level: one local probe + at most one peer probe
    assert eng.sem_cluster.probe_dispatches <= 2


@pytest.mark.slow
def test_batched_scheduling_throughput_speedup():
    """The benchmark acceptance: >= 2x submit-to-result throughput at
    4 nodes x 64 concurrent users (observed ~45x on this host)."""
    from benchmarks.cooperative_hit_rate import run_batched

    rows = {name: derived for name, _, derived in run_batched(rounds=3)}
    speedup = float(rows["coop_sched_speedup"].split("=")[1].rstrip("x"))
    assert speedup >= 2.0, rows
