"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs the ref.py
pure-jnp oracle.  interpret mode executes the kernel body in Python on CPU,
validating BlockSpec indexing, online-softmax math and masking.

This file is the line of defense for every decode-path kernel: the ops.py
wrappers route ``impl="auto"`` to the jnp ref off-TPU, so CI never executes
a Pallas body through the serving path — only the explicit
``pallas_interpret`` cases here (and the engine-level ones in
test_kv_paged.py) do."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import (attention_kv_bytes_per_step,
                                           paged_attention,
                                           paged_attention_ref)
from repro.kernels.similarity import (similarity_lookup, similarity_topk_touch,
                                      similarity_topk_touch_ref)
from repro.serving.kv_cache import PagedKVCache


# f32 agreement of a kernel's similarity scores with its jnp oracle: a few
# ULPs of an O(1) dot product, whatever the accumulation order
IVF_SCORE_TOL = dict(rtol=1e-6, atol=1e-6)


def _unit(rng, *shape):
    x = rng.normal(size=shape).astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


class TestSimilarityKernel:
    @pytest.mark.parametrize("q,c,d", [(4, 32, 16), (128, 512, 64),
                                       (100, 1000, 48), (7, 513, 128),
                                       (1, 8, 256)])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_matches_ref(self, q, c, d, dtype, nprng):
        qs = _unit(nprng, q, d)
        ks = _unit(nprng, c, d)
        ks[min(5, c - 1)] = qs[0]                     # guaranteed exact hit
        valid = nprng.random(c) > 0.3
        valid[min(5, c - 1)] = True
        qd, kd = jnp.asarray(qs, dtype), jnp.asarray(ks, dtype)
        i_ref, s_ref = similarity_lookup(qd, kd, jnp.asarray(valid), impl="ref")
        i_pal, s_pal = similarity_lookup(qd, kd, jnp.asarray(valid),
                                         impl="pallas_interpret",
                                         block_q=32, block_c=64)
        s_ref, s_pal = np.asarray(s_ref), np.asarray(s_pal)
        finite = np.isfinite(s_ref) & (s_ref > -1e29)
        np.testing.assert_allclose(s_ref[finite], s_pal[finite],
                                   rtol=2e-2, atol=2e-2)
        # ties may resolve differently; verify score at chosen index instead
        sc = qs @ ks.T
        sc[:, ~valid] = -np.inf
        chosen = sc[np.arange(q), np.asarray(i_pal)]
        np.testing.assert_allclose(chosen[finite], s_pal[finite],
                                   rtol=2e-2, atol=2e-2)

    def test_all_invalid_returns_neginf(self):
        q = jnp.ones((4, 16), jnp.float32) / 4.0
        k = jnp.ones((32, 16), jnp.float32) / 4.0
        valid = jnp.zeros((32,), bool)
        _, s = similarity_lookup(q, k, valid, impl="pallas_interpret",
                                 block_q=4, block_c=8)
        assert np.all(np.asarray(s) < -1e29)


class TestFlashAttention:
    @pytest.mark.parametrize("b,s,h,k,d", [(1, 64, 4, 4, 16), (2, 128, 8, 2, 32),
                                           (1, 96, 4, 1, 64), (1, 64, 6, 3, 8)])
    @pytest.mark.parametrize("window", [0, 32])
    def test_matches_ref(self, b, s, h, k, d, window, nprng):
        q = nprng.normal(size=(b, s, h, d)).astype(np.float32)
        kk = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        v = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        o_ref = flash_attention(q, kk, v, causal=True, window=window, impl="ref")
        o_pal = flash_attention(q, kk, v, causal=True, window=window,
                                impl="pallas_interpret", block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                                   np.asarray(o_pal, np.float32),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16(self, nprng):
        b, s, h, k, d = 1, 64, 4, 2, 32
        q = jnp.asarray(nprng.normal(size=(b, s, h, d)), jnp.bfloat16)
        kk = jnp.asarray(nprng.normal(size=(b, s, k, d)), jnp.bfloat16)
        v = jnp.asarray(nprng.normal(size=(b, s, k, d)), jnp.bfloat16)
        o_ref = flash_attention(q, kk, v, impl="ref")
        o_pal = flash_attention(q, kk, v, impl="pallas_interpret",
                                block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                                   np.asarray(o_pal, np.float32),
                                   rtol=3e-2, atol=3e-2)


class TestDecodeAttention:
    @pytest.mark.parametrize("b,s,h,k,d", [(2, 64, 4, 4, 16), (3, 100, 8, 2, 32),
                                           (1, 128, 4, 1, 64)])
    def test_matches_ref(self, b, s, h, k, d, nprng):
        q = nprng.normal(size=(b, h, d)).astype(np.float32)
        kk = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        v = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        kv_len = np.array([min(s, 7 + 13 * i) for i in range(b)], np.int32)
        o_ref = decode_attention(q, kk, v, kv_len, impl="ref")
        o_pal = decode_attention(q, kk, v, kv_len, impl="pallas_interpret",
                                 block_kv=32)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                                   rtol=2e-3, atol=2e-3)

    def test_length_zero_safe(self, nprng):
        b, s, h, k, d = 1, 32, 2, 2, 8
        q = nprng.normal(size=(b, h, d)).astype(np.float32)
        kk = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        v = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        out = decode_attention(q, kk, v, np.zeros((b,), np.int32),
                               impl="pallas_interpret", block_kv=16)
        assert np.all(np.isfinite(np.asarray(out)))


def _paged_case(rng, *, B, page, n_pages, K, D, H=None, C=1, lengths=None,
                shared_pages=0, dtype=np.float32):
    """Build a pool + block tables the way PagedKVCache lays them out:
    rows map ceil(len / page) pages (first ``shared_pages`` of them shared
    across all rows — the prefix-index case), everything else INVALID."""
    H = H or K
    P = n_pages * B + 1                  # headroom: distinct pages per row
    if lengths is None:
        lengths = rng.integers(0, n_pages * page - C + 1, size=(B,))
    lengths = np.asarray(lengths, np.int32)
    kp = rng.normal(size=(P, K, page, D)).astype(dtype)
    vp = rng.normal(size=(P, K, page, D)).astype(dtype)
    bt = np.full((B, n_pages), PagedKVCache.INVALID, np.int32)
    nxt = shared_pages
    for b in range(B):
        used = -(-int(lengths[b] + C) // page)       # pages the row touches
        for j in range(min(used, n_pages)):
            if j < shared_pages:
                bt[b, j] = j
            else:
                bt[b, j] = nxt
                nxt += 1
    q = rng.normal(size=(B, C, H, D)).astype(dtype)
    return (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(bt), jnp.asarray(lengths))


class TestPagedAttention:
    """Fused in-place paged attention vs the gather-path oracle (ref.py
    replicates ``paged_gather_view`` + the model's fp32-softmax GQA bit for
    bit, so ref-vs-interpret closeness here transfers to the serving
    path)."""

    @pytest.mark.parametrize("B,page,n_pages,K,H,D,C", [
        (3, 16, 4, 2, 4, 16, 1),       # GQA decode
        (2, 8, 6, 4, 4, 32, 1),        # MHA decode, ragged
        (2, 16, 4, 2, 8, 16, 8),       # chunked prefill, 4 q heads/group
        (1, 32, 2, 1, 2, 64, 16),      # single row, wide chunk
    ])
    def test_matches_gather_oracle(self, B, page, n_pages, K, H, D, C, nprng):
        q, kp, vp, bt, ln = _paged_case(nprng, B=B, page=page,
                                        n_pages=n_pages, K=K, H=H, D=D, C=C)
        o_ref = paged_attention(q, kp, vp, bt, ln, impl="ref")
        o_pal = paged_attention(q, kp, vp, bt, ln, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                                   rtol=2e-3, atol=2e-3)

    def test_partial_last_page_and_page_boundary(self, nprng):
        """Rows sitting mid-page, exactly on a page boundary, and at 0."""
        q, kp, vp, bt, ln = _paged_case(nprng, B=4, page=16, n_pages=4, K=2,
                                        H=4, D=16, lengths=[5, 16, 32, 0])
        o_ref = paged_attention(q, kp, vp, bt, ln, impl="ref")
        o_pal = paged_attention(q, kp, vp, bt, ln, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                                   rtol=2e-3, atol=2e-3)

    def test_idle_all_invalid_row_is_finite(self, nprng):
        """An idle decode slot rides the dispatch with an all-INVALID table
        row; the kernel must finalize it to exact zeros, not NaN."""
        q, kp, vp, bt, ln = _paged_case(nprng, B=3, page=16, n_pages=3, K=2,
                                        H=4, D=16, lengths=[20, 0, 7])
        bt = bt.at[1].set(PagedKVCache.INVALID)
        out = np.asarray(paged_attention(q, kp, vp, bt, ln,
                                         impl="pallas_interpret"))
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[1], 0.0)

    def test_shared_prefix_pages(self, nprng):
        """Cross-user shared prefix pages: rows alias physical pages."""
        q, kp, vp, bt, ln = _paged_case(nprng, B=4, page=8, n_pages=6, K=2,
                                        H=4, D=16, shared_pages=2,
                                        lengths=[30, 22, 17, 40])
        assert np.array_equal(np.asarray(bt)[:, :2],
                              np.tile([[0, 1]], (4, 1)))
        o_ref = paged_attention(q, kp, vp, bt, ln, impl="ref")
        o_pal = paged_attention(q, kp, vp, bt, ln, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                                   rtol=2e-3, atol=2e-3)

    def test_bf16(self, nprng):
        q, kp, vp, bt, ln = _paged_case(nprng, B=2, page=16, n_pages=3, K=2,
                                        H=4, D=32, dtype=np.float32)
        q, kp, vp = (x.astype(jnp.bfloat16) for x in (q, kp, vp))
        o_ref = paged_attention(q, kp, vp, bt, ln, impl="ref")
        o_pal = paged_attention(q, kp, vp, bt, ln, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(o_ref, np.float32),
                                   np.asarray(o_pal, np.float32),
                                   rtol=3e-2, atol=3e-2)

    def test_auto_routes_to_ref_off_tpu(self, nprng):
        """CI has no TPU: auto must be the jnp oracle, bit for bit."""
        q, kp, vp, bt, ln = _paged_case(nprng, B=2, page=16, n_pages=3, K=2,
                                        H=4, D=16)
        if jax.default_backend() == "tpu":
            pytest.skip("auto routes to the real kernel on TPU")
        np.testing.assert_array_equal(
            np.asarray(paged_attention(q, kp, vp, bt, ln, impl="auto")),
            np.asarray(paged_attention(q, kp, vp, bt, ln, impl="ref")))

    def test_seeded_property_sweep(self, nprng):
        """Seeded stand-in for the hypothesis sweep: random ragged lengths,
        INVALID rows, shared prefixes, chunk widths."""
        for trial in range(8):
            B = int(nprng.integers(1, 5))
            page = int(nprng.choice([8, 16]))
            n_pages = int(nprng.integers(2, 6))
            K = int(nprng.choice([1, 2, 4]))
            H = K * int(nprng.choice([1, 2, 4]))
            C = int(nprng.choice([1, 1, 4, 8]))
            q, kp, vp, bt, ln = _paged_case(
                nprng, B=B, page=page, n_pages=n_pages, K=K, H=H, D=16, C=C,
                shared_pages=int(nprng.integers(0, 2)))
            o_ref = paged_attention(q, kp, vp, bt, ln, impl="ref")
            o_pal = paged_attention(q, kp, vp, bt, ln,
                                    impl="pallas_interpret")
            np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                                       rtol=2e-3, atol=2e-3,
                                       err_msg=f"trial {trial}")

    def test_hypothesis_sweep(self, nprng):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        @settings(max_examples=15, deadline=None)
        @given(B=st.integers(1, 4), page=st.sampled_from([8, 16]),
               n_pages=st.integers(2, 5), K=st.sampled_from([1, 2, 4]),
               G=st.sampled_from([1, 2, 4]), C=st.sampled_from([1, 4, 8]),
               seed=st.integers(0, 2**31 - 1))
        def check(B, page, n_pages, K, G, C, seed):
            rng = np.random.default_rng(seed)
            q, kp, vp, bt, ln = _paged_case(rng, B=B, page=page,
                                            n_pages=n_pages, K=K, H=K * G,
                                            D=16, C=C)
            o_ref = paged_attention(q, kp, vp, bt, ln, impl="ref")
            o_pal = paged_attention(q, kp, vp, bt, ln,
                                    impl="pallas_interpret")
            np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_pal),
                                       rtol=2e-3, atol=2e-3)

        check()

    def test_byte_model(self):
        """The benchmark/docs byte model: in-place strictly below gather for
        any non-empty batch, and exactly the mapped-page traffic."""
        kv = np.array([100, 0, 17, 512])
        kw = dict(page_size=16, max_len=512, kv_heads=8, head_dim=32,
                  dtype_bytes=4)
        g = attention_kv_bytes_per_step(kv, impl="gather", **kw)
        p = attention_kv_bytes_per_step(kv, impl="paged", **kw)
        mapped = sum(-(-int(x) // 16) * 16 for x in kv)
        row = 2 * 8 * 32 * 4
        assert p == mapped * row
        assert g == (mapped + 2 * 4 * 512) * row
        assert p < g
        with pytest.raises(ValueError):
            attention_kv_bytes_per_step(kv, impl="nope", **kw)


class TestFusedTopkTouch:
    """Fused top-k + LRU-touch epilogue vs the unfused oracle."""

    def _case(self, rng, Q, C, D):
        q = _unit(rng, Q, D)
        ks = _unit(rng, C, D)
        # exact hits incl. two queries hitting the SAME slot (multiplicity)
        ks[3] = q[0]
        ks[11 % C] = q[1]
        if Q > 2:
            q[2] = q[0]
        valid = rng.random(C) > 0.3
        valid[[3, 11 % C]] = True
        lu = rng.integers(0, 50, C).astype(np.int32)
        fr = rng.integers(0, 50, C).astype(np.int32)
        return (jnp.asarray(q), jnp.asarray(ks), jnp.asarray(valid),
                jnp.asarray(lu), jnp.asarray(fr), jnp.asarray(np.int32(99)))

    @pytest.mark.parametrize("Q,C,D,k", [(8, 64, 16, 4), (5, 100, 32, 1),
                                         (16, 48, 16, 8)])
    def test_matches_unfused_oracle(self, Q, C, D, k, nprng):
        q, ks, valid, lu, fr, clock = self._case(nprng, Q, C, D)
        r_ref = similarity_topk_touch(q, ks, valid, k, lu, fr, clock,
                                      threshold=0.98, impl="ref")
        r_pal = similarity_topk_touch(q, ks, valid, k, lu, fr, clock,
                                      threshold=0.98, impl="pallas_interpret",
                                      block_c=16)
        for a, b, name in zip(r_ref, r_pal, ("idx", "score", "lu", "fr")):
            if name == "score":
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=2e-2, atol=2e-2)
            else:
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=name)

    def test_mask_rows_never_touch(self, nprng):
        q, ks, valid, lu, fr, clock = self._case(nprng, 8, 64, 16)
        mask = jnp.asarray(np.array([1, 0, 1, 1, 0, 1, 1, 1], bool))
        for impl in ("ref", "pallas_interpret"):
            _, _, lu2, fr2 = similarity_topk_touch(
                q, ks, valid, 2, lu, fr, clock, threshold=0.98, mask=mask,
                impl=impl, block_c=16)
            # query 1's exact hit is masked: its slot must be untouched
            assert int(fr2[11 % 64]) == int(fr[11 % 64]), impl
            # query 0 and its duplicate query 2 both touch slot 3
            assert int(fr2[3]) == int(fr[3]) + 2, impl

    def test_touch_semantics_match_apply_probe(self, nprng):
        """End-to-end: SemanticCache with fuse_touch=True transitions state
        exactly like the unfused lookup + apply_probe path."""
        import dataclasses

        from repro.core.semantic_cache import SemanticCache

        C, D, P, Q = 48, 16, 4, 8
        base = SemanticCache(capacity=C, key_dim=D, payload_dim=P,
                             threshold=0.9)
        st0 = base.init()
        ks = _unit(nprng, C, D)
        st0 = base.insert(st0, jnp.asarray(ks[:30]),
                          jnp.asarray(nprng.normal(size=(30, P)),
                                      jnp.float32))
        q = _unit(nprng, Q, D)
        q[0] = ks[3]
        q[1] = ks[3]
        q[2] = ks[7]
        mask = np.ones(Q, bool)
        mask[5] = False
        for impl in ("ref", "pallas_interpret"):
            fused = dataclasses.replace(base, fuse_touch=True,
                                        lookup_impl=impl)
            s1, r1 = base.lookup(st0, jnp.asarray(q), mask=jnp.asarray(mask))
            s2, r2 = fused.lookup(st0, jnp.asarray(q), mask=jnp.asarray(mask))
            np.testing.assert_array_equal(np.asarray(r1.hit),
                                          np.asarray(r2.hit), err_msg=impl)
            np.testing.assert_array_equal(np.asarray(r1.value),
                                          np.asarray(r2.value), err_msg=impl)
            for f in ("last_used", "freq", "clock", "hits", "misses",
                      "valid"):
                np.testing.assert_array_equal(
                    np.asarray(getattr(s1, f)), np.asarray(getattr(s2, f)),
                    err_msg=f"{impl}:{f}")


class TestKernelVsModelAttention:
    def test_flash_equals_model_xla_path(self, nprng):
        """The kernel and the model's XLA attention implement the same op."""
        from repro.models import layers as L

        b, s, h, k, d = 2, 64, 4, 2, 16
        q = nprng.normal(size=(b, s, h, d)).astype(np.float32)
        kk = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        v = nprng.normal(size=(b, s, k, d)).astype(np.float32)
        pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        model_out = L.causal_attention(jnp.asarray(q), jnp.asarray(kk),
                                       jnp.asarray(v), pos, pos, causal=True)
        kern_out = flash_attention(q, kk, v, causal=True,
                                   impl="pallas_interpret",
                                   block_q=32, block_kv=32)
        np.testing.assert_allclose(np.asarray(model_out), np.asarray(kern_out),
                                   rtol=2e-3, atol=2e-3)


class TestIVFPQ:
    """Two-stage IVF-PQ digest probe: the Pallas body (interpret=True) must
    pick EXACTLY the oracle's probed lists and candidate indices
    (``assert_array_equal``): the decode is a one-hot matmul (copies
    codebook entries exactly) and the merge replays ``lax.top_k`` tie
    order.  Scores agree to ``IVF_SCORE_TOL``, a few f32 ULPs: the kernel's
    per-list matmul accumulates the D-long dot product in another order
    than the oracle's single (Q, L*cap) matmul, in interpret mode and on
    the MXU alike.  A row with fewer than k candidates pads with NEG_INF
    scores whose indices are unspecified (the kernel never visits lists
    no query of the tile probed), so index equality covers the real
    candidates, as in the similarity kernels' tests."""

    @staticmethod
    def _assert_matches(ref, pal):
        (i_ref, s_ref, sel_ref), (i_pal, s_pal, sel_pal) = (
            [np.asarray(a) for a in r] for r in (ref, pal))
        np.testing.assert_array_equal(sel_ref, sel_pal, err_msg="sel")
        np.testing.assert_allclose(s_pal, s_ref, err_msg="score",
                                   **IVF_SCORE_TOL)
        real = s_ref > -1e29
        np.testing.assert_array_equal(i_ref[real], i_pal[real],
                                      err_msg="idx")

    @staticmethod
    def _index(rng, L, cap, S, D, K):
        """Random packed index in the core/digest.py layout: some dead
        lists, ~30% tombstoned slots, owners spread over K clusters."""
        centroids = _unit(rng, L, D)
        cent_valid = rng.random(L) > 0.15
        cent_valid[: max(2, L // 4)] = True           # enough live lists
        codes = rng.integers(0, 256, size=(L, cap, S)).astype(np.uint8)
        slot_valid = rng.random((L, cap)) > 0.3
        slot_owner = rng.integers(0, K, size=(L, cap)).astype(np.int32)
        slot_valid &= cent_valid[:, None]             # dead list => dead slots
        codebook = (rng.standard_normal((S, 256, D // S)) * 0.05).astype(
            np.float32)
        return tuple(jnp.asarray(a) for a in
                     (centroids, cent_valid, codes, slot_valid, slot_owner,
                      codebook))

    @pytest.mark.parametrize("Q,L,cap,S,D,n_probe,k",
                             [(8, 8, 4, 2, 16, 3, 1), (16, 16, 8, 4, 32, 4, 4),
                              (8, 12, 6, 4, 16, 12, 2), (24, 9, 5, 8, 64, 1, 3),
                              (8, 4, 1100, 4, 16, 2, 4)])   # lists in tiles
    def test_kernel_bit_exact_vs_oracle(self, Q, L, cap, S, D, n_probe, k,
                                        nprng):
        from repro.kernels.ivf_pq.kernel import ivf_pq_probe_kernel
        from repro.kernels.ivf_pq.ref import ivf_pq_probe_ref

        K = 3
        idxarrs = self._index(nprng, L, cap, S, D, K)
        q = jnp.asarray(_unit(nprng, Q, D))
        home = jnp.asarray(nprng.integers(0, K, size=Q).astype(np.int32))
        self._assert_matches(
            ivf_pq_probe_ref(q, home, *idxarrs, k=k, n_probe=n_probe),
            ivf_pq_probe_kernel(q, home, *idxarrs, k=k, n_probe=n_probe,
                                interpret=True))

    def test_ops_pads_ragged_query_tile(self, nprng):
        """Public wrapper pads Q to a multiple of 8 (padded rows home=-1)
        and slices the outputs back — same indices as the ref impl."""
        from repro.kernels.ivf_pq import ivf_pq_probe

        idxarrs = self._index(nprng, 8, 4, 2, 16, 2)
        q = jnp.asarray(_unit(nprng, 5, 16))          # 5 % 8 != 0
        home = jnp.asarray(np.array([0, 1, 0, 1, 0], np.int32))
        i_ref, s_ref = ivf_pq_probe(q, home, *idxarrs, k=2, n_probe=3,
                                    impl="ref")
        i_pal, s_pal = ivf_pq_probe(q, home, *idxarrs, k=2, n_probe=3,
                                    impl="pallas_interpret")
        sel = np.zeros((5, 3), np.int32)              # not returned here
        self._assert_matches((i_ref, s_ref, sel), (i_pal, s_pal, sel))

    def test_auto_routes_to_ref_off_tpu(self, nprng):
        """CI has no TPU: auto must be the jnp oracle, bit for bit."""
        from repro.kernels.ivf_pq import ivf_pq_probe

        if jax.default_backend() == "tpu":
            pytest.skip("auto routes to the real kernel on TPU")
        idxarrs = self._index(nprng, 8, 4, 2, 16, 2)
        q = jnp.asarray(_unit(nprng, 8, 16))
        home = jnp.zeros(8, jnp.int32)
        for a, b in zip(ivf_pq_probe(q, home, *idxarrs, k=2, n_probe=4,
                                     impl="auto"),
                        ivf_pq_probe(q, home, *idxarrs, k=2, n_probe=4,
                                     impl="ref")):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_home_cluster_rows_never_match(self, nprng):
        """A probe must exclude its own cluster's advertised rows: with
        every slot owned by cluster 0, a home=0 query gets only NEG_INF
        sentinels while a home=1 query scores live slots."""
        from repro.kernels.ivf_pq import ivf_pq_probe

        centroids, cent_valid, codes, slot_valid, _, codebook = self._index(
            nprng, 8, 4, 2, 16, 2)
        owner0 = jnp.zeros((8, 4), jnp.int32)
        q = jnp.asarray(_unit(nprng, 8, 16))
        for impl in ("ref", "pallas_interpret"):
            _, s_home = ivf_pq_probe(q, jnp.zeros(8, jnp.int32), centroids,
                                     cent_valid, codes, slot_valid, owner0,
                                     codebook, k=1, n_probe=8, impl=impl)
            _, s_away = ivf_pq_probe(q, jnp.ones(8, jnp.int32), centroids,
                                     cent_valid, codes, slot_valid, owner0,
                                     codebook, k=1, n_probe=8, impl=impl)
            assert (np.asarray(s_home) < -1e29).all(), impl
            assert (np.asarray(s_away) > -1e29).any(), impl

    def test_hits_come_only_from_probed_lists(self, nprng):
        """With n_probe=1 every returned candidate's list (idx // cap) is
        the query's single selected list — stage 2 never leaks unprobed
        rows into the top-k."""
        from repro.kernels.ivf_pq.ref import ivf_pq_probe_ref

        L, cap = 12, 6
        idxarrs = self._index(nprng, L, cap, 4, 16, 3)
        q = jnp.asarray(_unit(nprng, 16, 16))
        home = jnp.asarray(nprng.integers(0, 3, size=16).astype(np.int32))
        idx, score, sel = ivf_pq_probe_ref(q, home, *idxarrs, k=3, n_probe=1)
        idx, score, sel = (np.asarray(a) for a in (idx, score, sel))
        real = score > -1e29
        assert (idx[real.all(axis=1)].min(initial=0) >= 0)
        lists = idx // cap
        assert (lists[real] == sel[:, 0][:, None].repeat(3, 1)[real]).all()

    def test_decode_is_exact_codebook_gather(self, nprng):
        """onehot(codes) @ codebook copies entries bitwise — the property
        the kernel/oracle bit-exactness rests on."""
        from repro.kernels.ivf_pq.ref import decode_pq_codes

        S, dsub = 4, 8
        cb = nprng.standard_normal((S, 256, dsub)).astype(np.float32)
        codes = nprng.integers(0, 256, size=(10, S))
        dec = np.asarray(decode_pq_codes(jnp.asarray(cb),
                                         jnp.asarray(codes.astype(np.int32))))
        want = np.concatenate([cb[s][codes[:, s]] for s in range(S)], axis=1)
        np.testing.assert_array_equal(dec, want)

    def test_hypothesis_sweep(self, nprng):
        pytest.importorskip("hypothesis")
        from hypothesis import given, settings, strategies as st

        from repro.kernels.ivf_pq.kernel import ivf_pq_probe_kernel
        from repro.kernels.ivf_pq.ref import ivf_pq_probe_ref

        @settings(max_examples=15, deadline=None)
        @given(Q=st.sampled_from([8, 16]), L=st.integers(4, 12),
               cap=st.integers(2, 8), S=st.sampled_from([2, 4]),
               n_probe=st.integers(1, 4), k=st.integers(1, 3),
               seed=st.integers(0, 2**31 - 1))
        def check(Q, L, cap, S, n_probe, k, seed):
            rng = np.random.default_rng(seed)
            n_probe = min(n_probe, L)
            idxarrs = self._index(rng, L, cap, S, 16, 3)
            q = jnp.asarray(_unit(rng, Q, 16))
            home = jnp.asarray(rng.integers(0, 3, size=Q).astype(np.int32))
            self._assert_matches(
                ivf_pq_probe_ref(q, home, *idxarrs, k=k, n_probe=n_probe),
                ivf_pq_probe_kernel(q, home, *idxarrs, k=k, n_probe=n_probe,
                                    interpret=True))

        check()

    def test_byte_model(self):
        """ivf_pq scan traffic is n_sub+2 bytes/slot vs D+4 for the brute
        int8 board row; at region scale (1M rows) the model shows >=4x."""
        from repro.obs.profile import digest_probe_bytes, ivf_pq_probe_bytes

        rows, L, S, D, nq, K = 1_000_000, 1024, 8, 64, 64, 4
        ivf = ivf_pq_probe_bytes(nq, L, -(-rows // L), S, D)
        brute = digest_probe_bytes(nq // K, K, rows // K, D, "int8")
        assert ivf > 0
        assert brute / ivf >= 4.0, (brute, ivf)
