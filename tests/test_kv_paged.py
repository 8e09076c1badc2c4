"""Paged KV cache: block tables, prefix sharing, tail-chunk compile reuse.

Covers the three contracts of the paged subsystem:

  * tail-chunk retrace fix — every dense chunk dispatch is the static
    (1, prefill_chunk) shape with the true width passed as data, so ONE
    ``prefill_chunk`` compile serves every remainder length, and chunked
    tokens stay bit-identical to one-shot prefill
  * paged + prefix-shared serving is bit-identical to the dense slotted
    path over random shared-prefix batches (mapped pages hold exactly the
    bytes prefill would have written), with refcounts draining to zero
    once the engine drains — property-tested via hypothesis when
    installed, with a seeded fallback that always runs
  * host-side bookkeeping units — copy-on-write remapping, scatter
    duplicate-slot rejection, free-list recycling of index entries
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.data.workload import SharedPrefixWorkload
from repro.models import build_model
from repro.serving.engine import ServingConfig, ServingEngine
from repro.serving.kv_cache import PagedKVCache, batch_cache_scatter


@pytest.fixture(scope="module")
def fp32_model():
    # fp32: bf16 near-ties can flip argmax between batch widths
    cfg = dataclasses.replace(get_config("coic-paper"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _serve(model, params, prompts, **kw):
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=kw.pop("max_batch", 4), max_len=kw.pop("max_len", 96),
        max_new_tokens=kw.pop("max_new", 6), **kw))
    rids = [eng.submit(p) for p in prompts]
    eng.run_until_drained()
    by = {r.req_id: r for r in eng.results}
    return eng, [by[rid].tokens for rid in rids]


def _shared_prefix_prompts(rng, vocab, n, prefix_lens=(33, 17),
                           suffix=(3, 20)):
    """Prompts drawn over a few shared heads + random private tails."""
    heads = [rng.integers(0, vocab, size=(L,)).astype(np.int32)
             for L in prefix_lens]
    out = []
    for i in range(n):
        sfx = rng.integers(0, vocab,
                           size=(int(rng.integers(*suffix)),)).astype(np.int32)
        out.append(np.concatenate([heads[i % len(heads)], sfx]))
    return out


# ---------------------------------------------------------------------------
# tail-chunk retrace fix
# ---------------------------------------------------------------------------


def test_tail_chunk_one_compile_across_remainders(fp32_model, nprng):
    """THE regression this PR pins: with prefill_chunk=16, prompts whose
    lengths leave >= 3 distinct tail remainders must share ONE
    ``prefill_chunk`` compile (the old code dispatched the raw remainder
    width, retracing per distinct length), and chunked tokens must equal
    the one-shot prefill path bit for bit."""
    cfg, model, params = fp32_model
    # remainders mod 16: 5, 3, 2, 0 — three distinct partial tails
    prompts = [nprng.integers(0, cfg.vocab_size, size=(L,)).astype(np.int32)
               for L in (21, 35, 50, 48)]
    eng_c, toks_c = _serve(model, params, prompts, prefill_chunk=16,
                           max_batch=2)
    assert eng_c.dispatches["prefill_chunk"] >= 8      # chunk path taken
    assert eng_c._chunk_fn._cache_size() == 1, \
        "tail chunks retraced: expected ONE compile for all remainders"
    eng_o, toks_o = _serve(model, params, prompts, max_batch=2)
    for c, o in zip(toks_c, toks_o):
        np.testing.assert_array_equal(c, o)


# ---------------------------------------------------------------------------
# paged == dense (seeded fallback property + hypothesis widening)
# ---------------------------------------------------------------------------


def _assert_paged_matches_dense(model, params, prompts, expect_share=True):
    """``expect_share``: some prompt is admitted after an earlier one with
    the same head has registered its pages (more prompts than the 4 slots;
    prompts admitted in one step all compute their heads)."""
    eng_d, toks_d = _serve(model, params, prompts)
    eng_p, toks_p = _serve(model, params, prompts, kv_page=16,
                           prefill_chunk=32)
    eng_n, toks_n = _serve(model, params, prompts, kv_page=16,
                           prefill_chunk=32, prefix_share=False)
    for d, p, n in zip(toks_d, toks_p, toks_n):
        np.testing.assert_array_equal(d, p)
        np.testing.assert_array_equal(d, n)
    # refcounts return to zero with the engine drained; every table slot
    # unmapped; sharing actually happened (same heads repeat)
    for eng in (eng_p, eng_n):
        assert (eng.kv.refcount == 0).all()
        assert (eng.kv.block_table == PagedKVCache.INVALID).all()
        assert eng.stats()["kv"]["pages_in_use"] == 0
    assert eng_n.prefill_tokens_shared == 0
    if expect_share:
        assert eng_p.prefill_tokens_shared > 0
        assert eng_p.prefill_tokens_computed < eng_n.prefill_tokens_computed
    else:
        assert (eng_p.prefill_tokens_computed
                == eng_n.prefill_tokens_computed)
    return eng_p


def test_paged_prefix_sharing_bit_identical_seeded(fp32_model, nprng):
    """Seeded fallback (always runs): random shared-prefix batches decode
    the same tokens through the paged + prefix-shared path as through the
    dense slotted path, and sharing elides prefill compute."""
    cfg, model, params = fp32_model
    prompts = _shared_prefix_prompts(nprng, cfg.vocab_size, 7)
    eng = _assert_paged_matches_dense(model, params, prompts)
    assert eng.stats()["kv"]["pages_shared"] > 0


def test_paged_prefix_sharing_bit_identical_hypothesis(fp32_model):
    """Hypothesis widening of the same property: random suffix lengths,
    session mixes, and request counts."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, model, params = fp32_model

    @settings(max_examples=5, deadline=None)
    @given(st.integers(0, 2 ** 31 - 1),
           st.integers(3, 8),
           st.lists(st.integers(2, 24), min_size=1, max_size=3))
    def prop(seed, n_req, prefix_extra):
        rng = np.random.default_rng(seed)
        prompts = _shared_prefix_prompts(
            rng, cfg.vocab_size, n_req,
            prefix_lens=tuple(16 + e for e in prefix_extra))
        _assert_paged_matches_dense(model, params, prompts,
                                    expect_share=n_req > 4)

    prop()


# ---------------------------------------------------------------------------
# in-place paged-attention kernel through the serving path
# ---------------------------------------------------------------------------


def test_paged_kernel_attn_bit_identical_tokens(fp32_model, nprng):
    """attn_impl="paged_interpret" (the fused in-place kernel, interpreted)
    decodes the same tokens as the gathered-view path AND the dense slotted
    path over a shared-prefix batch — prefill chunks, decode steps, idle
    rows, and shared pages all routed through the kernel."""
    cfg, model, params = fp32_model
    prompts = _shared_prefix_prompts(nprng, cfg.vocab_size, 6)
    eng_d, toks_d = _serve(model, params, prompts)
    eng_g, toks_g = _serve(model, params, prompts, kv_page=16,
                           prefill_chunk=32)
    eng_k, toks_k = _serve(model, params, prompts, kv_page=16,
                           prefill_chunk=32, attn_impl="paged_interpret")
    for d, g, k in zip(toks_d, toks_g, toks_k):
        np.testing.assert_array_equal(d, g)
        np.testing.assert_array_equal(d, k)
    assert eng_k.prefill_tokens_shared > 0     # kernel path saw shared pages


def test_paged_kernel_one_compile_across_occupancies(fp32_model, nprng):
    """The kernel grid is static over (B, heads, table width): page
    occupancy varies only through block-table/length DATA, so one decode
    compile must serve every mix — short rows, long rows, idle rows, and a
    second drained-and-refilled generation of requests."""
    cfg, model, params = fp32_model
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=4, max_len=96, max_new_tokens=4, kv_page=16,
        prefill_chunk=32, attn_impl="paged_interpret"))
    for p in _shared_prefix_prompts(nprng, cfg.vocab_size, 5):
        eng.submit(p)
    eng.run_until_drained()
    assert eng._decode_paged._cache_size() == 1
    # refill with very different lengths/occupancies: decode never retraces
    for L in (3, 90, 41):
        eng.submit(nprng.integers(0, cfg.vocab_size, size=(L,)).astype(
            np.int32))
    eng.run_until_drained()
    assert eng._decode_paged._cache_size() == 1, \
        "decode retraced on a new page-occupancy mix"
    # the chunk dispatch batch width tracks the chunking-set size (a
    # pre-existing width-driven shape), so its trace count is bounded by
    # max_batch — page occupancy itself must add nothing on top
    assert eng._chunk_paged._cache_size() <= 4


def test_attn_impl_config_validation():
    """attn_impl is a paged-cache knob: reject it without kv_page, and
    reject unknown values."""
    with pytest.raises(AssertionError):
        ServingConfig(attn_impl="paged")
    with pytest.raises(AssertionError):
        ServingConfig(attn_impl="nope", kv_page=16, max_len=64)
    ServingConfig(attn_impl="paged", kv_page=16, max_len=64)   # fine


def test_paged_semantic_mode_serves(fp32_model, nprng):
    """The sketch-descriptor prefix index (prefix_mode="semantic") serves
    the exact-repeat workload too — exact entries win, the semantic path
    just widens; tokens still match dense."""
    cfg, model, params = fp32_model
    prompts = _shared_prefix_prompts(nprng, cfg.vocab_size, 5,
                                     prefix_lens=(33,))
    eng_d, toks_d = _serve(model, params, prompts)
    eng_s, toks_s = _serve(model, params, prompts, kv_page=16,
                           prefill_chunk=32, prefix_mode="semantic")
    for d, s in zip(toks_d, toks_s):
        np.testing.assert_array_equal(d, s)
    assert eng_s.stats()["kv"]["pages_shared"] > 0


# ---------------------------------------------------------------------------
# host-side bookkeeping units (no model needed)
# ---------------------------------------------------------------------------


def _mk_kv(**kw):
    return PagedKVCache(None, max_batch=2, max_len=64, page_size=16, **kw)


def test_admit_maps_shared_pages_and_register_publishes(nprng):
    kv = _mk_kv()
    prompt = nprng.integers(0, 99, size=(40,)).astype(np.int32)
    assert kv.admit(0, prompt) == 0                    # cold: nothing shared
    kv.register(0, prompt)                             # publish pages 0, 1
    shared = kv.admit(1, prompt)
    assert shared == 32                                # 2 full pages mapped
    assert (kv.block_table[1, :2] == kv.block_table[0, :2]).all()
    assert (kv.refcount[kv.block_table[0, :2]] == 2).all()
    # the sharing cap: the page holding the last token is never shared
    assert kv.block_table[1, 2] != kv.block_table[0, 2]
    kv.free_slot(0)
    kv.free_slot(1)
    assert (kv.refcount == 0).all()


def test_freed_pages_stay_probeable_until_recycled(nprng):
    kv = _mk_kv()
    prompt = nprng.integers(0, 99, size=(40,)).astype(np.int32)
    kv.admit(0, prompt)
    kv.register(0, prompt)
    kv.free_slot(0)                                    # refcounts to 0
    assert kv.admit(1, prompt) == 32                   # index still serves
    kv.free_slot(1)


def test_recycle_invalidates_index_entries(nprng):
    kv = PagedKVCache(None, max_batch=2, max_len=64, page_size=16,
                      num_pages=8)                     # exactly 2 slots' span
    p1 = nprng.integers(0, 99, size=(40,)).astype(np.int32)
    p2 = nprng.integers(100, 199, size=(40,)).astype(np.int32)
    kv.admit(0, p1)
    kv.register(0, p1)
    kv.free_slot(0)
    # churn through the whole pool: p1's pages are recycled for p2
    kv.admit(0, p2)
    kv.admit(1, p2)
    assert len(kv._exact) < 4                          # p1 entries died
    kv.free_slot(0)
    kv.free_slot(1)


def test_copy_on_write_remaps_writer():
    kv = _mk_kv()
    prompt = np.arange(40, dtype=np.int32)
    kv.admit(0, prompt)
    kv.register(0, prompt)
    kv.admit(1, prompt)
    pid = int(kv.block_table[1, 0])
    pool = {"k": jnp.arange(2 * kv.num_pages * 16, dtype=jnp.float32
                            ).reshape(2, kv.num_pages, 16)}
    pool2 = kv.ensure_private(pool, 1, 0)
    new = int(kv.block_table[1, 0])
    assert new != pid                                  # writer remapped
    assert int(kv.block_table[0, 0]) == pid            # sharer untouched
    assert int(kv.refcount[pid]) == 1 and int(kv.refcount[new]) == 1
    np.testing.assert_array_equal(np.asarray(pool2["k"][:, new]),
                                  np.asarray(pool["k"][:, pid]))
    assert kv.stats.cow_copies == 1
    # private page: second call is a no-op
    assert kv.ensure_private(pool2, 1, 0) is pool2


def test_pool_sizing_guard():
    """A pool smaller than max_batch * pages_per_slot could exhaust mid
    admission (a slot always maps exactly pages_per_slot pages); the ctor
    rejects it up front so _acquire's exhaustion error stays unreachable."""
    with pytest.raises(AssertionError):
        PagedKVCache(None, max_batch=2, max_len=64, page_size=16,
                     num_pages=4)


def test_shared_prefix_workload_heads_and_determinism():
    """Every request carries its session's full head verbatim plus a
    bounded suffix; same seeds => same stream (the benchmark's equal-load
    contract between the share-on and share-off rows)."""
    mk = lambda: SharedPrefixWorkload(num_sessions=3, prefix_len=32,
                                      suffix_min=2, suffix_max=5,
                                      vocab_size=97, seed=3)
    wl = mk()
    reqs = list(wl.stream(20, seed=5))
    assert len(reqs) == 20
    for sess, prompt in reqs:
        np.testing.assert_array_equal(prompt[:32], wl.prefixes[sess])
        assert 34 <= len(prompt) <= 37
    for (s1, p1), (s2, p2) in zip(reqs, mk().stream(20, seed=5)):
        assert s1 == s2
        np.testing.assert_array_equal(p1, p2)


def test_scatter_rejects_duplicate_slots():
    cache = {"l0/k": jnp.zeros((1, 4, 8, 2))}
    rows = {"l0/k": jnp.ones((1, 2, 8, 2))}
    out = batch_cache_scatter(cache, rows, jnp.asarray([1, 3], jnp.int32))
    assert float(out["l0/k"][0, 1].sum()) > 0
    with pytest.raises(ValueError, match="duplicate target slots"):
        batch_cache_scatter(cache, rows, jnp.asarray([2, 2], jnp.int32))
