"""Serving engine: continuous batching correctness + CoIC integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.coic import CoICConfig
from repro.models import build_model
from repro.serving.engine import ServingConfig, ServingEngine


@pytest.fixture(scope="module")
def served_model():
    import dataclasses

    # fp32: bf16 near-ties can flip argmax between batched and single-row
    # decode (different reduction order), which is numerics, not scheduling
    cfg = dataclasses.replace(get_config("coic-paper"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _greedy_reference(model, params, prompt, max_new):
    logits, cache, ln = model.prefill(params, jnp.asarray(prompt[None, :]),
                                      max_len=len(prompt) + max_new + 8)
    out = []
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out.append(int(tok[0]))
    for _ in range(max_new - 1):
        logits, cache, ln = model.decode_step(params, cache, tok, ln)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(int(tok[0]))
    return np.asarray(out, np.int32)


def test_batched_generation_matches_single(served_model, nprng):
    """Requests served through continuous batching must produce exactly the
    single-request greedy generations."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=4, max_len=96, max_new_tokens=8))
    prompts = [nprng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
               for _ in range(6)]
    rids = [eng.submit(p) for p in prompts]
    eng.run_until_drained()
    assert len(eng.results) == 6
    by_id = {r.req_id: r for r in eng.results}
    for rid, prompt in zip(rids, prompts):
        ref = _greedy_reference(model, params, prompt, 8)
        got = by_id[rid].tokens
        np.testing.assert_array_equal(got, ref)


def test_coic_front_serves_repeats_from_edge(served_model, nprng):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=4, max_len=96, max_new_tokens=8,
        coic=CoICConfig(capacity=64, threshold=0.995, descriptor="prefix",
                        k_layers=2)))
    prompt = nprng.integers(0, cfg.vocab_size, size=(24,)).astype(np.int32)
    eng.submit(prompt)
    eng.run_until_drained()
    assert eng.results[0].source == "cloud"
    cloud_tokens = eng.results[0].tokens

    eng.submit(prompt.copy())                      # identical request
    eng.run_until_drained()
    assert eng.results[1].source == "edge"
    np.testing.assert_array_equal(eng.results[1].tokens[:8], cloud_tokens)
    assert eng.results[1].decode_steps == 0        # zero model steps — the win


def test_edge_hit_vs_threshold(served_model, nprng):
    """tau=1.01 (unreachable) => every request goes to the cloud."""
    cfg, model, params = served_model
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=2, max_len=96, max_new_tokens=4,
        coic=CoICConfig(capacity=16, threshold=1.01, descriptor="prefix")))
    prompt = nprng.integers(0, cfg.vocab_size, size=(16,)).astype(np.int32)
    eng.submit(prompt)
    eng.run_until_drained()
    eng.submit(prompt.copy())
    eng.run_until_drained()
    assert [r.source for r in eng.results] == ["cloud", "cloud"]


def test_slots_recycled(served_model, nprng):
    cfg, model, params = served_model
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=2, max_len=64, max_new_tokens=4))
    for _ in range(5):
        eng.submit(nprng.integers(0, cfg.vocab_size, size=(16,)).astype(np.int32))
    eng.run_until_drained()
    assert len(eng.results) == 5
    assert sorted(eng.free_slots) == [0, 1]


def test_overflow_reject_raises_without_consuming_rid(served_model):
    """Prompts longer than max_len must fail loudly at submit() — the old
    behavior silently truncated in _pad_prompts and served tokens
    conditioned on a prompt the caller never sent."""
    from repro.serving.engine import PromptTooLongError

    cfg, model, params = served_model
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=2, max_len=64, max_new_tokens=4))
    with pytest.raises(PromptTooLongError, match="exceeds max_len"):
        eng.submit(np.zeros((65,), np.int32))
    rid = eng.submit(np.zeros((64,), np.int32))    # at capacity: accepted
    assert rid == 0                                # reject consumed no rid
    eng.run_until_drained()
    assert not eng.results[0].truncated


def test_overflow_truncate_serves_head_and_flags(served_model, nprng):
    """on_overflow="truncate" serves the max_len head and stamps the
    result — the same tokens an in-bounds submission of that head gets."""
    cfg, model, params = served_model
    head = nprng.integers(0, cfg.vocab_size, size=(64,)).astype(np.int32)
    long = np.concatenate([head, head])
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=2, max_len=64, max_new_tokens=4, on_overflow="truncate"))
    r_long = eng.submit(long)
    r_head = eng.submit(head)
    eng.run_until_drained()
    by = {r.req_id: r for r in eng.results}
    assert by[r_long].truncated and not by[r_head].truncated
    np.testing.assert_array_equal(by[r_long].tokens, by[r_head].tokens)


@pytest.mark.parametrize("kv_page", [0, 16])
def test_row_retires_when_its_length_reaches_the_max_len_cap(
        served_model, nprng, kv_page):
    """A row stops at the step its length reaches ``max_len - 1``: a
    ``max_len - 3`` prompt gives 3 tokens, whatever ``max_new_tokens``
    asks, while a row 16 tokens shorter in the same batch runs to all
    of them.  The tokens are the greedy ones."""
    cfg, model, params = served_model
    max_len, max_new = 64, 8
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=2, max_len=max_len, max_new_tokens=max_new, eos_id=-1,
        kv_page=kv_page))
    capped = nprng.integers(0, cfg.vocab_size,
                            size=(max_len - 3,)).astype(np.int32)
    short = nprng.integers(0, cfg.vocab_size,
                           size=(max_len - 3 - 16,)).astype(np.int32)
    r_capped, r_short = eng.submit(capped), eng.submit(short)
    eng.run_until_drained()
    by = {r.req_id: r for r in eng.results}
    assert len(by[r_capped].tokens) == 3
    assert len(by[r_short].tokens) == max_new
    np.testing.assert_array_equal(
        by[r_capped].tokens, _greedy_reference(model, params, capped, 3))
    np.testing.assert_array_equal(
        by[r_short].tokens, _greedy_reference(model, params, short, max_new))
