"""The chip smoke's body at reduced width on the CPU: the same served path
(prefix descriptor, 4-node cooperative ladder, paged KV with the in-place
attention kernel) with every Pallas kernel in interpret mode."""
from chip_smoke import describe, serve_smoke
from repro.configs import get_config, reduced_config


def test_smoke_body_serves_hits_and_misses_in_interpret_mode():
    cfg = reduced_config(get_config("llama3.2-1b"))
    lines = []
    report = serve_smoke(cfg, seed=0, impl="pallas_interpret", max_len=64,
                         n_requests=8, prompt_lens=(8, 24), max_new_tokens=4,
                         log=lines.append)
    assert report["completed"] == 8
    assert report["hits"] >= 1 and report["misses"] >= 1
    assert report["hits"] + report["misses"] == 8
    # every kernel op ran the Pallas body, in the interpreter
    assert report["impls"]["similarity_topk_batched"] == ["pallas_interpret"]
    assert report["impls"]["paged_attention@decode"] == ["pallas_interpret"]
    assert set(report["errors"]) == {"similarity_topk_batched",
                                     "paged_attention@decode",
                                     "paged_attention@prefill_chunk"}
    assert describe(cfg).startswith(cfg.name)
