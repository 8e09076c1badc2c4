"""Paged KV prefix sharing: prefill compute saved at bit-identical tokens.

The KV-reuse scenario (ROADMAP "KV-level reuse"; CoIC's workload redundancy
pushed below the descriptor cache): co-located AR users ground requests in
the same scene context, so their prompts share long session HEADS
(``SharedPrefixWorkload``).  A paged engine (``kv_page > 0``) admits the
first request of a session normally, REGISTERS its full prompt pages in the
prefix index, and every follow-up request of that session MAPS those pages
through its block table instead of re-running prefill for them — same
physical KV bytes, refcounted.

Both measured rows drive the *identical* request stream through the same
paged continuous-batching engine; the only difference is
``prefix_share``:

  kv_share_off — every prompt pays full chunked prefill (the paged layout
                 alone: block tables, no cross-request mapping)
  kv_share_on  — page-aligned shared heads are mapped, only suffixes (and
                 each session's first admission) compute

Acceptance (``kv_reuse_accept``): sharing must cut computed prefill tokens
by >= 30% on this workload while decoded tokens stay BIT-IDENTICAL
per request — mapped pages hold exactly the bytes prefill would have
written (exact hash-chain index), so this is compute elision, not an
approximation.  ``kv_ladder_bound`` proves the per-step lookup-ladder
bound survives paged continuous batching: at most 1 descriptor + 1
grouped-lookup dispatch per engine step (<= 2) and <= 4 dispatches inside
the federated ladder, with paged chunked prefill active.

``kv_attn_gathered`` vs ``kv_attn_paged_kernel`` drive the same stream
with the only difference being how attention reads the page pool: the
dense ``paged_gather_view`` copy vs the in-place ``kernels/paged_attention``
op.  Each row reports steps/s and the modeled per-layer attention HBM
bytes/step (``attention_kv_bytes_per_step`` over the observed per-step
row fills); ``kv_attn_accept`` asserts the kernel row moves strictly
fewer bytes at bit-identical decoded tokens — nightly CI gates on it.

Emitted JSON record (``--json PATH`` / ``run(json_path=...)``): prefill
dispatches per computed token, prefix-share rate, p99 motion-to-photon
completion (paced steps), and the reduction ratio — the repo's benchmark
trajectory for KV reuse.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.data.workload import SharedPrefixWorkload

REPO_ROOT = Path(__file__).resolve().parent.parent


def _drive(model, params, wl: SharedPrefixWorkload, *, share: bool,
           n_requests: int, seed: int, coic=None, max_batch: int = 4,
           max_len: int = 96, page: int = 16, chunk: int = 32,
           step_ms: float = 2.0, attn_impl: str = "gather"):
    """Serve ``n_requests`` of ``wl`` through a fresh paged engine.
    Returns (engine, {rid: tokens}, wall_s, length_snaps) where
    ``length_snaps`` is one (max_batch,) row-fill vector per engine step
    (idle rows 0) — the input of the attention HBM byte model."""
    from repro.serving.engine import ServingConfig, ServingEngine

    eng = ServingEngine(model, params, ServingConfig(
        max_batch=max_batch, max_len=max_len, max_new_tokens=4,
        kv_page=page, prefill_chunk=chunk, prefix_share=share,
        step_ms=step_ms, coic=coic, attn_impl=attn_impl))
    rids = []
    snaps = []

    def _snap():
        snaps.append(np.where(eng.row_active, np.asarray(eng.lengths), 0))

    t0 = time.perf_counter()
    for i, (sess, prompt) in enumerate(wl.stream(n_requests, seed=seed + 1)):
        rids.append(eng.submit(prompt, node_id=i % 2, cluster_id=sess % 2
                               if coic is not None else 0))
        eng.step()
        _snap()
    while eng.pending or eng.queue or eng.chunking or eng.active:
        eng.step()
        _snap()
    wall = time.perf_counter() - t0
    by = {r.req_id: r for r in eng.results}
    return eng, {rid: by[rid] for rid in rids}, wall, snaps


def run(seed: int = 0, n_requests: int = 32, smoke: bool = False,
        json_path: str = ""):
    """Share-off vs share-on rows, the >= 30% acceptance row, and the
    ladder-bound row; optionally dumps the JSON perf record."""
    import dataclasses

    import jax

    from repro.configs import get_config
    from repro.core.coic import CoICConfig
    from repro.models import build_model

    if smoke:
        n_requests = 24
    # fp32 so the share-on/off token comparison is pure scheduling, not
    # bf16 near-tie numerics (the test-suite idiom)
    cfg = dataclasses.replace(get_config("coic-paper"), dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    wl = SharedPrefixWorkload(num_sessions=4, prefix_len=64, suffix_min=4,
                              suffix_max=16, vocab_size=cfg.vocab_size,
                              seed=seed)

    rows = []
    res = {}
    for share in (False, True):
        eng, by, wall, _ = _drive(model, params, wl, share=share,
                                  n_requests=n_requests, seed=seed)
        pt = eng.stats()["prefill_tokens"]
        p99 = float(np.percentile([r.completion_ms for r in by.values()], 99))
        res[share] = (eng, by, pt, p99)
        name = "kv_share_on" if share else "kv_share_off"
        kv = eng.stats()["kv"]
        rows.append((
            name, wall / n_requests * 1e6,
            f"prefill_computed={pt['computed']};"
            f"prefill_shared={pt['shared']};"
            f"chunk_dispatches={eng.dispatches['prefill_chunk']};"
            f"pages_shared={kv['pages_shared']};p99_ms={p99:.2f}"))

    eng_off, by_off, pt_off, p99_off = res[False]
    eng_on, by_on, pt_on, p99_on = res[True]
    match = all(np.array_equal(by_off[rid].tokens, by_on[rid].tokens)
                for rid in by_off)
    drained = (eng_on.kv.refcount == 0).all() and \
        (eng_off.kv.refcount == 0).all()
    reduction = 1.0 - pt_on["computed"] / max(1, pt_off["computed"])
    share_rate = pt_on["shared"] / max(1, pt_on["shared"]
                                       + pt_on["computed"])
    ok = match and bool(drained) and reduction >= 0.30
    rows.append(("kv_reuse_accept", 0.0,
                 f"reduction={reduction:.3f};share_rate={share_rate:.3f};"
                 f"tokens_match={match};refcounts_drained={bool(drained)};"
                 f"ok={ok}"))

    # gathered-view vs in-place paged-attention kernel: the same stream
    # through the same paged+shared engine, differing only in attn_impl.
    # Off-TPU the kernel runs interpreted (Python-speed — steps/s is NOT
    # comparable there; the modeled HBM bytes/step and the token match
    # are), so the pair uses a smaller slice of the stream.
    import jax as _jax

    from repro.kernels.paged_attention import attention_kv_bytes_per_step

    on_tpu = _jax.default_backend() == "tpu"
    kimpl = "paged" if on_tpu else "paged_interpret"
    n_attn = n_requests if on_tpu else max(6, n_requests // 4)
    attn_res = {}
    for name, impl, model_impl in (
            ("kv_attn_gathered", "gather", "gather"),
            ("kv_attn_paged_kernel", kimpl, "paged")):
        eng, by, wall, snaps = _drive(model, params, wl, share=True,
                                      n_requests=n_attn, seed=seed,
                                      attn_impl=impl)
        per_layer = float(np.mean([attention_kv_bytes_per_step(
            s, page_size=16, max_len=96, kv_heads=cfg.num_kv_heads,
            head_dim=cfg.head_dim, dtype_bytes=np.dtype(cfg.dtype).itemsize,
            impl=model_impl) for s in snaps]))
        steps_per_s = eng.step_count / max(wall, 1e-9)
        attn_res[name] = (by, per_layer)
        rows.append((name, wall / max(1, eng.step_count) * 1e6,
                     f"steps_per_s={steps_per_s:.2f};"
                     f"hbm_bytes_per_step_per_layer={per_layer:.0f};"
                     f"attn_impl={impl}"))
    by_g, bytes_g = attn_res["kv_attn_gathered"]
    by_k, bytes_k = attn_res["kv_attn_paged_kernel"]
    attn_match = all(np.array_equal(by_g[r].tokens, by_k[r].tokens)
                     for r in by_g)
    attn_ok = attn_match and bytes_k < bytes_g
    rows.append(("kv_attn_accept", 0.0,
                 f"bytes_gathered={bytes_g:.0f};bytes_paged={bytes_k:.0f};"
                 f"bytes_ratio={bytes_k / max(bytes_g, 1e-9):.3f};"
                 f"tokens_match={attn_match};ok={attn_ok}"))

    # ladder bound under paged continuous batching: a federated CoIC front
    # in front of the paged engine must keep the per-step ladder at <= 2
    # engine dispatches (1 descriptor + 1 grouped lookup) and <= 4 inside
    # the federation, with paged chunked prefill live in the same steps
    coic = CoICConfig(capacity=32, threshold=0.98, descriptor="sketch",
                      descriptor_dim=64, num_nodes=2, num_clusters=2,
                      digest_size=16, digest_interval=4)
    eng_l, _, _, _ = _drive(model, params, wl, share=True,
                            n_requests=max(12, n_requests // 2),
                            seed=seed + 7, coic=coic)
    fed_max = eng_l.sem_fed.stats()["max_ladder_dispatches"]
    chunked = eng_l.dispatches["prefill_chunk"]
    bound_ok = eng_l.max_step_ladder <= 2 and fed_max <= 4 and chunked > 0
    rows.append(("kv_ladder_bound", 0.0,
                 f"step_ladder_max={eng_l.max_step_ladder};"
                 f"fed_ladder_max={fed_max};prefill_chunks={chunked};"
                 f"max=4;ok={bound_ok}"))

    if json_path:
        dispatches_per_token = (eng_on.dispatches["prefill_chunk"]
                                / max(1, pt_on["computed"]))
        with open(json_path, "w") as f:
            json.dump({
                "bench": "kv_reuse", "n_requests": n_requests,
                "prefill_dispatches_per_token": dispatches_per_token,
                "prefix_share_rate": share_rate,
                "prefill_reduction": reduction,
                "p99_mtp_ms_share_on": p99_on,
                "p99_mtp_ms_share_off": p99_off,
                "tokens_match": bool(match),
                "ok": bool(ok),
                "attn_hbm_bytes_per_step_gathered": bytes_g,
                "attn_hbm_bytes_per_step_paged_kernel": bytes_k,
                "attn_tokens_match": bool(attn_match),
                "attn_ok": bool(attn_ok),
            }, f, indent=2)
    return rows


def run_smoke():
    # anchor the perf record at the repo root so it lands in the same
    # place no matter where run.py is invoked from
    return run(smoke=True, json_path=str(REPO_ROOT / "BENCH_kv_reuse.json"))


if __name__ == "__main__":
    import sys

    path = ""
    if "--json" in sys.argv:
        path = sys.argv[sys.argv.index("--json") + 1]
    for r in run(smoke="--smoke" in sys.argv, json_path=path):
        print(",".join(str(x) for x in r))
