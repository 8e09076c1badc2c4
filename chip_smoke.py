#!/usr/bin/env python3
"""Chip smoke: the CoIC serving path on a TPU, end to end, checked.

    python chip_smoke.py              # one chip: llama3.2-1b served through
                                      # the ladder and the paged-KV engine
    python chip_smoke.py --chips 4    # four chips: the peer rung as a
                                      # cross-chip shard_map collective

One chip: ``get_config("llama3.2-1b")`` at its published widths with
random weights from ``--seed`` serves a stream of repeated prompts through
``ServingEngine`` (prefix descriptor, a 4-node cooperative cache pooled on
the chip, paged KV with the in-place attention kernel).  The first wave
misses (prefill + decode), the second is served by the ladder.  The run
fails unless every request completes, every hit returns the tokens of a
miss of the same prompt, every kernel op resolved to ``pallas``, and the
Pallas kernels agree with their jnp oracles on the chip within the bounds
stated below.

Four chips: a ``CooperativeEdgeCluster`` on a 4-device ``cache`` mesh,
one shard per chip, probed through ``sharded_topk_lookup`` and compared
with the pooled probe on one chip.

Exits non-zero, printing no result, where JAX finds no TPU.  The last line
of a passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core.cluster import ClusterConfig, CooperativeEdgeCluster  # noqa: E402
from repro.core.coic import CoICConfig  # noqa: E402
from repro.kernels.paged_attention import paged_attention  # noqa: E402
from repro.kernels.similarity import (similarity_topk,  # noqa: E402
                                      similarity_topk_batched)
from repro.models import build_model  # noqa: E402
from repro.obs.metrics import MetricsRegistry  # noqa: E402
from repro.obs.profile import disable_profiling, enable_profiling  # noqa: E402
from repro.serving.engine import ServingConfig, ServingEngine  # noqa: E402
from repro.serving.kv_cache import PagedKVCache  # noqa: E402

# Pallas-vs-oracle bounds.  Similarity scores are dot products of unit
# vectors: rounding both operands to bf16 (one MXU pass) moves a score by
# at most 2 * 2^-8 * |q| |k| = 2^-7.  Attention outputs are softmax-weighted
# averages of unit-normal bf16 values: the oracle rounds its logits and
# probabilities to bf16 (relative 2^-8) where the kernel keeps f32, which
# moves an output by a few 1e-2 at most.
SIM_ATOL = 2.0 ** -7
ATTN_ATOL = 2.0 ** -4
# a repeat of a prompt scores ~1.0 against its own cached descriptor;
# random weights put unrelated prompts closer together than trained ones
# would, so the smoke asks for a near-exact match
HIT_THRESHOLD = 0.99


class SmokeFailure(RuntimeError):
    """A phase produced a wrong result."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def describe(cfg) -> str:
    return (f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
            f"{cfg.num_heads} heads over {cfg.num_kv_heads} kv heads, "
            f"head_dim {cfg.head_dim}, d_ff {cfg.d_ff}, "
            f"vocab {cfg.vocab_size}, {cfg.dtype}")


def _attention_impl(lowered) -> str:
    """What a lowered engine step runs for paged attention: ``pallas`` where
    the Mosaic kernel is in the program, ``pallas_interpret`` where the
    kernel's jitted wrapper is lowered for the interpreter (off the chip),
    else ``ref``."""
    text = lowered.as_text()
    if "paged_attention" in re.findall(r'kernel_name = "([^"]+)"', text):
        return "pallas"
    if "@paged_attention_kernel" in text:
        return "pallas_interpret"
    return "ref"


def serve_smoke(cfg, *, seed: int = 0, impl: str = "pallas",
                max_batch: int = 8, max_len: int = 512, kv_page: int = 16,
                num_nodes: int = 4, n_prompts: int = 4, n_requests: int = 16,
                prompt_lens=(64, 256), max_new_tokens: int = 16,
                log=print) -> dict:
    """Serve ``n_requests`` prompts (drawn with repeats from ``n_prompts``)
    through ``ServingEngine`` in two waves and check the results.

    ``impl`` is what every kernel op must resolve to: ``"pallas"`` on a TPU
    (the engine runs its default ``auto`` selection), ``"pallas_interpret"``
    to force the Pallas interpreter on a CPU.  Raises ``SmokeFailure`` on a
    wrong result; returns the report."""
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_lens[0], prompt_lens[1] + 1, size=n_prompts)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n)).astype(np.int32)
               for n in lens]
    # wave 1 holds every prompt (misses); wave 2 repeats them (hits)
    order = np.concatenate([rng.permutation(n_prompts),
                            rng.integers(0, n_prompts,
                                         n_requests - n_prompts)])
    on_chip = impl == "pallas"
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=max_batch, max_len=max_len, max_new_tokens=max_new_tokens,
        kv_page=kv_page,
        attn_impl="paged" if on_chip else "paged_interpret",
        coic=CoICConfig(descriptor="prefix", num_nodes=num_nodes,
                        threshold=HIT_THRESHOLD,
                        lookup_impl="auto" if on_chip else impl)))

    prof = MetricsRegistry()
    enable_profiling(prof)
    try:
        prompt_of = {}
        t0 = time.perf_counter()
        half = n_requests // 2
        for wave in (order[:half], order[half:]):
            for p in wave:
                rid = eng.submit(prompts[p], node_id=len(prompt_of) % num_nodes)
                prompt_of[rid] = int(p)
            eng.run_until_drained()
        jax.block_until_ready(eng.cache)
        served_s = time.perf_counter() - t0
    finally:
        disable_profiling()

    results = eng.results
    stats = eng.stats()
    hits = [r for r in results if r.source != "cloud"]
    misses = [r for r in results if r.source == "cloud"]
    report = {"served_s": served_s, "completed": stats["completed"],
              "hits": len(hits), "misses": len(misses),
              "sources": {s: sum(r.source == s for r in results)
                          for s in ("edge", "peer", "cloud")}}
    log(f"served: {report['completed']}/{n_requests} completed, "
        f"{report['hits']} hits {report['sources']}, "
        f"{report['misses']} misses, {served_s:.3f} s wall")
    _check(report["completed"] == n_requests == len(results),
           f"{report['completed']} of {n_requests} requests completed")
    _check(bool(hits) and bool(misses), "need at least one hit and one miss")
    miss_tokens = {}
    for r in misses:
        _check(len(r.tokens) == max_new_tokens,
               f"miss {r.req_id} decoded {len(r.tokens)} tokens")
        miss_tokens.setdefault(prompt_of[r.req_id], []).append(r.tokens)
    for r in hits:
        same = miss_tokens.get(prompt_of[r.req_id], [])
        _check(any(np.array_equal(r.tokens, t) for t in same),
               f"hit {r.req_id} ({r.source}) returned tokens that no miss "
               "of its prompt decoded")

    # resolved impl of every kernel op: host-side ops from the profiler's
    # kernel/<op>/<impl>/ names, the attention kernel from the lowered
    # programs of the engine's jitted paged decode and chunk steps
    impls = {}
    for name in prof.names():
        parts = name.split("/")
        if parts[0] == "kernel" and parts[-1] == "calls":
            impls.setdefault(parts[1], set()).add(parts[2])
    bt = jnp.asarray(eng.kv.decode_table(eng.row_active))
    C = eng._chunk_width
    lowered = {
        "decode": eng._decode_paged.lower(eng.params, eng.cache, eng.tokens,
                                          eng.lengths, bt),
        "prefill_chunk": eng._chunk_paged.lower(
            eng.params, jnp.zeros((1, C), jnp.int32), eng.cache,
            jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32), bt[:1]),
    }
    for step, low in lowered.items():
        impls[f"paged_attention@{step}"] = {_attention_impl(low)}
    report["impls"] = {k: sorted(v) for k, v in impls.items()}
    for op, got in sorted(report["impls"].items()):
        log(f"kernel {op}: {','.join(got)}")
    _check("similarity_topk_batched" in impls,
           "the ladder never ran similarity_topk_batched")
    for op, got in impls.items():
        _check(got == {impl}, f"{op} resolved to {sorted(got)}, not {impl}")

    report["errors"] = kernel_errors(eng, prompts, impl=impl, seed=seed,
                                     log=log)
    return report


def kernel_errors(eng, prompts, *, impl: str, seed: int, log=print) -> dict:
    """Pallas-vs-``ref`` max abs error of the two served-path kernels at
    the smoke's shapes: ``similarity_topk_batched`` over the cache as the
    run left it, probed with the prompts' descriptors, and
    ``paged_attention`` over a random pool of the engine's layout for a
    decode step and a full-width prefill chunk."""
    out = {}
    keys, valid, _ = eng.sem_cluster._stacks()
    desc, _ = eng._extract_descriptors(prompts)
    N = keys.shape[0]
    q = jnp.broadcast_to(jnp.asarray(desc), (N,) + desc.shape)
    pi, ps = similarity_topk_batched(q, keys, valid, 1, impl=impl)
    ri, rs = similarity_topk_batched(q, keys, valid, 1, impl="ref")
    pi, ps, ri, rs = (np.asarray(a)[..., 0] for a in (pi, ps, ri, rs))
    err = float(np.abs(ps - rs).max())
    out["similarity_topk_batched"] = err
    log(f"similarity_topk_batched: max |pallas - ref| score {err:.3e} "
        f"(bound {SIM_ATOL:.3e})")
    _check(err <= SIM_ATOL, f"similarity score error {err} > {SIM_ATOL}")
    # a differing top-1 is allowed only where two slots tie to within the
    # bound (exact f64 scores of both picks)
    exact = np.einsum("nqd,ncd->nqc", np.asarray(q, np.float64),
                      np.asarray(keys, np.float64))
    n_idx, q_idx = np.nonzero(pi != ri)
    for n, j in zip(n_idx, q_idx):
        gap = abs(exact[n, j, pi[n, j]] - exact[n, j, ri[n, j]])
        _check(gap <= 2 * SIM_ATOL,
               f"top-1 differs at node {n} query {j} with no tie "
               f"(gap {gap:.3e})")

    rng = np.random.default_rng(seed)
    pool = next(v for k, v in eng.cache.items() if k.endswith("/k"))
    P, K, page, D = pool.shape[1:]
    H = eng.model.cfg.num_heads
    B, n_pages = eng.kv.block_table.shape
    dt = pool.dtype
    kp = jnp.asarray(rng.standard_normal((P, K, page, D)), dt)
    vp = jnp.asarray(rng.standard_normal((P, K, page, D)), dt)
    for name, C in (("decode", 1), ("prefill_chunk", eng._chunk_width)):
        lengths = rng.integers(0, n_pages * page - C + 1, size=B)
        bt = np.full((B, n_pages), PagedKVCache.INVALID, np.int32)
        pages = rng.permutation(P)
        for b in range(B):
            used = min(n_pages, -(-int(lengths[b] + C) // page))
            bt[b, :used] = pages[b * n_pages:b * n_pages + used]
        qa = jnp.asarray(rng.standard_normal((B, C, H, D)), dt)
        args = (qa, kp, vp, jnp.asarray(bt), jnp.asarray(lengths, jnp.int32))
        o_p = np.asarray(paged_attention(*args, impl=impl), np.float32)
        o_r = np.asarray(paged_attention(*args, impl="ref"), np.float32)
        _check(bool(np.isfinite(o_p).all()), f"paged_attention {name}: "
               "non-finite output")
        err = float(np.abs(o_p - o_r).max())
        out[f"paged_attention@{name}"] = err
        log(f"paged_attention {name} (B={B}, C={C}): max |pallas - ref| "
            f"{err:.3e} (bound {ATTN_ATOL:.3e})")
        _check(err <= ATTN_ATOL,
               f"paged_attention {name} error {err} > {ATTN_ATOL}")
    return out


def cache_mesh_smoke(*, seed: int = 0, num_nodes: int = 4,
                     capacity: int = 4096, key_dim: int = 2048,
                     n_queries: int = 64, k: int = 4, log=print) -> dict:
    """The cooperative cluster across chips: shards one per device of a
    ``cache`` mesh.  Its peer probe (``sharded_topk_lookup``) is compared
    with the pooled probe of the same keys on one device, and its ladder
    (``lookup_grouped``: local rung per device, peer rung collective) with
    a twin cluster pooled on one device."""
    from repro.launch.mesh import make_cache_mesh
    from repro.parallel.sharding import sharded_topk_lookup

    mesh = make_cache_mesh(num_nodes)
    cfg = ClusterConfig(num_nodes=num_nodes, node_capacity=capacity,
                        key_dim=key_dim, payload_dim=1,
                        payload_dtype="int32", threshold=HIT_THRESHOLD)
    cl, twin = CooperativeEdgeCluster(cfg, mesh=mesh), \
        CooperativeEdgeCluster(cfg)
    rng = np.random.default_rng(seed)

    def unit(n):
        x = rng.standard_normal((n, key_dim)).astype(np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)

    n_rows = capacity * 3 // 4
    rows = [unit(n_rows) for _ in range(num_nodes)]
    for g in range(num_nodes):
        payload = jnp.arange(g * n_rows, (g + 1) * n_rows,
                             dtype=jnp.int32)[:, None]
        for c in (cl, twin):
            c.insert(g, jnp.asarray(rows[g]), payload)
    keys, valid, _ = cl._stacks()
    devs = [s.device.id for s in sorted(keys.addressable_shards,
                                        key=lambda s: s.index[0].start)]
    log(f"cluster keys {tuple(keys.shape)} sharding {keys.sharding}; "
        f"shard g on device {devs}")
    _check(len(set(devs)) == num_nodes,
           f"cache shards share devices: {devs}")

    # half the queries are planted copies of cached keys (exact hits)
    planted = np.concatenate([r[:n_queries // (2 * num_nodes)] for r in rows])
    queries = np.concatenate([planted, unit(n_queries - len(planted))])
    t0 = time.perf_counter()
    si, ss = jax.block_until_ready(sharded_topk_lookup(
        jnp.asarray(queries), keys, valid, k, mesh))
    sharded_s = time.perf_counter() - t0
    d0 = jax.devices()[0]
    oi, os_ = similarity_topk(
        jax.device_put(queries, d0),
        jax.device_put(keys, d0).reshape(num_nodes * capacity, key_dim),
        jax.device_put(valid, d0).reshape(-1), k)
    si, ss, oi, os_ = (np.asarray(a) for a in (si, ss, oi, os_))
    err = float(np.abs(ss - os_).max())
    log(f"sharded vs pooled probe: {n_queries} queries, k={k}: indices "
        f"identical {bool(np.array_equal(si, oi))}, max |score diff| "
        f"{err:.3e} (bound {SIM_ATOL:.3e}), first sharded call "
        f"{sharded_s:.3f} s wall")
    _check(np.array_equal(si, oi), "sharded and pooled indices differ")
    _check(err <= SIM_ATOL, f"sharded score error {err} > {SIM_ATOL}")
    _check(bool((ss[:len(planted), 0] > 0.99).all()),
           "a planted key missed its own shard")

    # the ladder: node g asks for one of its own keys (local hit), one of
    # node g+1's (peer hit) and a fresh vector (miss)
    grouped = np.stack([np.stack([rows[g][1], rows[(g + 1) % num_nodes][2],
                                  unit(1)[0]]) for g in range(num_nodes)])
    got, want = (c.lookup_grouped(jnp.asarray(grouped)) for c in (cl, twin))
    for name in ("hit", "tier", "owner", "value"):
        _check(np.array_equal(np.asarray(getattr(got, name)),
                              np.asarray(getattr(want, name))),
               f"mesh and pooled cluster ladders differ in {name}")
    lerr = float(np.abs(np.asarray(got.score) - np.asarray(want.score)).max())
    log(f"mesh vs pooled cluster ladder: tiers {np.asarray(got.tier).tolist()}"
        f", max |score diff| {lerr:.3e}")
    _check(lerr <= SIM_ATOL, f"ladder score error {lerr} > {SIM_ATOL}")
    _check(np.asarray(got.tier).tolist() == [[0, 1, 3]] * num_nodes,
           "expected local, peer, miss on every node")
    return {"devices": devs, "score_err": err, "ladder_score_err": lerr}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the served path; 4: the cross-chip peer rung")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX found {devices[0].platform}", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"--chips {args.chips} needs {args.chips} devices, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")

    compile_s = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    print(f"device: {devices[0].device_kind} x{len(devices)}")
    if args.chips == 4:
        cache_mesh_smoke(seed=args.seed)
    else:
        cfg = get_config("llama3.2-1b")
        print("model:", describe(cfg))
        serve_smoke(cfg, seed=args.seed, impl="pallas")
    print(f"compile: {sum(compile_s):.1f} s over {len(compile_s)} programs")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
