"""Serving launcher: CoIC edge cache in front of a batched LM server.

Replays a Zipf request stream against the engine and reports hit rate +
latency percentiles — the deployment shape of the paper's evaluation.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, reduced_config
from repro.core.coic import CoICConfig
from repro.core.policies import EvictionPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.serving.engine import ServingConfig, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="coic-paper")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--pool", type=int, default=16, help="distinct request contents")
    ap.add_argument("--zipf", type=float, default=1.1)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--threshold", type=float, default=0.98)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--policy", default="lru", choices=["lru", "lfu", "fifo"])
    ap.add_argument("--scheduling", default="batched",
                    choices=["batched", "sequential"],
                    help="batched: one lookup ladder per engine step; "
                         "sequential: one per request (baseline)")
    ap.add_argument("--no-coic", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    coic = None if args.no_coic else CoICConfig(
        capacity=args.capacity, threshold=args.threshold,
        descriptor="prefix", k_layers=2,
        policy=EvictionPolicy(args.policy))
    eng = ServingEngine(model, params, ServingConfig(
        max_batch=8, max_len=args.prompt_len + args.max_new + 8,
        max_new_tokens=args.max_new, coic=coic,
        scheduling=args.scheduling))

    rng = np.random.default_rng(0)
    pool = rng.integers(0, cfg.vocab_size,
                        size=(args.pool, args.prompt_len)).astype(np.int32)
    ranks = np.arange(1, args.pool + 1, dtype=np.float64)
    probs = ranks ** (-args.zipf)
    probs /= probs.sum()

    import time
    t0 = time.perf_counter()
    for _ in range(args.requests):
        idx = rng.choice(args.pool, p=probs)
        eng.submit(pool[idx])
        eng.step()
    eng.run_until_drained()
    wall = time.perf_counter() - t0

    lat = [r.latency_s for r in eng.results if r.source == "cloud"]
    stats = eng.stats()
    print(f"served {stats['completed']} requests in {wall:.2f}s "
          f"({stats['completed']/wall:.1f} req/s)")
    print(f"edge hits: {stats['edge_hits']}  peer hits: {stats['peer_hits']}  "
          f"cloud: {stats['cloud']}")
    print(f"device dispatches: {stats['dispatches']}")
    if "semantic" in stats:
        print(f"semantic cache: {stats['semantic']}")
    if lat:
        print(f"cloud latency p50 {np.percentile(lat, 50)*1e3:.1f} ms  "
              f"p95 {np.percentile(lat, 95)*1e3:.1f} ms")


if __name__ == "__main__":
    main()
