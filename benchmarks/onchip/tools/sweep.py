#!/usr/bin/env python3
"""Find an open-loop cell's knee on the chip: one process, one engine,
one set-up, then a window at each of several offered rates.

    python3 benchmarks/onchip/tools/sweep.py <cell> --seed N \\
        --seconds 20 --rates 4,8,12,16,20

Per rate it prints the requests due, how many were still unserved when
the window closed, how long the drain took, and the tails: the knee is
the highest rate at which nothing piles up (the drain stays short and the
tails flat).  The cell's traffic file then fixes its rate at about four
fifths of that.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402


def _q(xs, p):
    return float(np.percentile(xs, p)) if xs else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--root", default=str(ROOT))
    ap.add_argument("--bench-dir", default=str(HERE))
    args = ap.parse_args()
    import run as R
    R.enable_cache()
    import jax
    from harness import check, report, runner, spec, traffic, weights

    cell = spec.load_cell(Path(args.root), args.cell, Path(args.bench_dir))
    config, mix = cell.config, cell.traffic
    sv = config["serving"]
    params = weights.make_params(config["model"], args.seed)
    _, eng = runner.build_engine(config, params)
    del params
    drv = runner.Runner(eng, int(mix["max_submit_per_step"]))
    hot_tokens = {}
    t = time.perf_counter()
    first = traffic.plan(mix, seed=args.seed, seconds=args.seconds,
                         vocab=config["model"]["vocab_size"],
                         slots=sv["slots"], max_len=sv["max_len"],
                         chunk=sv["prefill_chunk"])
    runner.warm_up(drv, first.warm, hot_tokens)
    print(f"SWEEP set-up {time.perf_counter() - t:.1f} s", flush=True)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        p = traffic.plan(mix, seed=args.seed, seconds=args.seconds,
                         vocab=config["model"]["vocab_size"],
                         slots=sv["slots"], max_len=sv["max_len"],
                         chunk=sv["prefill_chunk"], rate=rate,
                         window_seed=args.seed * 1000 + i)
        drv.by_rid.clear()
        recs, t_close, steps, late = runner.open_loop(drv, p.window,
                                                      args.seconds)
        unserved = sum(1 for r in recs if r.in_window
                       and not (r.done_s <= t_close))
        drain = max((r.done_s for r in recs if r.source), default=0.0) - t_close
        run = runner.Run(records=recs, window_s=args.seconds,
                         steps_in_window=steps, setup={"setup_s": 0},
                         compiles_in_window=0, compile_names=[], late_s=late,
                         hot_tokens=hot_tokens, trace_dir=None,
                         memory_peak_bytes=0, counters={})
        hits, ttft = report.hit_latencies_ms(run), report.ttft_ms(run)
        itl = report.itl_ms(run, closed=False)
        print("SWEEP " + json.dumps({
            "rate": rate, "due": len(recs), "steps": steps,
            "step_ms": 1e3 * args.seconds / max(steps, 1),
            "unserved_at_close": unserved, "drain_s": drain,
            "hit_p50": _q(hits, 50), "hit_p95": _q(hits, 95),
            "ttft_p50": _q(ttft, 50), "ttft_p95": _q(ttft, 95),
            "itl_p50": _q(itl, 50), "itl_p95": _q(itl, 95),
            "ladder": check.ladder(run)}),
            flush=True)
    jax.block_until_ready(eng.cache)


if __name__ == "__main__":
    main()
