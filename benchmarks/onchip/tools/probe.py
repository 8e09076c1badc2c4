#!/usr/bin/env python3
"""Calibration readings on the chip for a configuration (not part of a
benchmark run).

    python3 benchmarks/onchip/tools/probe.py <cell> --seed N \\
        [--sims] [--trace-dump PATH] [--control SEEDS]

``--sims``: cosine similarities of the prefix descriptors of the mix's
distinct prompts (hot scenes and new scenes) against each other, and of
each prompt against itself computed in another batch and bucket: the
margin the ladder's threshold must fall in.

``--trace-dump``: a traced run of the cell, and every plane, line and
distinct operation (with its stats) written to PATH, with where the
decode program's kernels lie against the host's ``decode`` spans: what
the trace reduction matches on.

``--control``: for each seed, one short run of the cell at its own load,
then the float32 reference and the float8 control over the same sample:
the readings the logit-gap limit is set from.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def sims(cell, seed):
    from harness import runner, traffic, weights
    config, mix = cell.config, cell.traffic
    params = weights.make_params(config["model"], seed)
    _, eng = runner.build_engine(config, params)
    sv = config["serving"]
    p = traffic.plan(mix, seed=seed, seconds=30.0,
                     vocab=config["model"]["vocab_size"], slots=sv["slots"],
                     max_len=sv["max_len"], chunk=sv["prefill_chunk"])
    prompts = list(p.hot) + [r.prompt for r in p.window if r.scene < 0][:64]
    cap = int(mix["max_submit_per_step"])
    a = np.concatenate([eng._extract_descriptors(prompts[i:i + cap])[0]
                        for i in range(0, len(prompts), cap)])
    b = np.concatenate([eng._extract_descriptors(prompts[i:i + 1])[0]
                        for i in range(len(prompts))])
    s = a @ a.T
    off = s[~np.eye(len(s), dtype=bool)]
    self_sim = np.sum(a * b, -1)
    q = [0.5, 0.9, 0.99, 1.0]
    out = {"n": len(prompts),
           "distinct_max": float(off.max()),
           "distinct_quantiles": dict(zip(map(str, q), np.quantile(off, q).tolist())),
           "self_min": float(self_sim.min()),
           "self_quantiles": dict(zip(map(str, q), np.quantile(self_sim, q).tolist()))}
    print("SIMS", json.dumps(out), flush=True)
    del eng, params
    gc.collect()


def trace_dump(cell, seed, seconds, path):
    """A traced run of the cell; its trace's planes, lines and distinct
    events (with stats), and where each device op of the decode program
    lies against the host's ``decode`` spans, written to ``path``."""
    import glob

    from harness import runner
    from harness import trace_reduce as TR
    run, eng = runner.run(cell, seed, seconds, True, log=lambda *a: None)
    del eng
    gc.collect()
    f = glob.glob(f"{run.trace_dir}/**/*.xplane.pb", recursive=True)[0]
    pd = jax.profiler.ProfileData.from_file(f)
    with open(path, "w") as out:
        for plane in pd.planes:
            out.write(f"PLANE {plane.name}\n")
            for line in plane.lines:
                evs = list(line.events)
                out.write(f"  LINE {line.name!r} {len(evs)} events\n")
                seen = collections.OrderedDict()
                for ev in evs:
                    key = ev.name[:80]
                    if key not in seen:
                        seen[key] = (ev.start_ns, ev.duration_ns, ev.name[:300],
                                     {k: str(v)[:200] for k, v in ev.stats})
                for k, (st, du, nm, stats) in list(seen.items())[:40]:
                    out.write(f"    {nm!r} start {st} dur {du} {stats}\n")
        devs, spans, win = TR.load(f)
        red = TR.reduce(devs, spans, *win)
        out.write(f"REDUCED busy {red.busy_s} window {red.window_s} "
                  f"by_label {red.device_s_by_label} spans {red.span_count}\n")
        dec = sorted((s, e) for s, e, n in spans if n == "decode")
        starts = np.array([s for s, _ in dec])
        offs = []
        for op, lb in red.ops:
            if "paged_attention" in op.text:
                i = int(np.searchsorted(starts, op.start)) - 1
                if i >= 0:
                    offs.append((op.start - dec[i][0], dec[i][1] - op.end))
        out.write(f"PAGED vs decode span (start-after-open, end-before-close)"
                  f" first 40: {offs[:40]}\n")
    TR.remove_dir(run.trace_dir)
    print("TRACE DUMP", path, flush=True)


def control(cell, seeds, seconds):
    from harness import check, runner, spec
    ref = spec.load_reference(cell.config)
    for seed in seeds:
        t = time.perf_counter()
        run, eng = runner.run(cell, seed, seconds, False, log=lambda *a: None)
        del eng
        gc.collect()
        recs = check.sample(run, int(cell.traffic["check_requests"]), seed)
        g = check.logit_gaps(ref, cell.config["model"], seed, recs,
                             precisions=("f32", "fp8"))
        ld = check.ladder(run)
        print("CONTROL", json.dumps({"cell": cell.name, "seed": seed,
                                     "program_f32_gap": g["f32"],
                                     "fp8_gap": g["fp8"], "ladder": ld,
                                     "compiles_in_window": run.compiles_in_window,
                                     "secs": time.perf_counter() - t}),
              flush=True)
        del run


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--sims", action="store_true")
    ap.add_argument("--trace-dump", default="")
    ap.add_argument("--control", default="")
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args()
    import run as R
    from harness import spec
    R.enable_cache()
    cell = spec.load_cell(ROOT, args.cell)
    if args.sims:
        sims(cell, args.seed)
    if args.trace_dump:
        trace_dump(cell, args.seed, args.seconds, args.trace_dump)
    if args.control:
        control(cell, [int(s) for s in args.control.split(",")],
                args.seconds)


if __name__ == "__main__":
    main()
