#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (configuration and traffic) is found by name in ``BENCHMARK.json``
at the checkout root.  The run makes its weights and traffic from
``--seed``, warms up every shape the traffic uses, measures for
``--seconds``, drains, frees the engine and checks what the window
produced against the float32 reference.  With ``--trace 1`` the window
runs under the JAX profiler and the per-layer metrics are reported in
place of the end-to-end ones.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``breakdown`` when
traced, and ``checks``: each compared number beside its limit); the
checks are also the last lines of standard error.  Exits non-zero,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(ROOT / "src"))
sys.path.insert(0, str(HERE))


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


def enable_cache() -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache/`` at the checkout root (a fixed path:
    the path is part of the key)."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, bench_dir: Path = HERE, require_chip=True,
             log=_log, t_start: float = T_START):
    """One run; returns the result object (``None`` where no chip)."""
    import jax

    import repro  # noqa: F401  (the program under test: absent, no run)
    from harness import check, report, roofline, runner, spec
    from harness import trace_reduce as TR

    cell = spec.load_cell(root, workload, bench_dir)
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            log(f"no TPU: JAX found {devices[0].platform}")
            return None
        if len(devices) < cell.chips:
            log(f"{workload} needs {cell.chips} chips, JAX found "
                f"{len(devices)}")
            return None
    peak = (roofline.peaks(devices[0].device_kind) if require_chip
            else roofline.PEAKS["TPU v5 lite"])
    readers = spec.per_layer_readers(cell, bench_dir) if trace else {}
    ref = spec.load_reference(cell.config, bench_dir)

    run, eng = runner.run(cell, seed, seconds, trace, log=log,
                          t_start=t_start)
    closed = cell.traffic["loop"] == "closed"
    e2e = report.end_to_end(run, closed)
    ld = check.ladder(run)
    del eng
    gc.collect()

    metrics, breakdown, dev_extra = {}, None, {}
    if trace:
        t = time.perf_counter()
        devs, spans, win = TR.load_dir(run.trace_dir)
        red = TR.reduce(devs, spans, *win)
        ctx = report.Context(run, red, cell.config, peak)
        for m in cell.per_layer:
            v = readers[m["name"]](ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        breakdown = red.breakdown()
        dev_extra = {"busy_s": red.busy_s, "window_s": red.window_s}
        TR.remove_dir(run.trace_dir)
        log(f"trace: {sum(map(len, devs))} device ops, {len(spans)} host "
            f"spans, read in {time.perf_counter() - t:.1f} s")
    else:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    recs = check.sample(run, int(cell.traffic["check_requests"]), seed)
    t = time.perf_counter()
    gap = (check.logit_gaps(ref, cell.config["model"], seed, recs)["f32"]
           if recs else None)
    ref_s = time.perf_counter() - t
    limits = cell.config["check"]
    checks = {
        "logit_gap": {"value": gap, "limit": limits["logit_gap_limit"]},
        "ladder_disagreements": {"value": ld["disagree"], "limit": 0},
        "payload_mismatches": {"value": ld["mismatch"], "limit": 0},
        "never_served": {"value": ld["never"], "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())
    attempted = sum(r.in_window for r in run.records)
    late = run.late_s
    log(f"setup: {json.dumps({k: round(v, 3) for k, v in run.setup.items()})}")
    log(f"compiles in window: {run.compiles_in_window} {run.compile_names[:8]}")
    log(f"generator late: max {max(late, default=0.0) * 1e3:.1f} ms, "
        f"p95 {report.p95(late) * 1e3 if late else 0.0:.1f} ms over "
        f"{len(late)} submissions")
    log(f"hit/miss disagreements: {ld['disagree']}")
    log(f"counters: {json.dumps(run.counters)}")
    log(f"reference: {len(recs)} requests, "
        f"{sum(len(r.tokens) for r in recs)} served tokens, {ref_s:.1f} s")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    d0 = devices[0]
    return {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(ld["never"]), "metrics": metrics,
        "device": {"platform": d0.platform, "kind": d0.device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": run.memory_peak_bytes, **dev_extra},
        **({"breakdown": breakdown} if breakdown else {}),
        "checks": checks,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    enable_cache()
    res = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if res is None:
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
