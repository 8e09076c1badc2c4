# One function per paper table. Print ``name,us_per_call,derived`` CSV.
#
#   fig2a  recognition-latency reduction vs network conditions  (paper Fig 2a)
#   fig2b  3D-model load-latency reduction vs size              (paper Fig 2b)
#   cache_lookup  edge-lookup throughput                        (paper §2 hot spot)
#   hit_rate      hit rate vs threshold tau                     (paper §2 threshold)
#   roofline      per-(arch x shape) roofline terms             (scale requirement)
#   obs_overhead  traced-vs-untraced serving throughput         (docs/observability.md)
#
# --trace-out / --metrics-out route the obs_overhead suite's traced run
# into a Chrome trace-event JSON (load in Perfetto / chrome://tracing)
# and a metrics-registry snapshot.
from __future__ import annotations

import argparse
import functools
import sys
import traceback


def main(argv=None) -> None:
    from benchmarks import (ann_probe, block_reuse, cache_lookup, churn,
                            cooperative_hit_rate, federated_hit_rate,
                            frame_deadline, hit_rate, kv_reuse, load_latency,
                            obs_overhead, recognition_latency, roofline)
    from repro.launch.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--trace-out", default="",
                    help="export the obs_overhead traced run's Chrome "
                         "trace-event JSON here")
    ap.add_argument("--metrics-out", default="",
                    help="export the obs_overhead traced run's metrics "
                         "registry snapshot here")
    args = ap.parse_args(argv)
    enable_compile_cache()

    suites = [
        ("fig2a", recognition_latency.run),
        ("fig2b", load_latency.run),
        ("cache_lookup", cache_lookup.run),
        ("hit_rate", hit_rate.run),
        ("cooperative_hit_rate", cooperative_hit_rate.run),
        ("cooperative_batched", cooperative_hit_rate.run_batched),
        ("federated_hit_rate", federated_hit_rate.run_smoke),
        # also writes BENCH_churn.json; nightly asserts the acceptance row
        ("churn", churn.run_smoke),
        # also writes BENCH_ann_probe.json; nightly asserts ann_accept
        ("ann_probe", ann_probe.run_smoke),
        ("frame_deadline", frame_deadline.run_smoke),
        # also writes the BENCH_kv_reuse.json perf record to the repo root
        ("kv_reuse", kv_reuse.run_smoke),
        ("block_reuse", block_reuse.run),
        ("roofline", roofline.run),
        # also writes BENCH_obs_overhead.json (+ optional trace/metrics)
        ("obs_overhead", functools.partial(obs_overhead.run_smoke,
                                           trace_path=args.trace_out,
                                           metrics_path=args.metrics_out)),
    ]
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in suites:
        try:
            for row in fn():
                print(",".join(str(x) for x in row), flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{name},NaN,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
