"""A tiny cell of the on-chip benchmark that runs on the CPU: the same
harness, engine and reference as the chip's cells at a width a test can
hold, found by name from a BENCHMARK.json and data files written to a
temporary tree, as a later PR adds a cell."""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
BENCH = REPO / "benchmarks" / "onchip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY_MODEL = {"name": "tiny", "family": "vlm", "num_layers": 2, "d_model": 128,
              "num_heads": 4, "num_kv_heads": 2, "head_dim": 32, "d_ff": 256,
              "vocab_size": 512, "mlp_kind": "gated_silu", "rope_theta": 10000.0,
              "norm_eps": 1e-05, "num_image_patches": 16,
              "tie_embeddings": False, "qkv_bias": False, "dtype": "bfloat16"}
TINY_SERVING = {"slots": 4, "max_len": 128, "kv_page": 16, "kv_pages": 32,
                "prefill_chunk": 32, "max_new_tokens": 16,
                "attn_impl": "paged_interpret"}
TINY_HITS = {"loop": "open", "users": 8, "nodes": 4, "rate_per_s": 16.0,
             "image_tokens": 16,
             "text_len": {"dist": "lognormal", "min": 4, "max": 32,
                          "median": 8, "sigma": 0.8},
             "hot_scenes": 8, "zipf_s": 0.9, "hot_share": 0.8,
             "max_submit_per_step": 4, "check_requests": 6}
TINY_BACKLOG = {"loop": "closed", "clients_per_slot": 2, "nodes": 4,
                "image_tokens": 0,
                "text_len": {"dist": "loguniform", "min": 16, "max": 64},
                "max_requests": 2000, "ramp_completions": 2,
                "max_submit_per_step": 1, "check_requests": 2}
# set from CPU readings of this tiny cell, seeds 1-8 and 2**31 + 5: sound
# runs read at most 4.2e-3, the float8 control at least 2.7e-2
TINY_GAP_LIMIT = 0.01


def load_run():
    spec = importlib.util.spec_from_file_location("onchip_run",
                                                  BENCH / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def tiny_tree(tmp: Path, traffic: dict = None, name: str = "tiny-hits"):
    """A checkout-like tree: BENCHMARK.json at ``tmp`` naming the repo's
    cells plus ``tiny.<name>``, and a copy of the benchmark's data files
    (configs, traffic, metrics) with the tiny configuration and mix added.
    Returns (root, bench_dir, workload)."""
    bench = tmp / "bench"
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(BENCH / sub, bench / sub)
    cfg = json.loads((BENCH / "configs" / "llava-next-34b-8l.json").read_text())
    cfg.update(name="tiny", model=TINY_MODEL, serving=TINY_SERVING,
               check={"logit_gap_limit": TINY_GAP_LIMIT})
    cfg["coic"] = dict(cfg["coic"], capacity=64, threshold=0.999,
                       lookup_impl="ref")
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    (bench / "traffic" / f"{name}.json").write_text(
        json.dumps(traffic or TINY_HITS))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "tests", "file":
                         "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU test cell"})
    wl = f"tiny.{name}"
    b["workloads"].append({"name": wl, "config": "tiny", "traffic": name,
                           "chips": 1, "why": "CPU test cell"})
    # the tiny cell reports what the repo's cell of the same loop reports
    like = next(w["name"] for w in b["workloads"]
                if json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                              .read_text())["loop"] == (traffic or TINY_HITS)["loop"])
    for m in b["end_to_end"] + b["per_layer"]:
        if like in m.get("workloads", []):
            m["workloads"].append(wl)
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp, bench, wl


def run_tiny(tmp: Path, seed: int, trace: bool = False, seconds: float = 2.0,
             traffic: dict = None, name: str = "tiny-hits"):
    root, bench, wl = tiny_tree(tmp, traffic, name)
    return load_run().run_cell(wl, seed, seconds, trace, root=root,
                               bench_dir=bench, require_chip=False,
                               log=lambda *a: None)
