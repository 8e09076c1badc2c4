"""The on-chip benchmark end to end at a tiny width on the CPU, found by
name: a new traffic file and new BENCHMARK.json entries make a new cell,
with no existing file edited.  And the command refuses to run without a
TPU, or without the program beside it."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import bench_tiny as BT


def _digest(root):
    return {p.relative_to(root).as_posix():
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_traffic_file_makes_a_new_cell(tmp_path):
    before = _digest(BT.BENCH)
    res = BT.run_tiny(tmp_path, seed=2 ** 31 + 3)
    assert _digest(BT.BENCH) == before
    assert res["correct"] and res["failed"] == 0 and res["attempted"] == 32
    assert set(res["metrics"]) == {"hit_p95_ms", "ttft_p95_ms", "setup_s"}
    for m in res["metrics"].values():
        assert m["value"] > 0
    assert list(res)[-1] == "checks"
    assert res["checks"]["ladder_disagreements"] == {"value": 0, "limit": 0}
    assert res["checks"]["logit_gap"]["value"] <= BT.TINY_GAP_LIMIT
    assert res["device"]["platform"] == "cpu"


def test_closed_loop_cell_keeps_every_slot_busy(tmp_path):
    res = BT.run_tiny(tmp_path, seed=9, traffic=BT.TINY_BACKLOG,
                      name="tiny-backlog")
    assert res["correct"] and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "out_tok_s", "setup_s"}
    assert res["metrics"]["out_tok_s"]["value"] > 0
    assert res["checks"]["ladder_disagreements"]["value"] == 0


def test_traced_run_reports_per_layer_metrics(tmp_path):
    res = BT.run_tiny(tmp_path, seed=5, trace=True)
    assert res["correct"]
    # host-clock readers report; device-trace readers find no device on
    # the CPU and leave their metric out
    assert set(res["metrics"]) == {"engine_step_ms.hit", "model_mfu.hit"}
    assert res["device"]["busy_s"] == 0.0 and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_every_metric_and_cell_is_found_by_name():
    bench = json.loads((BT.REPO / "BENCHMARK.json").read_text())
    assert [p for p in bench["paths"] if (BT.REPO / p).is_dir()] == \
        bench["paths"]
    for c in bench["configs"]:
        cfg = json.loads((BT.REPO / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert (BT.BENCH / "configs" / f"{cfg['reference']}.py").is_file()
    for w in bench["workloads"]:
        assert (BT.BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in bench["per_layer"]:
        ns = {}
        exec((BT.BENCH / "metrics" / f"{m['name']}.py").read_text(), ns)
        assert callable(ns["read"])
        assert (ns["UNIT"], ns["LAYER"], ns["MOVES"]) == \
            (m["unit"], m["layer"], m["moves"])


def _run_cmd(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    bench = json.loads((BT.REPO / "BENCHMARK.json").read_text())
    wl = bench["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, *bench["command"][1:], "--workload", wl,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_fails_without_a_tpu():
    r = _run_cmd(BT.REPO)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_command_fails_without_the_program(tmp_path):
    bench = json.loads((BT.REPO / "BENCHMARK.json").read_text())
    shutil.copy(BT.REPO / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(BT.REPO / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cmd(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "No module named 'repro'" in r.stderr
