"""Find a cell's pieces by name.

``BENCHMARK.json`` at the checkout root names each cell's configuration
and traffic; the files are found from those names alone:

    configs/<config>.json     sizes, serving knobs, correctness limits
    traffic/<traffic>.json    the mix's parameters
    metrics/<metric>.py       one reader per per-layer metric

so a new cell, mix or metric is a new file and a new entry, and no file
that is already there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parents[1]          # benchmarks/onchip


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]       # the cell's end-to-end metric entries
    per_layer: List[dict]        # the cell's per-layer metric entries


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str, bench_dir: Path = HERE) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    bench = json.loads((Path(root) / "BENCHMARK.json").read_text())
    wl = next((w for w in bench["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = json.loads((Path(root) / cfg["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in names]
    return Cell(name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def load_reader(name: str, bench_dir: Path = HERE):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    path = bench_dir / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "onchip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_reference(config: dict, bench_dir: Path = HERE):
    """The plain reference module a configuration names."""
    path = bench_dir / "configs" / f"{config['reference']}.py"
    spec = importlib.util.spec_from_file_location(
        "onchip_reference_" + config["reference"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def per_layer_readers(cell: Cell, bench_dir: Path = HERE) -> Dict[str, object]:
    return {m["name"]: load_reader(m["name"], bench_dir)
            for m in cell.per_layer}
