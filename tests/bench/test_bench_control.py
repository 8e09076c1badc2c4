"""The control of the on-chip benchmark's logit check: the float32
reference put in the program's place at float8 (the precision below the
configuration's bfloat16) fails the limit that sound runs pass.  At the
tiny width of the CPU cell, against that cell's own limit; the chip
cells' readings are in PERF.md."""
import tempfile
from pathlib import Path

import pytest

import bench_tiny as BT
from harness import check, runner, spec


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_float8_control_fails_where_the_program_passes(seed):
    with tempfile.TemporaryDirectory() as tmp:
        root, bench, wl = BT.tiny_tree(Path(tmp))
        cell = spec.load_cell(root, wl, bench)
        run, eng = runner.run(cell, seed, 2.0, False, log=lambda *a: None)
        del eng
        recs = check.sample(run, int(cell.traffic["check_requests"]), seed)
        ref = spec.load_reference(cell.config, bench)
        gaps = check.logit_gaps(ref, cell.config["model"], seed, recs,
                                precisions=("f32", "fp8"))
    assert gaps["f32"] <= BT.TINY_GAP_LIMIT < gaps["fp8"]
