"""The correctness control, judged in the program's place by the run's own
comparison.  At a size a test run can hold, on three seeds, the reference
computed through float8 (one precision step below the configuration's
bfloat16) lands its first choices far further below the float32
reference's best than the program's served tokens do, and a limit between
the two passes the program and fails the control.  Each cell's own limit
lies between the two readings it was set from on the chip, at the cell's
own size (``chipbench/calibrate.py``), and fails the control there."""
import json
import time
from pathlib import Path

import pytest
from bench_helpers import PEAKS, tiny_cell

from chipbench import run

WORKLOADS = Path(run.ROOT) / "chipbench" / "workloads"
#: a limit between the tiny cell's two readings (program at most 0.03,
#: control at least 0.14 on these seeds)
TINY_LIMIT = 0.07


def test_control_fails_where_the_program_passes():
    program, control = [], []
    for seed in (2**31 + 11, 2**31 + 12, 2**31 + 13):
        cell = tiny_cell("qwen2_7b", TINY_LIMIT)
        cell["check"]["sample"] = 12  # some hundreds of served tokens, as on the chip
        out = run.measure(cell, seed, 0.3, False, t_start=time.perf_counter(),
                          peaks=PEAKS, control=True)
        assert out["result"]["correct"] is True
        assert out["control_correct"] is False
        assert out["control_checks"]["max_logit_gap"]["limit"] == TINY_LIMIT
        program.append(out["gaps"]["max_logit_gap"])
        control.append(out["gaps"]["control_max_logit_gap"])
    assert min(control) >= 3 * max(program), (program, control)


@pytest.mark.parametrize("cell_name", sorted(p.stem for p in WORKLOADS.glob("*.json")))
def test_each_cells_limit_fails_its_chip_control(cell_name):
    check = json.loads((WORKLOADS / f"{cell_name}.json").read_text())["check"]
    limits, readings = check["limits"], check["readings"]
    assert set(readings) == set(limits)
    for name, limit in limits.items():
        program, control = readings[name]["program"], readings[name]["control"]
        assert program < limit < control and control >= 3 * program
    gaps = {"requests": check["sample"]}
    for name in limits:
        gaps[name] = readings[name]["program"]
        gaps["control_" + name] = readings[name]["control"]
    assert run.verdict(limits, gaps, 0)[0] is True
    assert run.verdict(limits, gaps, 0, prefix="control_")[0] is False
