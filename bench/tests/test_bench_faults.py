"""A run whose timed path is broken must come out not correct; so must the
control, the program's own uncorrected-Mitchell rung served in place of the
configured arithmetic. Smoke preset, interpret kernels, 2-second windows;
the limit is the cell's rehearsal limit (bench/limits)."""
import json

import pytest

from test_bench_rehearsal import bench, last_line

ARGS = ["--workload", "smollm-360m.decode", "--seed", "2147483712",
        "--seconds", "2", "--trace", "0", "--rehearse"]


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "altered_token"])
def test_broken_step_is_not_correct(fault):
    out = last_line(bench(fault, "--", *ARGS, script="bench/tests/faults.py"))
    assert out["correct"] is False
    chk = out["checks"]["mean_gap"]
    assert chk["value"] > chk["limit"]


@pytest.mark.parametrize("fault", ["stale_state", "half_batch",
                                   "admit_stale", "admit_half_batch",
                                   "admit_altered_token"])
def test_broken_admission_is_not_correct(fault):
    """The prefill cell: decode faults and faults in the admission path."""
    args = list(ARGS)
    args[1] = "qwen3-4b.prefill"
    out = last_line(bench(fault, "--", *args, script="bench/tests/faults.py"))
    assert out["correct"] is False
    chk = out["checks"]["mean_gap"]
    assert chk["value"] > chk["limit"]


@pytest.mark.parametrize("cell", ["smollm-360m.decode", "qwen3-4b.prefill"])
def test_control_is_not_correct(cell):
    args = list(ARGS)
    args[1] = cell
    out = last_line(bench(*args, "--control", "mitchell"))
    assert out["correct"] is False
    chk = out["checks"]["mean_gap"]
    assert chk["value"] > chk["limit"]
    assert json.dumps(out)
