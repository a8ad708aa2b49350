"""Rehearsals of whole runs on the CPU: the smoke preset, interpret-mode
kernels, a 2-second window."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def bench(*args, cwd=ROOT, script="bench/run.py", timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_ENABLE_X64", None)
    p = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=timeout)
    return p


def last_line(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def benchmark():
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


@pytest.mark.parametrize("cell, traced", [("smollm-360m.decode", 0),
                                          ("qwen3-4b.prefill", 1)])
def test_rehearsal_prints_the_result_line(cell, traced):
    out = last_line(bench("--workload", cell, "--seed", "2147483711",
                          "--seconds", "2", "--trace", str(traced),
                          "--rehearse"))
    assert all(k in out for k in KEYS)
    assert list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and out["rehearsal"] is True
    bm = benchmark()
    group = bm["per_layer"] if traced else bm["end_to_end"]
    allowed = {m["name"] for m in group
               if cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) <= allowed
    if not traced:
        assert set(out["metrics"]) == allowed
    for m in out["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    chk = out["checks"]["mean_gap"]
    assert chk["value"] <= chk["limit"]


def test_off_the_chip_without_rehearsal_no_result():
    p = bench("--workload", "smollm-360m.decode", "--seed", "1",
              "--seconds", "2", "--trace", "0")
    assert p.returncode != 0
    assert not p.stdout.strip()
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_are_not_enough(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = bench("--workload", "smollm-360m.decode", "--seed", "1",
              "--seconds", "2", "--trace", "0", "--rehearse", cwd=tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()
