"""Everything the harness finds by name: the benchmark file, a cell, its
configuration, traffic mix and limits, and the metric readers.

A cell named in ``BENCHMARK.json`` resolves to

* ``bench/configs/<config>.json``   sizes, source and arithmetic,
* ``bench/traffic/<traffic>.json``  the mix the generator reads,
* ``bench/limits/<cell>.json``      what ``correct`` compares against,
* ``bench/metrics/<metric>.py``     one reader per metric, ``read(run)``.

Adding a configuration, a mix or a metric is adding files and entries; no
file here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise SystemExit(f"unknown workload {name!r}; known: "
                     f"{[w['name'] for w in bm['workloads']]}")


def config(name: str) -> dict:
    return load_json(BENCH / "configs" / f"{name}.json")


def traffic(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits(cell_name: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell_name}.json")


def metrics_for(bm: dict, cell_name: str, traced: bool) -> list[dict]:
    """The cell's end-to-end metrics (untraced run) or per-layer metrics
    (traced run): those without a ``workloads`` key, and those that list
    the cell."""
    group = bm["per_layer"] if traced else bm["end_to_end"]
    return [m for m in group
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(metric: str):
    """``read(run)`` from ``bench/metrics/<metric>.py``."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks(device_kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise SystemExit(f"no peaks for device kind {device_kind!r} in "
                         f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]
