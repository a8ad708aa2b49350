#!/usr/bin/env python3
"""The chip benchmark: one cell of ``BENCHMARK.json`` per run.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's configuration and scheduler, warms up every program the
window drives (set-up), serves the cell's traffic through
``Scheduler.step()`` for ``--seconds``, checks the served tokens against the
plain reference, and prints one JSON line last on stdout. ``--trace 0``
reports the cell's end-to-end metrics; ``--trace 1`` profiles the window and
reports its per-layer metrics, with ``device.busy_s``/``window_s`` and a
``breakdown``. Without a TPU (or with fewer chips than the cell asks for) it
exits 3 and prints no result.

``--rehearse`` runs the same path on the CPU at the program's smoke preset
with interpret-mode kernels: a test of the harness, not a measurement.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: requests generated per run: more than any window consumes
REQUESTS = 8192


@dataclass
class Cell:
    """A cell resolved from its files, and the program's config for it."""
    bm: dict
    cell: dict
    conf: dict
    mix: object
    limit: float
    cfg: object
    sizes: dict
    rehearse: bool


@dataclass
class Run:
    """One served window and its check: what the metric readers read."""
    cell: dict
    mix: object
    sizes: dict
    window: object
    setup_s: float
    peak: dict | None
    trace: object | None
    memory_peak_bytes: int
    max_gap: float | None
    mean_gap: float | None   # the number compared
    compared: int
    requests_compared: int


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, smoke preset, interpret-mode kernels")
    ap.add_argument("--control", default=None, choices=("mitchell",),
                    help="serve the program's coarser rung in place of the "
                         "configured one (the check must then fail)")
    return ap.parse_args(argv)


def fail(msg: str, code: int = 3):
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def prepare(workload: str, *, rehearse: bool, control: str | None) -> Cell:
    """Resolve the cell, check the device, and build the served config."""
    try:
        import jax
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        fail(f"cannot import the program under test ({e}): run from the "
             "root of a checkout", 2)
    from bench import program, spec, traffic

    bm = spec.benchmark()
    cell = spec.cell(bm, workload)
    conf = spec.config(cell["config"])
    mix = traffic.Mix.from_json(cell["traffic"],
                                spec.traffic(cell["traffic"]))
    lim = spec.limits(cell["name"])
    limit = lim["rehearsal"]["limit"] if rehearse else lim["mean_gap"]["limit"]
    devs = jax.devices()
    if not rehearse:
        if devs[0].platform != "tpu":
            fail(f"no TPU found (JAX platform {devs[0].platform!r})")
        if len(devs) < cell["chips"]:
            fail(f"cell needs {cell['chips']} chips, found {len(devs)}")
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    cfg = program.model_config(conf, rehearse=rehearse,
                               backend="pallas" if rehearse else "auto",
                               control=control)
    return Cell(bm, cell, conf, mix, limit, cfg,
                program.reference_sizes(conf, cfg), rehearse)


def serve(c: Cell, seed: int, seconds: float, *, traced: bool = False,
          t_start: float | None = None) -> Run:
    """Set up, serve one window, read the device, free the program's state,
    and check the served tokens against the reference."""
    import jax
    import numpy as np
    from bench import check, program, spec, traffic, window
    from bench import trace as tracing

    t_start = time.perf_counter() if t_start is None else t_start
    weights = program.make_weights(c.sizes, seed)
    jax.block_until_ready(weights)
    sched, _ = program.scheduler(
        c.cfg, weights, c.mix,
        kernel_backend="pallas-interpret" if c.rehearse else "pallas-tpu")
    window.warm_up(sched, c.mix, c.cfg.vocab_size)
    pending = deque(traffic.requests(c.mix, c.cfg.vocab_size, seed,
                                     REQUESTS))
    setup_s = time.perf_counter() - t_start

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        win = window.measure(sched, c.mix, pending, seconds,
                             annotate=jax.profiler.TraceAnnotation
                             if trace_dir else None)
    if trace_dir:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    stats_fn = getattr(dev, "memory_stats", None)
    mem = (stats_fn() if stats_fn else None) or {}

    t_after = time.perf_counter()
    reduced = None
    if trace_dir:
        try:
            reduced = tracing.reduce(tracing.find_xplane(trace_dir))
        except RuntimeError as e:
            if not c.rehearse:
                raise
            print(f"bench: rehearsal trace not reduced: {e}",
                  file=sys.stderr)
        shutil.rmtree(trace_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    del sched
    gc.collect()
    t_ref = time.perf_counter()
    picked = check.sample(win.finished(), c.mix.check, seed, c.mix.batch)
    g = np.concatenate(check.gaps(c.conf, c.sizes, weights, picked,
                                  c.mix.check, c.mix.batch)) \
        if picked else None
    max_gap, mean_gap, n_cmp = \
        (float(g.max()), float(g.mean()), int(g.size)) if g is not None \
        else (None, None, 0)
    print(f"bench: seed {seed}: set-up {setup_s:.1f} s, window "
          f"{win.seconds:.3f} s ({len(win.ticks)} ticks, {win.tokens()} "
          f"tokens, {len(win.finished())} requests finished), trace "
          f"reduction {t_ref - t_after:.1f} s, reference "
          f"{time.perf_counter() - t_ref:.1f} s over {n_cmp} served tokens "
          f"of {len(picked)} requests, widest gap {max_gap}; "
          f"{win.tick_summary()}; compiles in the window: {win.compiles}",
          file=sys.stderr, flush=True)
    return Run(cell=c.cell, mix=c.mix, sizes=c.sizes, window=win,
               setup_s=setup_s,
               peak=None if c.rehearse else spec.peaks(dev.device_kind),
               trace=reduced,
               memory_peak_bytes=int(mem.get("peak_bytes_in_use", 0)),
               max_gap=max_gap, mean_gap=mean_gap, compared=n_cmp,
               requests_compared=len(picked))


def result(c: Cell, run: Run, traced: bool) -> dict:
    """The result line: metrics read by their readers, the device, and the
    numbers compared with their limits (last)."""
    import jax
    from bench import spec

    win = run.window
    attempted = sum(s.req.started >= 0 or s.req.failed
                    for s in win.served.values())
    failed = sum(s.req.failed for s in win.served.values()) + win.faults
    correct = run.mean_gap is not None and failed == 0 and \
        run.mean_gap <= c.limit
    metrics = {}
    for m in spec.metrics_for(c.bm, c.cell["name"], traced):
        value = spec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.ops,
                            "idle_gaps": run.trace.gaps}
    if c.rehearse:
        out["rehearsal"] = True
    out["checks"] = {"mean_gap": {"value": run.mean_gap, "limit": c.limit},
                     "failed": {"value": failed, "limit": 0}}
    return out


def main(argv=None) -> dict:
    args = parse(argv)
    c = prepare(args.workload, rehearse=args.rehearse, control=args.control)
    run = serve(c, args.seed, args.seconds, traced=bool(args.trace),
                t_start=T_START)
    out = result(c, run, bool(args.trace))
    for name, chk in out["checks"].items():
        print(f"check {name} {chk['value']} limit {chk['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
