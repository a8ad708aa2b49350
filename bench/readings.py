#!/usr/bin/env python3
"""The numbers a cell's limit is set from, many seeds in one process.

    python bench/readings.py --workload <cell> --seeds 1,2,3 --seconds <s>
        [--control mitchell] [--rehearse]

Each seed is one window of the cell's own traffic through the same
``serve`` path as ``bench/run.py`` (set-up, window, check), so the reading
is what a run of that seed would compare. One JSON line per seed on stdout:
``{"seed", "max_gap", "mean_gap", "compared", "requests", "out_tok_s"}``. The
benchmark's own runs never run this; it is how the lower reading (sound
runs over a dozen seeds) and the upper reading (the control: the program's
own uncorrected-Mitchell rung served in place of the configured one) of
``bench/limits/<cell>.json`` were read. The activation scale the reference
uses is the mix's ``check.scale`` (``bench/check.py``).
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> list:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default=None, choices=("mitchell",))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    from bench import run as bench_run

    c = bench_run.prepare(args.workload, rehearse=args.rehearse,
                          control=args.control)
    out = []
    for seed in (int(s) for s in args.seeds.split(",")):
        r = bench_run.serve(c, seed, args.seconds)
        line = {"seed": seed, "max_gap": r.max_gap, "mean_gap": r.mean_gap,
                "compared": r.compared,
                "requests": r.requests_compared,
                "out_tok_s": r.window.tokens() / r.window.seconds,
                "control": args.control,
                "scale": c.mix.check.get("scale", "row")}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


if __name__ == "__main__":
    main()
