"""Least time of all the window's counted work (bench/work.py) over the
device's busy time: the share of its roofline the whole device work runs
at, whatever implements it."""
from bench import work


def read(run):
    t = run.trace
    if t is None or run.peak is None or t.busy_s <= 0:
        return None
    s = work.sizes(run.sizes)
    least = work.window_least_seconds(s, run.window, run.mix, run.peak)
    return 100.0 * least / t.busy_s
