"""95th percentile of the gaps between consecutive tokens of one request,
over every gap inside the window (numpy's linear interpolation)."""
import numpy as np


def read(run):
    gaps = [b - a for s in run.window.served.values()
            for a, b in zip(s.times, s.times[1:])]
    if len(gaps) < 200:
        return None
    return float(np.percentile(gaps, 95)) * 1e3
