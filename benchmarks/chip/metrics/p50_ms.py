"""Median query latency over every query scheduled in the window, from
its scheduled arrival; a failed query counts as infinite."""
import numpy as np


def read(run):
    lat = np.sort(run.window.latencies_ms())
    pos = 0.5 * (len(lat) - 1)
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(lat[hi]):
        return float("inf")
    return float(lat[lo] + (lat[hi] - lat[lo]) * (pos - lo))
