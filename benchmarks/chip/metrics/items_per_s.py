"""Candidate items of the queries completed inside the window, divided by
the window's seconds."""
import numpy as np


def read(run):
    w = run.window
    done = ~np.isnan(w.done()) & (w.done() <= w.seconds)
    return float(w.sched.sizes[done].sum()) / w.seconds
