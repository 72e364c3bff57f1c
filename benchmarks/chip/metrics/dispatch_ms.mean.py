"""Mean time of the runtime's ``runtime.dispatch`` span (the served
forward's call: enqueue and the copy of the inputs to the device), over
the requests dispatched in the window (the program's request log)."""
import numpy as np

import program


def read(run):
    rows = program.requests(run.window)
    if rows is None or not len(rows["rid"]):
        return None
    return float(np.mean(rows["dispatch"] - rows["pad"]) * 1e3)
