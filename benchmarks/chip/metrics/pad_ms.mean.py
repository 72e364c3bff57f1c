"""Mean time a request spends being padded to its bucket: from the worker's
pick-up to the end of its ``runtime.pad`` span, over the requests
dispatched in the window (the program's request log)."""
import numpy as np

import program


def read(run):
    rows = program.requests(run.window)
    if rows is None or not len(rows["rid"]):
        return None
    return float(np.mean(rows["pad"] - rows["pickup"]) * 1e3)
