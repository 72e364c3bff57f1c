"""Mean host time inside the served forward's call (dispatch and the copy
of the inputs to the device; the call does not wait for the result), over
the calls dispatched in the window."""
import numpy as np


def read(run):
    calls = run.window.calls_in_window()
    return float(np.mean([c.t1 - c.t0 for c in calls]) * 1e3) \
        if calls else None
