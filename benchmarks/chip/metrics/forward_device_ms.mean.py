"""Mean device time of one execution of the served forward (the
``jit_forward`` module), over its executions in the trace."""
import numpy as np


def read(run):
    p = run.window.profile
    if p is None or not p.forward:
        return None
    return float(np.mean([s.end_ns - s.start_ns for s in p.forward]) * 1e-6)
