"""95th percentile of how long a released query waited in the runtime's
queue before a worker first picked up one of its requests
(``t_exec_start - t_released``), over the queries of the window."""
import numpy as np


def read(run):
    wait = [r.t_exec_start - r.t_released for r in run.window.records
            if np.isfinite(r.t_released) and np.isfinite(r.t_exec_start)]
    return float(np.percentile(wait, 95) * 1e3) if wait else None
