"""95th percentile of how late the node's feeder released each query into
the runtime (``t_released - t_arrival``), over the queries of the window."""
import numpy as np


def read(run):
    late = [r.t_released - r.t_arrival for r in run.window.records
            if np.isfinite(r.t_released)]
    return float(np.percentile(late, 95) * 1e3) if late else None
