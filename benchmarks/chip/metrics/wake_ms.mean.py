"""Mean time from the end of a request's forward execution on the device to
the end of its ``runtime.device_wait`` span (the worker back from
``block_until_ready``: the completion's notice and the thread's wake),
over the traced requests.  The request log's stamps are put on the
trace's clock by one offset fitted on the traced calls, and the device's
plane on the host's by the least shift that puts the executions inside
the waits (``program.device_shift``), so it reads high by at most the
shortest launch delay."""
import numpy as np

import program


def read(run):
    got = program.aligned(run)
    if got is None:
        return None
    rows, off, shift = got
    iv = program.waits(rows, off)
    iv = iv[np.argsort(iv[:, 0])]
    wakes = []
    for e in run.window.profile.forward:
        end = e.end_ns + shift
        k = int(np.searchsorted(iv[:, 0], e.start_ns + shift, "right")) - 1
        if k >= 0 and end <= iv[k, 1]:         # inside its request's wait
            wakes.append(iv[k, 1] - end)
    return float(np.mean(wakes) * 1e-6) if wakes else None
