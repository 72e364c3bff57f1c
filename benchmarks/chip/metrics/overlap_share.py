"""Share of requests picked up while the same worker's previous request
was still in flight: with each worker's requests in the order of their
``dispatch`` stamps, those whose ``pickup`` comes before the previous
request's ``ready``, over the requests that have a previous one (the
program's request log, rows dispatched in the window).  A worker that
waits for each result before it takes the next request reads 0."""
import numpy as np

import program


def read(run):
    rows = program.requests(run.window)
    if rows is None:
        return None
    hits = total = 0
    for w in np.unique(rows["worker"]):
        mine = rows["worker"] == w
        order = np.argsort(rows["dispatch"][mine])
        pickup = rows["pickup"][mine][order]
        ready = rows["ready"][mine][order]
        hits += int(np.sum(pickup[1:] < ready[:-1]))
        total += len(order) - 1
    return 100.0 * hits / total if total else None
