"""The served forward's share of its roofline, in percent: over the traced
calls, the least time the chip could take for each (the larger of its
operations over peak FLOP/s and its bytes over peak bytes/s, from
``costs.py`` and the cell's model module, at the bucket it ran and with
the pool rows it carried), summed, over the device time of those calls'
executions."""
import costs
import traffic


def read(run):
    p = run.window.profile
    if p is None or not run.peak:
        return None
    pairs = p.paired_forward()
    device = sum(e.end_ns - e.start_ns for _, e in pairs) * 1e-9
    if device <= 0:
        return None
    calls = {c.index: c for c in run.window.calls}
    least = 0.0
    for span, _ in pairs:
        c = calls[int(span.stats["call"])]
        rows, ok = traffic.pool_rows(run.window.sched, c.tags)
        inputs = {k: v[rows[ok]] for k, v in run.pool.items()}
        least += costs.least_seconds(run.model, run.cfg, c.bucket, inputs,
                                     run.peak)
    return 100.0 * least / device
