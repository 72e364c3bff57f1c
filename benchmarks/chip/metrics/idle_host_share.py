"""Share of the traced stretch, in percent, in which no operation ran on
the device and no request's ``runtime.device_wait`` was open: the idle
time the host causes, over the same stretch ``device_idle_share`` divides
by.  The request log's stamps are put on the trace's clock by one offset
fitted on the traced calls, and the device's idle gaps on the host's by
the least shift that puts the executions inside the waits
(``program.device_shift``)."""
import program


def read(run):
    got = program.aligned(run)
    p = run.window.profile
    if got is None or p.window_ns[1] <= p.window_ns[0]:
        return None
    rows, off, shift = got
    gaps = [(a + shift, b + shift) for a, b, _ in p.gaps]
    host = sum(b - a for a, b in gaps) - program.covered_ns(
        gaps, program.waits(rows, off)).sum()
    return 100.0 * host / (p.window_ns[1] - p.window_ns[0])
