"""Share of the traced stretch in which no operation ran on the device, in
percent: 1 - union of device operation intervals / traced stretch."""


def read(run):
    p = run.window.profile
    if p is None or p.window_s <= 0:
        return None
    return 100.0 * (1.0 - p.busy_s / p.window_s)
