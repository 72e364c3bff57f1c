"""The whole step's share of the chip's peak, in percent: the items the
traced calls served times the model's operations per item (the cell's
model module), over the traced stretch's seconds times the chip's peak
FLOP/s."""


def read(run):
    p = run.window.profile
    if p is None or not run.peak or p.window_s <= 0:
        return None
    items = sum(int(s.stats["rows"]) for s, _ in p.paired_forward())
    if not items:
        return None
    return 100.0 * items * run.model.flops_per_item(run.cfg) / (
        p.window_s * run.peak["flops_per_s"])
