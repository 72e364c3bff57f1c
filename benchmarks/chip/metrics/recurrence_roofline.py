"""The two recurrences' share of their roofline, in percent: the least
time of the GRU and AUGRU per traced call, over their device time
per execution of the forward (``recurrence_device_ms.mean``'s ops).  A
call's least time is the larger of the model's
``recurrence_flops_per_item`` times the call's bucket over peak FLOP/s and
its ``recurrence_bytes`` at that bucket over peak bytes/s
(``models/<model>.py``), averaged over the traced calls.  A model with no
recurrence reads nothing."""
import os

import harness

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEVICE = harness._load(HERE, "metrics", "recurrence_device_ms.mean")


def read(run):
    p = run.window.profile
    model = run.model
    if p is None or not run.peak \
            or not hasattr(model, "recurrence_flops_per_item"):
        return None
    pairs = p.paired_forward()
    ns = DEVICE.recurrence_ns(run)
    if not pairs or ns is None:
        return None
    least = sum(max(int(s.stats["bucket"])
                    * model.recurrence_flops_per_item(run.cfg)
                    / run.peak["flops_per_s"],
                    model.recurrence_bytes(run.cfg, int(s.stats["bucket"]))
                    / run.peak["hbm_bytes_per_s"]) for s, _ in pairs)
    return 100.0 * (least / len(pairs)) / (ns * 1e-9 / len(p.forward))
