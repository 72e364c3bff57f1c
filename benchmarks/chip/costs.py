"""The roofline arithmetic: the least time a chip could take for one
forward call.

What a call needs comes from the cell's model module
(``models/<model>.py``), from shapes and ids alone, whatever implements
the model: ``flops_per_item(cfg)``, the operations per candidate item,
and ``call_bytes(cfg, bucket, inputs)``, the bytes a call at ``bucket``
rows whose real rows are ``inputs`` has to move.
"""
from __future__ import annotations


def least_seconds(model, cfg: dict, bucket: int, inputs: dict,
                  peak: dict) -> float:
    """The larger of the call's operations over peak FLOP/s and its bytes
    over peak bytes/s."""
    return max(bucket * model.flops_per_item(cfg) / peak["flops_per_s"],
               model.call_bytes(cfg, bucket, inputs)
               / peak["hbm_bytes_per_s"])
