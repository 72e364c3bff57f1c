"""Operations and bytes of one DLRM forward call, from shapes and ids alone.

The counts are what the algorithm needs, whatever implements it:

* flops per item: 2 * fan_in * fan_out + fan_out (bias) per MLP layer;
  ``n_tables * (hotness - 1) * embed_dim`` adds to pool the bags; the dot
  interaction as the product of the (R, D) feature rows with themselves,
  ``2 * R * R * D`` with ``R = n_tables + 1``;
* bytes per call at bucket ``b``: every distinct embedding row the call's
  ids name, once (``embed_dim * 4`` bytes each; a row named twice need not
  be read twice, so no gather, however it is built, needs fewer), the ids
  and the dense inputs of the ``b`` rows, every MLP weight and bias once,
  and the ``b`` outputs (all float32 / int32).
"""
from __future__ import annotations

import numpy as np


def _mlp(d_in: int, widths) -> tuple[int, int]:
    """(flops per row, parameters) of an MLP stack."""
    flops = params = 0
    for w in widths:
        flops += 2 * d_in * w + w
        params += d_in * w + w
        d_in = w
    return flops, params


def _interaction_width(cfg: dict) -> int:
    r = cfg["n_tables"] + 1
    return r * (r - 1) // 2 + cfg["dense_fc"][-1]


def flops_per_item(cfg: dict) -> int:
    """Forward operations for one candidate item."""
    bottom, _ = _mlp(cfg["n_dense"], cfg["dense_fc"])
    top, _ = _mlp(_interaction_width(cfg), cfg["predict_fc"])
    f, h, d = cfg["n_tables"], cfg["hotness"], cfg["embed_dim"]
    r = f + 1
    return bottom + f * (h - 1) * d + 2 * r * r * d + top


def weight_bytes(cfg: dict) -> int:
    """Bytes of every MLP weight and bias (float32)."""
    _, bottom = _mlp(cfg["n_dense"], cfg["dense_fc"])
    _, top = _mlp(_interaction_width(cfg), cfg["predict_fc"])
    return 4 * (bottom + top)


def distinct_rows(sparse: np.ndarray) -> int:
    """Distinct (table, id) pairs among the ids ``sparse`` (rows, F, H)."""
    f = sparse.shape[1]
    keys = (sparse.astype(np.int64)
            + (np.arange(f, dtype=np.int64) << 32)[None, :, None])
    return len(np.unique(keys))


def call_flops(cfg: dict, bucket: int) -> int:
    """Operations of one forward call at ``bucket`` rows."""
    return bucket * flops_per_item(cfg)


def call_bytes(cfg: dict, bucket: int, rows_gathered: int) -> int:
    """Bytes one forward call at ``bucket`` rows that names
    ``rows_gathered`` distinct embedding rows has to move."""
    f, h, d = cfg["n_tables"], cfg["hotness"], cfg["embed_dim"]
    per_row = 4 * (f * h + cfg["n_dense"] + 1)
    return 4 * d * rows_gathered + bucket * per_row + weight_bytes(cfg)


def least_seconds(cfg: dict, bucket: int, rows_gathered: int,
                  peak: dict) -> float:
    """The least time the chip could take for one call: the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    return max(call_flops(cfg, bucket) / peak["flops_per_s"],
               call_bytes(cfg, bucket, rows_gathered)
               / peak["hbm_bytes_per_s"])
