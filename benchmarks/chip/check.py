"""The comparison that decides ``correct``.

Every answer the window served is compared with the reference's logit
for the same pool row, the reference computed at the precision the
configuration states.  The numbers:

* ``max_gap``: the widest gap between a served logit and the reference's,
  over every served item, as a share of the reference logits' root mean
  square over the pool;
* ``rms_gap``: the root mean square of those gaps, on the same scale;
* ``unserved_items``: items of completed queries that no served call
  carried (limit 0);
* ``stray_items``: served rows whose tag names no scheduled item
  (limit 0);
* ``failed_queries``: queries whose answer never came, or came with an
  error (limit 0).

A gap decides ``correct`` where the configuration's ``correctness`` gives
it a limit; the others are reported beside.  A non-finite served answer
reads as an infinite gap.
"""
from __future__ import annotations

import numpy as np

from traffic import ITEM_STRIDE, Schedule, pool_rows


def real_rows(tags: np.ndarray) -> int:
    """Rows of a padded call before its padding, which repeats row 0."""
    if len(tags) > 1 and tags[-1] == tags[0]:
        return int(np.argmax(tags[1:] == tags[0])) + 1
    return len(tags)


def served_items(tags: list[np.ndarray], outs: list[np.ndarray]
                 ) -> tuple[np.ndarray, np.ndarray]:
    """(item tags, served logits) of every real row of every call."""
    items, values = [], []
    for t, o in zip(tags, outs):
        n = real_rows(t)
        items.append(np.asarray(t[:n], np.int64))
        values.append(np.asarray(o, np.float64).reshape(len(t))[:n])
    if not items:
        return np.zeros(0, np.int64), np.zeros(0)
    return np.concatenate(items), np.concatenate(values)


def compare(served: tuple[np.ndarray, np.ndarray], ref: np.ndarray,
            sched: Schedule, done: np.ndarray, failed: int) -> dict:
    """Every number the comparison reads, by name: ``served`` is (item
    tags, logits) of every real row served, ``ref`` the reference's logit
    of every pool row, ``done`` the completion instant of each query (NaN:
    not completed)."""
    items, values = served
    rows, ok = pool_rows(sched, items)
    scale = float(np.sqrt(np.mean(np.square(ref.astype(np.float64)))))
    g = np.abs(values[ok] - ref[rows[ok]]) / scale
    g = np.where(np.isfinite(g), g, np.inf)
    per_query = np.bincount(np.unique(items[ok]) // ITEM_STRIDE,
                            minlength=sched.n)
    completed = ~np.isnan(done)
    return {
        "max_gap": float(g.max()) if len(g) else 0.0,
        "rms_gap": float(np.sqrt(np.mean(np.square(g)))) if len(g) else 0.0,
        "unserved_items": int(np.sum(sched.sizes[completed]
                                     - per_query[completed])),
        "stray_items": int((~ok).sum()),
        "failed_queries": int(failed),
    }


def judged(numbers: dict, limits: dict) -> dict:
    """``{name: {"value", "limit"}}`` of the numbers that decide
    ``correct``: the counts, each with the limit 0, and the gaps the
    configuration gives a limit (see PERF.md for how each was set)."""
    limits = dict(limits, unserved_items=0, stray_items=0, failed_queries=0)
    return {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()
            if k in limits}
