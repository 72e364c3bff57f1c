"""The one traffic generator: reads a mix file's parameters and turns them,
with a run's seed, into an open-loop schedule; and the draws that every
model's payload pool (``models/<model>.py``'s ``draw_pool``) shares.

A mix file (``traffic/<mix>.json``) holds only data:

    load_of_knee  offered rate as a multiple of the configuration's knee
    sizes         query-size law (``production``: lognormal body + Pareto
                  tail, clipped; the law of ``repro.core.query_gen``)
    pool_rows     rows of the payload pool every query reads from
    base_seed     seed of the one draw of arrivals and sizes that every
                  run serves
    at_close      ``wait`` (in-flight queries are waited for) or
                  ``abandon`` (the backlog is dropped when the window
                  closes)

Every seed gets the same arrivals and query sizes, in the same order: a
tail percentile at 0.8 of the knee moves by a fifth with the order of the
bursts alone (runs of 20 s, PERF.md), so the order is part of the work,
and runs of different seeds then differ by the system's spread, not the
draw's.  The seed draws the data: which pool rows each query reads, the
pool (from ``pool_key``) and the weights.  Arrivals are a Poisson process
(exponential gaps) scaled so that the schedule fills ``[0, seconds)``
exactly.
"""
from __future__ import annotations

import dataclasses

import numpy as np

# items of one query are tagged ``query * ITEM_STRIDE + item``; the largest
# query of any size law here has fewer items than this
ITEM_STRIDE = 1024


@dataclasses.dataclass(frozen=True)
class Schedule:
    """One run's queries, in arrival order."""
    times: np.ndarray      # scheduled arrival, seconds from the window start
    sizes: np.ndarray      # candidate items per query
    offsets: np.ndarray    # first pool row of each query

    @property
    def n(self) -> int:
        return len(self.times)


def sample_sizes(law: dict, rng: np.random.Generator, n: int) -> np.ndarray:
    """Query sizes from ``law`` (copied from ``query_gen.SizeDist``)."""
    if law["kind"] != "production":
        raise ValueError(f"unknown size law {law['kind']!r}")
    sigma = law["sigma"]
    mu = np.log(law["mean"] * 0.9) - sigma ** 2 / 2
    body = rng.lognormal(mu, sigma, size=n)
    tail = law["tail_xm"] * (1.0 + rng.pareto(law["tail_alpha"], size=n))
    pick_tail = rng.random(n) < law["tail_frac"]
    s = np.where(pick_tail, tail, body)
    return np.clip(np.round(s), 1, law["max_size"]).astype(np.int64)


def schedule(mix: dict, rate_qps: float, seconds: float,
             seed: int) -> Schedule:
    """The run's arrivals, sizes and pool offsets."""
    n = max(1, int(round(rate_qps * seconds)))
    base = np.random.default_rng(mix["base_seed"])
    gaps = base.exponential(1.0, size=n)
    sizes = sample_sizes(mix["sizes"], base, n)
    if sizes.max() >= ITEM_STRIDE:
        raise ValueError(f"query of {sizes.max()} items >= {ITEM_STRIDE}")
    c = np.cumsum(gaps)
    times = seconds * np.concatenate([[0.0], c[:-1]]) / c[-1]
    rows = mix["pool_rows"]
    if sizes.max() > rows:
        raise ValueError(f"pool of {rows} rows < query of {sizes.max()}")
    offsets = np.random.default_rng(seed).integers(0, rows - sizes + 1)
    return Schedule(times, sizes, offsets)


def pool_rows(sched: Schedule, tags: np.ndarray
              ) -> tuple[np.ndarray, np.ndarray]:
    """(pool row of each item tag, mask of the tags that name an item of
    ``sched``); a tag that names none has row -1."""
    tags = np.asarray(tags, np.int64)
    q, i = tags // ITEM_STRIDE, tags % ITEM_STRIDE
    ok = (q >= 0) & (q < sched.n)
    ok[ok] &= i[ok] < sched.sizes[q[ok]]
    rows = np.full(len(tags), -1, np.int64)
    rows[ok] = sched.offsets[q[ok]] + i[ok]
    return rows, ok


def pool_key(seed: int):
    """A PRNG key of the run's payload pool: all bits of ``seed``, kept
    apart from the weights' key."""
    import jax
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, seed >> 32)
    return jax.random.fold_in(key, 0x706F6F6C)


def log_uniform_ids(key, shape: tuple[int, ...], vocab: int):
    """int32 ids over every row of a ``vocab``-row table with the
    log-uniform heavy head of ``repro.data.synthetic._zipf_ids``:
    ``floor(vocab ** u) - 1`` for ``u`` uniform on [0, 1), drawn from
    ``key`` (traceable)."""
    import jax
    import jax.numpy as jnp
    u = jax.random.uniform(key, shape, jnp.float32)
    ids = jnp.floor(jnp.exp(u * np.log(vocab))).astype(jnp.int32) - 1
    return jnp.clip(ids, 0, vocab - 1)
