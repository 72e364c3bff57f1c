"""Embedding tables and EmbeddingBag.

JAX has no native ``nn.EmbeddingBag`` and no CSR sparse — the gather+pool
primitive IS part of this system (kernel taxonomy §B.6 / §B.11).  Two layouts:

* fixed-hotness: indices ``(..., H)`` (every bag has exactly H lookups, the
  layout used by DLRM-RMC*/DIN synthetic workloads and by our dry-run shapes);
* ragged: flat ``indices (N,)`` + ``offsets (B+1,)`` (torch EmbeddingBag
  layout), pooled via ``jax.ops.segment_sum``.

Both have Pallas TPU kernels in ``repro.kernels.embedding_bag``; these jnp
implementations are the reference path and the CPU execution path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init_table(rng, vocab: int, dim: int, *, dtype=jnp.float32, scale: float | None = None):
    scale = scale if scale is not None else 1.0 / dim ** 0.5
    return (jax.random.normal(rng, (vocab, dim)) * scale).astype(dtype)


# ---------------------------------------------------------- 128-lane packing

LANES = 128
SUBLANES = 8


def pack_rows(table: jax.Array) -> jax.Array:
    """``(..., V, D)`` → ``(..., Vp, 128)`` with ``p = 128 // D`` rows of
    the table side by side in each packed row, at lanes ``j * D`` to
    ``(j + 1) * D``: a row-major reshape, zero rows appended to make ``Vp
    = ceil(V / p)``.  A width that does not divide 128 leaves zero lanes
    (2 of 128 at ``D = 18``), and its ``Vp`` is rounded up to a whole
    number of 8-row tiles.  A table whose width is 128 or more is returned
    as it is.

    Why: on the TPU a ``(V, D)`` float32 table with ``D < 128`` is laid out
    with the row axis minor (``{0,1:T(8,128)}``), so as not to pad ``D`` to
    128 lanes.  Each (8, 128) tile then holds 8 values of 128 different
    rows, one row lies across ``ceil(D / 8)`` tiles (four 4-KB tiles for
    ``D = 32``), and a gather reads all of them.  Packed, the table is
    row-major and each lookup reads one contiguous 512-byte row.  With
    ``Vp % 8 != 0`` the flat ``(F * Vp, 128)`` view that ``take_rows``
    gathers from is a copy of every table on every call (at ``D = 18``
    and 1M rows, 1.17 GB of temporaries); a width that divides 128 keeps
    the rows it was first packed with, whole tiles at the benchmark's 1M
    rows (250,000 at ``D = 32``)."""
    *lead, v, d = table.shape
    if d >= LANES:
        return table
    p = LANES // d
    vp = -(-v // p)
    if p * d < LANES:
        vp = -(-vp // SUBLANES) * SUBLANES
    if vp * p > v:
        table = jnp.pad(table, [(0, 0)] * len(lead) + [(0, vp * p - v), (0, 0)])
    table = table.reshape(*lead, vp, p * d)
    if p * d < LANES:
        table = jnp.pad(table, [(0, 0)] * (len(lead) + 1)
                        + [(0, LANES - p * d)])
    return table


def take_rows(tables: jax.Array, idx: jax.Array, dim: int,
              vocab: int) -> jax.Array:
    """Rows of width ``dim`` from a stack of ``vocab``-row tables, stored as
    ``(F, vocab, dim)`` or packed by ``pack_rows`` to ``(F, Vp, 128)``
    (told apart by the stored width): ``idx (..., F, H)`` with the ids of
    table ``f`` at ``[..., f, :]`` → ``(..., F, H, dim)``.

    Plain tables are read by ``jnp.take`` per table.  Packed ones are read
    by one gather over the stack seen as one ``(F * Vp, 128)`` table, at
    packed row ``f * Vp + id // p`` with ``p = 128 // dim``; compare and
    select then keep lanes ``(id % p) * dim`` to ``+ dim``, so every value
    is the stored one, bit for bit (no one-hot contraction, which the TPU
    would run in bfloat16).
    Either way ids outside ``[0, vocab)`` read what ``jnp.take`` reads: an
    id in ``[-vocab, 0)`` counts from the end, any other reads NaN (never
    the zero rows ``pack_rows`` appends).

    Why one flat gather and not one batched over the packed tables: on a
    TPU v5e that batched gather, whose (table, row) index XLA packs into
    one word, halted the core at its first call in every fresh process;
    the flat gather ran."""
    if tables.shape[-1] == dim:
        return jax.vmap(lambda t, i: jnp.take(t, i, axis=0),
                        in_axes=(0, 1), out_axes=1)(tables, idx)
    f, vp, lanes = tables.shape
    p = lanes // dim
    idx = jnp.where(idx < 0, idx + vocab, idx)
    row = jnp.where((idx >= 0) & (idx < vocab),
                    idx // p + vp * jnp.arange(f)[:, None], f * vp)
    packed = jnp.take(tables.reshape(f * vp, lanes), row, axis=0)
    lane = idx % p
    rows = packed[..., :dim]
    for j in range(1, p):
        rows = jnp.where((lane == j)[..., None],
                         packed[..., j * dim:(j + 1) * dim], rows)
    return rows


# ---------------------------------------------------------------- fixed-hotness


def embedding_bag(table: jax.Array, idx: jax.Array, *, mode: str = "sum",
                  weights: jax.Array | None = None) -> jax.Array:
    """Pooled lookup.  ``table (V, D)``, ``idx (..., H)`` → ``(..., D)``.

    ``weights`` (same shape as idx) enables weighted-sum pooling (DIN's
    attention-weighted pooling reuses this).
    """
    rows = jnp.take(table, idx, axis=0)          # (..., H, D)
    if weights is not None:
        rows = rows * weights[..., None]
    if mode == "sum":
        return rows.sum(axis=-2)
    if mode == "mean":
        return rows.mean(axis=-2)
    if mode == "max":
        return rows.max(axis=-2)
    if mode == "none":
        return rows                               # (..., H, D) unpooled
    raise ValueError(f"unknown pooling mode {mode!r}")


# ---------------------------------------------------------------------- ragged


def segment_ids_from_offsets(offsets: jax.Array, total: int) -> jax.Array:
    """offsets (B+1,) → segment id per element (total,)."""
    return jnp.searchsorted(offsets, jnp.arange(total, dtype=offsets.dtype),
                            side="right") - 1


def embedding_bag_ragged(table: jax.Array, indices: jax.Array, offsets: jax.Array,
                         *, num_bags: int, mode: str = "sum") -> jax.Array:
    """torch.nn.EmbeddingBag layout: flat ``indices (N,)``, ``offsets (B+1,)``."""
    rows = jnp.take(table, indices, axis=0)                       # (N, D)
    seg = segment_ids_from_offsets(offsets, indices.shape[0])
    if mode == "sum":
        return jax.ops.segment_sum(rows, seg, num_segments=num_bags)
    if mode == "mean":
        s = jax.ops.segment_sum(rows, seg, num_segments=num_bags)
        cnt = jax.ops.segment_sum(jnp.ones_like(seg, dtype=rows.dtype), seg,
                                  num_segments=num_bags)
        return s / jnp.maximum(cnt, 1.0)[:, None]
    if mode == "max":
        return jax.ops.segment_max(rows, seg, num_segments=num_bags)
    raise ValueError(f"unknown pooling mode {mode!r}")


# ----------------------------------------------------------- compressed tables


def hashed_lookup(table: jax.Array, idx: jax.Array) -> jax.Array:
    """Hash trick: fold arbitrary ids into the table's vocab."""
    return jnp.take(table, idx % table.shape[0], axis=0)


def init_qr_tables(rng, vocab: int, dim: int, *, num_buckets: int, dtype=jnp.float32):
    """Quotient-remainder compositional embedding [arXiv:1909.02107]."""
    rq, rr = jax.random.split(rng)
    n_q = -(-vocab // num_buckets)  # ceil
    return {
        "q": init_table(rq, n_q, dim, dtype=dtype),
        "r": init_table(rr, num_buckets, dim, dtype=dtype),
        "num_buckets": num_buckets,
    }


def qr_lookup(params, idx: jax.Array, *, combine: str = "mult") -> jax.Array:
    nb = params["num_buckets"]
    q = jnp.take(params["q"], idx // nb, axis=0)
    r = jnp.take(params["r"], idx % nb, axis=0)
    return q * r if combine == "mult" else q + r
