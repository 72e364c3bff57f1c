"""Jit'd public wrappers for the Pallas kernels.

Each wrapper pads to the kernel's tiling constraints (lane = 128, batch
tiles) and always runs the Pallas kernel — compiled for the TPU, or in
the Pallas interpreter with ``interpret=True``.  There is no backend
switch: a caller that wants the jnp oracle calls ``kernels.ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import cin as cin_k
from repro.kernels import embedding_bag as eb_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import interaction as ix_k

_LANE = 128


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("mode", "interpret"))
def embedding_bag(table, idx, *, mode: str = "sum", interpret: bool = False):
    """(V, D), (B, H) → (B, D).  Ids outside [0, V) are clamped into the
    table: the kernel DMAs each row, and an out-of-range DMA faults."""
    b, _ = idx.shape
    v, d = table.shape
    tp = _pad_to(table, 1, _LANE)
    tile_b = 8
    ip = _pad_to(jnp.clip(idx, 0, v - 1), 0, tile_b)
    out = eb_k.embedding_bag(tp, ip, mode=mode, tile_b=tile_b,
                             interpret=interpret)
    return out[:b, :d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def dot_interaction(feats, *, interpret: bool = False):
    """(B, F, D) → (B, F(F-1)/2)."""
    b = feats.shape[0]
    fp = _pad_to(feats, 2, _LANE)
    tile_b = 32 if b % 32 == 0 else (8 if b % 8 == 0 else (2 if b % 2 == 0 else 1))
    fp = _pad_to(fp, 0, tile_b)
    out = ix_k.dot_interaction(fp, tile_b=tile_b, interpret=interpret)
    return out[:b]


@functools.partial(jax.jit, static_argnames=("interpret",))
def cin_layer(x0, xk, w, *, interpret: bool = False):
    """(B, F, D), (B, H, D), (H·F, Hn) → (B, Hn, D)."""
    b, _, d = x0.shape
    tile_d = _LANE
    x0p = _pad_to(x0, 2, tile_d)
    xkp = _pad_to(xk, 2, tile_d)
    tile_b = 8 if b % 8 == 0 else (2 if b % 2 == 0 else 1)
    out = cin_k.cin_layer(x0p, xkp, w, tile_b=tile_b, tile_d=tile_d,
                          interpret=interpret)
    return out[:, :, :d]


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, pos, *, interpret: bool = False):
    """q (B, Hq, D), k/v (B, T, Hkv, D), pos (B,) → (B, Hq, D)."""
    t = k.shape[1]
    tile_t = 128 if t % 128 == 0 else (64 if t % 64 == 0 else t)
    return fa_k.decode_attention(q, k, v, pos, tile_t=tile_t,
                                 interpret=interpret)
