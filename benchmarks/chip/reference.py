"""Plain DLRM reference: weights from the seed and a float32 forward.

Written from the DLRM description (Naumov et al., arXiv:1906.00091) as
DeepRecSys Table I sizes it, and imports nothing of the program:

* bottom MLP over the dense features, ReLU after every layer;
* one embedding bag per table: the sum of the ``hotness`` rows its ids
  name;
* dot interaction: the bottom MLP's output and the pooled bags are the
  feature rows; every pair ``(i, j)`` with ``i > j`` gives one dot
  product, taken row by row in that order, and the bottom MLP's output is
  appended after them;
* top MLP, ReLU between layers, none after the last; its one output is
  the item's logit.

The weights follow the published initialisation recipe of this repo's
served models, re-derived from the seed here: the run's seed gives a
threefry key, split 16 ways; key 0 splits into one key per table, each
table normal / sqrt(embed_dim); key 2 splits into one key per bottom
layer, key 14 into one (the single task) and that into one per top
layer, each weight normal * sqrt(1 / fan_in), each bias zero.

Two settings say how it computes:

* ``store``: the type every stored tensor (tables, weights, inputs) and
  every operation's result is rounded to (``float32``: kept as it is);
* ``precision``: how each contraction (the MLPs' matrix products and the
  dot interaction) multiplies, named as JAX names matmul precisions and
  built from bfloat16 pieces the way a TPU builds them, the same on any
  chip: ``highest``, float32 products (the reference); ``high``, three
  passes, each operand split into a bfloat16 head and tail and the
  tail-by-tail product left out; ``default``, one pass of bfloat16
  operands.  Products are summed in float32.

The embedding bags' sums and the bias additions are float32 additions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init_weights(seed: int, cfg: dict) -> dict:
    """Reference weights on the default device, one table at a time."""
    rs = jax.random.split(jax.random.PRNGKey(seed), 16)
    d = cfg["embed_dim"]
    table_keys = jax.random.split(rs[0], cfg["n_tables"])
    draw = jax.jit(lambda k: jax.random.normal(k, (cfg["vocab"], d))
                   * (1.0 / d ** 0.5))
    tables = [draw(k) for k in table_keys]
    r = cfg["n_tables"] + 1
    n_pairs = r * (r - 1) // 2
    top_in = n_pairs + cfg["dense_fc"][-1]
    return {"tables": tables,
            "bottom": _mlp(rs[2], cfg["n_dense"], cfg["dense_fc"]),
            "top": _mlp(jax.random.split(rs[14], 1)[0], top_in,
                        cfg["predict_fc"])}


def _mlp(key, d_in: int, widths) -> list[tuple[jax.Array, jax.Array]]:
    layers = []
    for k, w in zip(jax.random.split(key, len(widths)), widths):
        scale = (1.0 / max(d_in, 1)) ** 0.5
        layers.append((jax.random.normal(k, (d_in, w)) * scale,
                       jnp.zeros((w,), jnp.float32)))
        d_in = w
    return layers


def rounder(dtype: str):
    """``x -> x`` rounded to ``dtype``'s significand and exponent bits,
    saturating at the largest such number, and held in float32.
    ``reduce_precision`` is kept by XLA where a round trip through
    ``dtype`` may be folded away (excess precision)."""
    if dtype == "float32":
        return lambda x: x
    fi = jnp.finfo(jnp.dtype(dtype))
    top = (2.0 - 2.0 ** -fi.nmant) * 2.0 ** (2 ** (fi.nexp - 1) - 1)
    return lambda x: jax.lax.reduce_precision(
        jnp.clip(x, -top, top), exponent_bits=fi.nexp,
        mantissa_bits=fi.nmant)


def contraction(precision: str):
    """``(subscripts, a, b) -> einsum`` at ``precision`` (see above)."""
    bf = rounder("bfloat16")

    def highest(sub, a, b):
        return jnp.einsum(sub, a, b, precision=HIGHEST)

    def high(sub, a, b):
        ah, bh = bf(a), bf(b)
        al, bl = bf(a - ah), bf(b - bh)
        return (highest(sub, ah, bh) + highest(sub, ah, bl)
                + highest(sub, al, bh))

    def default(sub, a, b):
        return highest(sub, bf(a), bf(b))
    return {"highest": highest, "high": high, "default": default}[precision]


def forward(w: dict, dense: jax.Array, sparse: jax.Array, *,
            store: str = "float32", precision: str = "highest") -> jax.Array:
    """Logits (B,) of the items ``dense`` (B, n_dense), ``sparse`` (B, F, H)."""
    rs, mul = rounder(store), contraction(precision)

    def dot(a, b):
        return rs(mul("bk,kn->bn", a, b))

    def linear(x, layer):
        return rs(dot(x, rs(layer[0])) + rs(layer[1]))

    x = rs(dense)
    for layer in w["bottom"]:
        x = jax.nn.relu(linear(x, layer))
    feats = [x]
    for f, table in enumerate(w["tables"]):
        rows = rs(jnp.take(table, sparse[:, f, :], axis=0))   # (B, H, D)
        feats.append(rs(rows.sum(axis=1)))
    dots = [rs(mul("bd,bd->b", feats[i], feats[j]))
            for i in range(len(feats)) for j in range(i)]
    z = jnp.concatenate([jnp.stack(dots, axis=1), x], axis=1)
    for k, layer in enumerate(w["top"]):
        z = linear(z, layer)
        if k < len(w["top"]) - 1:
            z = jax.nn.relu(z)
    return z[:, 0]


@functools.partial(jax.jit, static_argnames=("store", "precision"))
def _block(w, dense, sparse, store, precision):
    return forward(w, dense, sparse, store=store, precision=precision)


def logits(w: dict, dense: np.ndarray, sparse: np.ndarray, *,
           store: str = "float32", precision: str = "highest",
           block: int = 4096) -> np.ndarray:
    """Logits of every row of ``dense``/``sparse``, in blocks of ``block``
    rows (one compiled shape: the last block is padded)."""
    n = dense.shape[0]
    out = np.empty(n, np.float32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        d, s = dense[lo:hi], sparse[lo:hi]
        if hi - lo < block:
            d = np.concatenate([d, np.repeat(d[:1], block - (hi - lo), 0)])
            s = np.concatenate([s, np.repeat(s[:1], block - (hi - lo), 0)])
        out[lo:hi] = np.asarray(_block(w, d, s, store, precision))[:hi - lo]
    return out
