"""What every model's plain reference shares: how it rounds, how it
contracts, and its evaluation over a whole payload pool.

Each model's own reference (weights from the seed and a float32 forward)
is in ``models/<model>.py`` and imports nothing of the program.  Two
settings say how a reference computes:

* ``store``: the type every stored tensor (tables, weights, inputs) and
  every operation's result is rounded to (``float32``: kept as it is);
* ``precision``: how each contraction (matrix product, dot product)
  multiplies, named as JAX names matmul precisions and
  built from bfloat16 pieces the way a TPU builds them, the same on any
  chip: ``highest``, float32 products (the reference); ``high``, three
  passes, each operand split into a bfloat16 head and tail and the
  tail-by-tail product left out; ``default``, one pass of bfloat16
  operands.  Products are summed in float32.

Other sums, such as embedding bags and bias additions, are float32
additions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


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


@functools.cache
def _jitted(forward):
    return jax.jit(forward, static_argnames=("store", "precision"))


def logits(forward, w, pool: dict[str, np.ndarray], *,
           store: str = "float32", precision: str = "highest",
           block: int = 4096) -> np.ndarray:
    """``forward(w, batch, store=, precision=)`` over every row of
    ``pool``, in blocks of ``block`` rows (one compiled shape: the last
    block is padded by repeating its first row)."""
    fn = _jitted(forward)
    n = len(next(iter(pool.values())))
    out = np.empty(n, np.float32)
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        batch = {}
        for k, v in pool.items():
            v = v[lo:hi]
            if hi - lo < block:
                v = np.concatenate([v, np.repeat(v[:1], block - (hi - lo), 0)])
            batch[k] = v
        out[lo:hi] = np.asarray(fn(w, batch, store=store,
                                   precision=precision))[:hi - lo]
    return out
