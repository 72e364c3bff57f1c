"""Pallas TPU kernel for DIEN's recurrences: the GRU of the interest
extractor and the attention-gated AUGRU of the interest evolving layer,
each as one kernel over all T steps.

The equations are ``layers/rnn.py``'s (the paper's gate convention).  The
state ``h`` stays in VMEM from the first step to the last; the input
projections of a chunk of steps are one matmul before its serial steps, so
each step does one ``(B, Hp) @ (Hp, 3·Hp)`` product and its gates
(Hp = 128 at DIEN's H = 36).  The products take the program's matmul
precision, as the forward's other contractions do.

Layout, padded by the caller (``layers/rnn.py``):
    xs   (T, B, K)     float32 — time-major inputs, B a multiple of 8,
                                 K a multiple of 128
    wi   (K, 3·Hp)     — input projection, gate g in lanes [g·Hp, g·Hp+H),
                         Hp = H rounded up to 128
    bi   (1, 3·Hp)     — its bias, laid out alike
    wh   (Hp, 3·Hp)    — recurrent projection, rows H.. zero
    h0   (B, Hp)
    att  (T, B, 1)     — AUGRU only: each step's attention score
The padded lanes of every gate are zero, so the state's lanes H.. stay
zero: ``u = σ(0)``, ``h̃ = tanh(0) = 0`` and ``h ← (1 − u)·h`` keeps 0.

Output: the state after every step, (T, B, Hp) (GRU), or after the last
step, (B, Hp) (AUGRU).

The grid walks chunks of ``tc`` steps in order ("arbitrary"), carrying the
state in a VMEM scratch; ``tc`` divides T and keeps a chunk's blocks to
about 2,048 rows, so at B = 256 a step of the grid holds some 9 MB of VMEM
(inputs and outputs double-buffered, the chunk's projections once).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
CHUNK_ROWS = 2048


def chunk_steps(t: int, b: int) -> int:
    """The most steps per grid step that divide ``t`` and hold at most
    ``CHUNK_ROWS`` rows of ``b``."""
    cap = max(1, CHUNK_ROWS // b)
    return max(d for d in range(1, min(t, cap) + 1) if t % d == 0)


def _kernel(*refs, gated: bool, every_step: bool):
    if gated:
        x_ref, wi_ref, bi_ref, wh_ref, h0_ref, a_ref, out_ref, h_scr, g_scr = refs
    else:
        x_ref, wi_ref, bi_ref, wh_ref, h0_ref, out_ref, h_scr, g_scr = refs
    tc, b, k = x_ref.shape
    hp = wh_ref.shape[0]

    @pl.when(pl.program_id(0) == 0)
    def _():
        h_scr[...] = h0_ref[...]

    # the chunk's input projections, off the serial path
    gi = jnp.dot(x_ref[...].reshape(tc * b, k), wi_ref[...],
                 preferred_element_type=jnp.float32) + bi_ref[...]
    g_scr[...] = gi.reshape(tc, b, 3 * hp)
    wh = wh_ref[...]

    def step(t, h):
        g = g_scr[t]                                          # (B, 3·128)
        gh = jnp.dot(h, wh, preferred_element_type=jnp.float32)
        r = jax.nn.sigmoid(g[:, :hp] + gh[:, :hp])
        u = jax.nn.sigmoid(g[:, hp:2 * hp] + gh[:, hp:2 * hp])
        if gated:
            u = a_ref[t] * u                                  # ũ = a_t · u
        c = jnp.tanh(g[:, 2 * hp:] + r * gh[:, 2 * hp:])
        h = (1.0 - u) * h + u * c
        if every_step:
            out_ref[t] = h
        return h

    h = jax.lax.fori_loop(0, tc, step, h_scr[...])
    h_scr[...] = h
    if not every_step:
        out_ref[...] = h


def recurrence(xs, wi, bi, wh, h0, att=None, *,
               interpret: bool = False) -> jax.Array:
    """The GRU (``att`` None: every step's state, (T, B, Hp)) or the
    AUGRU (``att`` (T, B, 1): the last state, (B, Hp)) over padded,
    time-major inputs; see the module docstring for the layout."""
    t, b, k = xs.shape
    hp = wh.shape[0]
    assert b % 8 == 0 and k % LANES == 0 and hp % LANES == 0, \
        (xs.shape, wh.shape)
    assert wi.shape == (k, 3 * hp) and bi.shape == (1, 3 * hp)
    gated = att is not None
    tc = chunk_steps(t, b)

    def whole(shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    in_specs = [pl.BlockSpec((tc, b, k), lambda i: (i, 0, 0)),
                whole(wi.shape), whole(bi.shape), whole(wh.shape),
                whole(h0.shape)]
    args = [xs, wi, bi, wh, h0]
    if gated:
        in_specs.append(pl.BlockSpec((tc, b, 1), lambda i: (i, 0, 0)))
        args.append(att)
        out_spec = whole((b, hp))
        out_shape = jax.ShapeDtypeStruct((b, hp), jnp.float32)
    else:
        out_spec = pl.BlockSpec((tc, b, hp), lambda i: (i, 0, 0))
        out_shape = jax.ShapeDtypeStruct((t, b, hp), jnp.float32)

    def kernel(*refs):
        _kernel(*refs, gated=gated, every_step=not gated)

    return pl.pallas_call(
        kernel,
        grid=(t // tc,),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((b, hp), jnp.float32),
                        pltpu.VMEM((tc, b, 3 * hp), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
