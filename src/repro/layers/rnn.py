"""Recurrent cells for DIEN (Zhou et al., arXiv:1809.03672, §4): the
interest extractor's GRU and the interest evolving layer's attention-gated
AUGRU.

Both take the paper's gate convention, in which the update gate ``u``
weights the new candidate:

    u_t = σ(W^u x_t + U^u h_{t-1} + b^u)
    r_t = σ(W^r x_t + U^r h_{t-1} + b^r)
    h̃_t = tanh(W^h x_t + r_t ∘ U^h h_{t-1} + b^h)
    h_t = (1 − u_t) ∘ h_{t-1} + u_t ∘ h̃_t

The AUGRU scales the update gate by the step's attention score,
``ũ_t = a_t · u_t``, so a step with ``a_t = 0`` leaves the state as it was.

The equations are written once, as a ``lax.scan``.  A program lowered for
the TPU runs each recurrence as one Pallas kernel instead
(``kernels/gru.py``: the state in VMEM for all T steps), since a scan's
while loop runs some five device ops per step, 1,000 or more per DIEN
forward.  Gradients are the scan's on every platform.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import flags
from repro.kernels import gru as gru_k
from repro.layers.mlp import init_linear, linear


def init_gru(rng, d_in: int, d_hidden: int, *, dtype=jnp.float32):
    ri, rh = jax.random.split(rng)
    return {
        # gates computed jointly: [reset, update, candidate]; the biases
        # b^r, b^u, b^h are the input projection's
        "wi": init_linear(ri, d_in, 3 * d_hidden, bias=True, dtype=dtype),
        "wh": init_linear(rh, d_hidden, 3 * d_hidden, bias=False, dtype=dtype),
    }


def _gru_gates(params, x_t, h):
    """(update gate ``u_t``, candidate ``h̃_t``) of one step."""
    gi = linear(params["wi"], x_t)
    gh = linear(params["wh"], h)
    ir, iu, ic = jnp.split(gi, 3, axis=-1)
    hr, hu, hc = jnp.split(gh, 3, axis=-1)
    r = jax.nn.sigmoid(ir + hr)
    u = jax.nn.sigmoid(iu + hu)
    return u, jnp.tanh(ic + r * hc)


def _h0(params, xs, h0):
    if h0 is not None:
        return h0
    return jnp.zeros((xs.shape[0], params["wh"]["w"].shape[0]), xs.dtype)


def _gru_scan(params, xs, h0):
    def step(h, x_t):
        u, c = _gru_gates(params, x_t, h)
        h_new = (1.0 - u) * h + u * c
        return h_new, h_new

    _, hs = jax.lax.scan(step, h0, jnp.swapaxes(xs, 0, 1),
                         unroll=flags.scan_unroll())
    return jnp.swapaxes(hs, 0, 1)


def _augru_scan(params, xs, att, h0):
    def step(h, inp):
        x_t, a_t = inp
        u, c = _gru_gates(params, x_t, h)
        u = a_t[:, None] * u                       # attention-scaled update
        return (1.0 - u) * h + u * c, None

    hT, _ = jax.lax.scan(step, h0,
                         (jnp.swapaxes(xs, 0, 1), jnp.swapaxes(att, 0, 1)),
                         unroll=flags.scan_unroll())
    return hT


# ------------------------------------------------------- the TPU kernel


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def _pad(x, *sizes):
    return jnp.pad(x, [(0, n - d) for d, n in zip(x.shape, sizes)])


def _gate_lanes(w, hp: int):
    """(..., 3H) → (..., 3·hp): gate g's H values at lanes g·hp, the rest
    zero."""
    *lead, g = w.shape
    w = w.reshape(*lead, 3, g // 3)
    return _pad(w, *lead, 3, hp).reshape(*lead, 3 * hp)


def _kernel_args(params, xs, h0):
    """The padded, time-major operands of ``kernels.gru.recurrence``."""
    b, t, d = xs.shape
    h = params["wh"]["w"].shape[0]
    bp, k, hp = _up(b, 8), _up(d, gru_k.LANES), _up(h, gru_k.LANES)
    f32 = jnp.float32
    wi = _pad(_gate_lanes(params["wi"]["w"].astype(f32), hp), k, 3 * hp)
    bi = _gate_lanes(params["wi"]["b"].astype(f32), hp)[None]
    wh = _pad(_gate_lanes(params["wh"]["w"].astype(f32), hp), hp, 3 * hp)
    x = _pad(jnp.swapaxes(xs, 0, 1).astype(f32), t, bp, k)
    return x, wi, bi, wh, _pad(h0.astype(f32), bp, hp)


def _gru_tpu(params, xs, h0, *, interpret: bool = False):
    b, _, _ = xs.shape
    h = params["wh"]["w"].shape[0]
    hs = gru_k.recurrence(*_kernel_args(params, xs, h0), interpret=interpret)
    return jnp.swapaxes(hs[:, :b, :h], 0, 1).astype(xs.dtype)


def _augru_tpu(params, xs, att, h0, *, interpret: bool = False):
    b, t, _ = xs.shape
    h = params["wh"]["w"].shape[0]
    args = _kernel_args(params, xs, h0)
    a = _pad(jnp.swapaxes(att, 0, 1).astype(jnp.float32), t, args[0].shape[1])
    hT = gru_k.recurrence(*args, a[..., None], interpret=interpret)
    return hT[:b, :h].astype(xs.dtype)


def _fused(scan, tpu):
    """``tpu`` where the program is lowered for the TPU, ``scan`` anywhere
    else; the gradient is ``scan``'s everywhere (the kernel has none)."""
    @jax.custom_vjp
    def run(*args):
        return jax.lax.platform_dependent(*args, tpu=tpu, default=scan)

    run.defvjp(lambda *args: (run(*args), args),
               lambda args, g: jax.vjp(scan, *args)[1](g))
    return run


_gru = _fused(_gru_scan, _gru_tpu)
_augru = _fused(_augru_scan, _augru_tpu)


def gru(params, xs: jax.Array, h0: jax.Array | None = None) -> jax.Array:
    """xs (B, T, d_in) → hidden states (B, T, d_hidden)."""
    return _gru(params, xs, _h0(params, xs, h0))


def augru(params, xs: jax.Array, att: jax.Array,
          h0: jax.Array | None = None) -> jax.Array:
    """DIEN's attention-gated GRU over all T steps: update gate scaled by
    the attention score.  xs (B, T, d_in), att (B, T) → the state after
    the last step (B, d_hidden)."""
    return _augru(params, xs, att, _h0(params, xs, h0))
