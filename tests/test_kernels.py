"""Pallas kernel sweeps: shapes × dtypes, interpret=True vs the jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref

KEY = jax.random.PRNGKey(7)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else \
        dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("vocab,batch,hot,dim", [
    (64, 8, 4, 128), (128, 16, 1, 128), (1000, 8, 16, 256),
    (37, 4, 3, 130),                       # non-128 dim → wrapper pads
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_embedding_bag_kernel(vocab, batch, hot, dim, dtype):
    table = jax.random.normal(KEY, (vocab, dim)).astype(dtype)
    idx = jax.random.randint(KEY, (batch, hot), 0, vocab)
    got = ops.embedding_bag(table, idx, interpret=True)
    # oracle in f32 (the kernel accumulates f32; a bf16-accumulating oracle
    # would itself carry ~H·2⁻⁸ drift)
    want = ref.embedding_bag(table.astype(jnp.float32), idx).astype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_embedding_bag_kernel_modes(mode):
    table = jax.random.normal(KEY, (50, 128))
    idx = jax.random.randint(KEY, (8, 5), 0, 50)
    got = ops.embedding_bag(table, idx, mode=mode, interpret=True)
    # the kernel accumulates with Kahan compensation, so hold it to the
    # f64-exact pooled value (up to f32 ulps of the row magnitudes) — an
    # f32 oracle with atol=0 would demand bitwise-matching *rounding order*,
    # which near-cancelling bags cannot satisfy for any other order
    rows = np.asarray(table, np.float64)[np.asarray(idx)]
    want = rows.sum(axis=1)
    if mode == "mean":
        want = want / idx.shape[1]
    np.testing.assert_allclose(np.asarray(got, np.float64), want,
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("batch,fields,dim", [
    (32, 8, 32), (64, 27, 16), (8, 4, 64), (10, 5, 130),   # odd batch → pad
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dot_interaction_kernel(batch, fields, dim, dtype):
    feats = (jax.random.normal(KEY, (batch, fields, dim)) / dim ** 0.5).astype(dtype)
    got = ops.dot_interaction(feats, interpret=True)
    want = ref.dot_interaction_packed(feats)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("batch,f,h,hn,dim", [
    (8, 6, 5, 7, 128), (16, 10, 10, 4, 64), (4, 3, 8, 16, 130),
])
def test_cin_kernel(batch, f, h, hn, dim):
    x0 = jax.random.normal(KEY, (batch, f, dim)) / dim ** 0.5
    xk = jax.random.normal(jax.random.fold_in(KEY, 1), (batch, h, dim)) / dim ** 0.5
    w = jax.random.normal(jax.random.fold_in(KEY, 2), (h * f, hn))
    got = ops.cin_layer(x0, xk, w, interpret=True)
    want = ref.cin_layer(x0, xk, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("b,hq,hkv,d,t", [
    (2, 8, 2, 64, 256), (4, 4, 4, 32, 128), (1, 16, 8, 128, 512),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_kernel(b, hq, hkv, d, t, dtype):
    q = jax.random.normal(KEY, (b, hq, d)).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, hkv, d)).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, hkv, d)).astype(dtype)
    pos = jax.random.randint(KEY, (b,), 1, t + 1)
    got = ops.decode_attention(q, k, v, pos, interpret=True)
    want = ref.decode_attention(q, k, v, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=3e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_flash_decode_pos_zero_vs_one():
    """pos=1 attends only to slot 0 (pos=0 would be an empty softmax —
    serving never issues it, decode always follows a ≥1-token prefill)."""
    b, hq, hkv, d, t = 1, 2, 1, 32, 128
    q = jax.random.normal(KEY, (b, hq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, t, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, t, hkv, d))
    got = ops.decode_attention(q, k, v, jnp.array([1]), interpret=True)
    np.testing.assert_allclose(np.asarray(got[0, 0]), np.asarray(v[0, 0, 0]),
                               rtol=1e-5, atol=1e-5)
