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


@pytest.mark.parametrize("b,t,d,h", [
    (3, 7, 36, 36),       # a bucket under 8 rows, one chunk of 7 steps
    (64, 100, 36, 36),    # DIEN at bucket 64: four chunks of 25 steps
    (256, 10, 20, 36),    # eight rows of steps per chunk: chunks of 8 → 5
    (5, 4, 8, 130),       # a state wider than 128 lanes
])
def test_gru_kernel_matches_the_scan(b, t, d, h):
    """The TPU branch of ``layers.rnn`` (padded, time-major, the Pallas
    kernel interpreted) against the scan that defines the equations: the
    GRU's every state and the AUGRU's last, from a nonzero state, with
    nonzero biases and attention scores in (0, 1), at ``highest``."""
    from repro.layers import rnn
    ks = jax.random.split(jax.random.fold_in(KEY, b * t), 5)
    p = rnn.init_gru(ks[0], d, h)
    p["wi"]["b"] = jax.random.normal(ks[1], p["wi"]["b"].shape)
    xs = jax.random.normal(ks[2], (b, t, d))
    h0 = 0.5 * jax.random.normal(ks[3], (b, h))
    att = jax.random.uniform(ks[4], (b, t))
    with jax.default_matmul_precision("highest"):
        got = rnn._gru_tpu(p, xs, h0, interpret=True)
        want = rnn._gru_scan(p, xs, h0)
        got_t = rnn._augru_tpu(p, xs, att, h0, interpret=True)
        want_t = rnn._augru_scan(p, xs, att, h0)
    assert got.shape == (b, t, h) and got_t.shape == (b, h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(want_t),
                               rtol=1e-5, atol=1e-5)


def test_gru_kernel_chunks_divide_the_steps():
    from repro.kernels import gru
    assert gru.chunk_steps(100, 64) == 25
    assert gru.chunk_steps(100, 256) == 5
    assert gru.chunk_steps(100, 8) == 100
    assert gru.chunk_steps(7, 2048) == 1
    assert gru.chunk_steps(97, 64) == 1


def test_gru_gradient_is_the_scans():
    """The kernel has no gradient of its own: ``rnn.gru``/``rnn.augru``
    differentiate as the scan does."""
    from repro.layers import rnn
    p = {"gru": rnn.init_gru(KEY, 6, 4),
         "augru": rnn.init_gru(jax.random.fold_in(KEY, 1), 4, 4)}
    xs = jax.random.normal(KEY, (2, 3, 6))
    att = jnp.full((2, 3), 0.5)
    h0 = jnp.zeros((2, 4))

    def loss(gru, augru):
        return lambda p: augru(p["augru"], gru(p["gru"], xs, h0), att,
                               h0).sum()
    got = jax.grad(loss(rnn.gru, rnn.augru))(p)
    want = jax.grad(loss(rnn._gru_scan, rnn._augru_scan))(p)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6)
