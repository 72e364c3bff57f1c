"""Unit tests for the layer substrate.  (Hypothesis property tests live in
test_properties.py so these plain tests run even without the dev extras.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.layers import attention as A
from repro.layers import embedding as E
from repro.layers import interactions as IX
from repro.layers import moe as M
from repro.layers import rnn as R

KEY = jax.random.PRNGKey(0)


# ----------------------------------------------------------- embedding bag


def test_qr_embedding_covers_vocab():
    p = E.init_qr_tables(KEY, 1000, 8, num_buckets=32)
    idx = jnp.arange(1000)
    out = E.qr_lookup(p, idx)
    assert out.shape == (1000, 8)
    # distinct ids map to distinct embeddings with very high probability
    assert len(np.unique(np.asarray(out).round(5), axis=0)) > 990


# ------------------------------------------------------------ interactions


def test_dot_interaction_symmetric_pairs():
    f = jax.random.normal(KEY, (3, 5, 7))
    out = IX.dot_interaction(f)
    z = np.einsum("bfd,bgd->bfg", np.asarray(f), np.asarray(f))
    li, lj = np.tril_indices(5, k=-1)
    np.testing.assert_allclose(np.asarray(out), z[:, li, lj], rtol=1e-5)


def test_fm_identity():
    """FM pooling == explicit pairwise sum."""
    f = jax.random.normal(KEY, (4, 6, 8))
    got = IX.fm_interaction(f)
    fn = np.asarray(f)
    want = np.zeros((4, 8))
    for i in range(6):
        for j in range(6):
            if i < j:
                want += fn[:, i] * fn[:, j]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_cin_shapes_and_grad():
    p = IX.init_cin(KEY, 6, 8, [10, 12])
    x = jax.random.normal(KEY, (3, 6, 8))
    out = IX.cin(p, x)
    assert out.shape == (3, 22)
    g = jax.grad(lambda pp: IX.cin(pp, x).sum())(p)
    assert all(np.isfinite(np.asarray(gi)).all() for gi in g)


def test_din_attention_mask_excludes_history():
    p = IX.init_din_attention(KEY, 8)
    hist = jax.random.normal(KEY, (2, 6, 8))
    tgt = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 8))
    mask = jnp.array([[True] * 6, [True, True, False, False, False, False]])
    out = IX.din_attention(p, hist, tgt, mask=mask)
    # row 1 must not depend on masked history items
    hist2 = hist.at[1, 2:].set(99.0)
    out2 = IX.din_attention(p, hist2, tgt, mask=mask)
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(out2[1]), rtol=1e-5)


def test_capsule_routing_norm_bounded():
    """Squash keeps capsule norms in (0, 1)."""
    p = IX.init_capsule_routing(KEY, 16)
    hist = jax.random.normal(KEY, (4, 20, 16)) * 3
    caps = IX.capsule_routing(p, hist, n_interests=4, n_iters=3)
    norms = np.linalg.norm(np.asarray(caps), axis=-1)
    assert (norms < 1.0 + 1e-5).all()


# -------------------------------------------------------------------- moe


def test_moe_capacity_drops_overflow():
    p = M.init_moe(KEY, 8, 16, 4, 1)
    x = jnp.ones((1, 64, 8))            # identical tokens → one expert hot
    y, aux = M.apply_moe(p, x, top_k=1, capacity_factor=0.25)
    assert float(aux["dropped_frac"]) > 0.5


# --------------------------------------------------------------- attention


def test_gqa_matches_mha_when_kv_equal():
    d, h, hd, s, b = 32, 4, 8, 10, 2
    p = A.init_attention(KEY, d, h, h, hd)
    x = jax.random.normal(KEY, (b, s, d))
    out = A.attention(p, x, n_heads=h, n_kv_heads=h, head_dim=hd, causal=True)
    assert out.shape == (b, s, d)


def test_decode_matches_full_attention():
    """Token-by-token decode must equal the full causal forward."""
    d, hq, hkv, hd, s, b = 32, 4, 2, 8, 6, 2
    p = A.init_attention(KEY, d, hq, hkv, hd)
    freqs = A.rope_freqs(hd)
    x = jax.random.normal(KEY, (b, s, d))
    full = A.attention(p, x, n_heads=hq, n_kv_heads=hkv, head_dim=hd,
                       causal=True, freqs=freqs)
    cache = A.init_kv_cache(b, s, hkv, hd)
    outs = []
    for t in range(s):
        o, cache = A.decode_attention(p, x[:, t:t + 1], cache, n_heads=hq,
                                      n_kv_heads=hkv, head_dim=hd, freqs=freqs)
        outs.append(o)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(dec),
                               rtol=2e-4, atol=2e-4)


def test_flash_equals_dense_causal():
    b, s, hq, hkv, d = 2, 256, 4, 2, 16
    q = jax.random.normal(KEY, (b, s, hq, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 2), (b, s, hkv, d))
    mask = jnp.tril(jnp.ones((s, s), bool))[None, None]
    dense = A._sdpa(q, k, v, mask)
    fl = A.flash_sdpa(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(fl),
                               rtol=2e-5, atol=2e-5)


def test_rope_preserves_norm_and_relative_phase():
    freqs = A.rope_freqs(8)
    x = jax.random.normal(KEY, (1, 4, 2, 8))
    r = A.apply_rope(x, jnp.arange(4), freqs)
    np.testing.assert_allclose(np.linalg.norm(np.asarray(r), axis=-1),
                               np.linalg.norm(np.asarray(x), axis=-1),
                               rtol=1e-5)
    # dot(q_i, k_j) depends only on i - j
    q = jnp.ones((1, 8, 1, 8))
    k = jnp.ones((1, 8, 1, 8))
    qr = A.apply_rope(q, jnp.arange(8), freqs)[0, :, 0]
    kr = A.apply_rope(k, jnp.arange(8), freqs)[0, :, 0]
    d1 = float(qr[3] @ kr[1])
    d2 = float(qr[5] @ kr[3])
    assert abs(d1 - d2) < 1e-4


# -------------------------------------------------------------------- rnn


def test_gru_matches_manual_step():
    """The paper's convention (DIEN §4): the update gate weights the new
    candidate, h_t = (1 - u) h_{t-1} + u h̃."""
    p = R.init_gru(KEY, 4, 6)
    p["wi"]["b"] = jax.random.normal(jax.random.PRNGKey(3), (18,))
    xs = jax.random.normal(KEY, (2, 5, 4))
    hs = R.gru(p, xs)
    assert hs.shape == (2, 5, 6)
    wi, b, wh = (np.asarray(p["wi"]["w"], np.float64), np.asarray(p["wi"]["b"]),
                 np.asarray(p["wh"]["w"], np.float64))
    sig = lambda v: 1 / (1 + np.exp(-v))  # noqa: E731
    h = np.zeros((2, 6))
    for t in range(5):
        x = np.asarray(xs[:, t], np.float64)
        r = sig(x @ wi[:, :6] + b[:6] + h @ wh[:, :6])
        u = sig(x @ wi[:, 6:12] + b[6:12] + h @ wh[:, 6:12])
        c = np.tanh(x @ wi[:, 12:] + b[12:] + r * (h @ wh[:, 12:]))
        h = (1 - u) * h + u * c
        np.testing.assert_allclose(np.asarray(hs[:, t]), h, rtol=1e-5,
                                   atol=1e-5)
    # AUGRU with full attention is the GRU; with zero attention, frozen
    h_full = R.augru(p, xs, jnp.ones((2, 5)))
    np.testing.assert_allclose(np.asarray(h_full), h, rtol=1e-5, atol=1e-5)
    # AUGRU with zero attention == frozen state
    h_frozen = R.augru(p, xs, jnp.zeros((2, 5)))
    np.testing.assert_allclose(np.asarray(h_frozen), np.zeros((2, 6)), atol=1e-6)
