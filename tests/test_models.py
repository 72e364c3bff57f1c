"""Per-architecture smoke tests (reduced configs, one forward/train step on
CPU, asserting shapes + finite outputs) — all 10 assigned archs + the 8
DeepRecInfra paper models."""
import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data import synthetic as syn
from repro.layers import embedding as emb_lib
from repro.models import gnn, lm, recsys
from repro.serve.models import (recsys_model, served_forward,
                                served_param_shapes)

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def nprng():
    return np.random.default_rng(0)


RECSYS_ARCHS = configs.list_archs("recsys")
LM_ARCHS = configs.list_archs("lm")


@pytest.mark.parametrize("arch", RECSYS_ARCHS)
def test_recsys_smoke_forward_and_grad(arch, nprng=None):
    nprng = np.random.default_rng(0)
    cfg = configs.get(arch).smoke_config
    params = recsys.init(KEY, cfg)
    batch = syn.recsys_batch(nprng, cfg, 8)
    out = recsys.forward(params, cfg, batch)
    expected = (8,) if cfg.n_tasks == 1 else (8, cfg.n_tasks)
    assert out.shape == expected
    assert np.isfinite(np.asarray(out)).all()
    loss, grads = jax.value_and_grad(
        lambda p: recsys.loss_fn(p, cfg, batch))(params)
    assert np.isfinite(float(loss))
    assert all(np.isfinite(np.asarray(g)).all()
               for g in jax.tree_util.tree_leaves(grads))


@pytest.mark.parametrize("arch", ["mind", "bert4rec"])
def test_recsys_retrieval_head(arch):
    nprng = np.random.default_rng(0)
    cfg = configs.get(arch).smoke_config
    params = recsys.init(KEY, cfg)
    batch = syn.recsys_batch(nprng, cfg, 2, n_candidates=64, with_label=False)
    scores = recsys.score_candidates(params, cfg, batch)
    assert scores.shape == (2, 64)
    assert np.isfinite(np.asarray(scores)).all()


def test_recsys_bulk_forward_matches_direct():
    nprng = np.random.default_rng(0)
    cfg = configs.get("xdeepfm").smoke_config
    params = recsys.init(KEY, cfg)
    batch = syn.recsys_batch(nprng, cfg, 32, with_label=False)
    direct = recsys.forward(params, cfg, batch)
    chunked = recsys.bulk_forward(params, cfg, batch, chunk=8)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(chunked),
                               rtol=1e-5, atol=1e-5)


RECSYS_SCOPES = ("embedding_gather", "bottom_mlp", "interaction", "top_mlp")
DIEN_SCOPES = ("embedding_gather", "interaction", "top_mlp",
               "interaction/interest_extractor", "interaction/interest_attention",
               "interaction/interest_evolution")


def without_metadata(hlo: str) -> str:
    """Compiled HLO text less each instruction's metadata and the source
    locations those point into."""
    body = hlo.split("\nFileNames\n")[0]
    return re.sub(r", metadata=\{[^}]*\}", "", body)


@pytest.mark.parametrize("arch, scopes", [("dlrm-rmc1", RECSYS_SCOPES),
                                          ("dien", DIEN_SCOPES)])
@pytest.mark.parametrize("bucket", [1, 64])
def test_recsys_forward_scopes_change_only_metadata(monkeypatch, bucket,
                                                    arch, scopes):
    """The served forward's HLO names the gather, the bottom MLP, the
    interaction and the top MLP (DIEN: its GRU, attention and AUGRU
    inside the interaction); without the scopes XLA compiles the same
    program."""
    cfg = configs.get(arch).smoke_config

    def compiled() -> str:
        jax.clear_caches()
        params = served_param_shapes(cfg)
        batch = syn.recsys_specs(cfg, bucket, with_label=False)
        return served_forward("cpu").lower(params, cfg,
                                           batch).compile().as_text()

    scoped = compiled()
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in scopes:
        assert any(f"jit(forward)/{scope}/" in n for n in op_names), scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = compiled()
    assert not any(f"/{scope}/" in plain for scope in scopes)
    assert without_metadata(scoped) == without_metadata(plain)


@pytest.mark.parametrize("pooling", ["sum", "mean"])
@pytest.mark.parametrize("dim,vocab", [(8, 1000), (16, 1001), (32, 1003),
                                       (64, 999), (10, 1000), (18, 1000),
                                       (18, 1001), (128, 1001)])
def test_packed_lookup_matches_plain(dim, vocab, pooling):
    """Tables packed into 128-lane rows give back what ``jnp.take`` gives
    on the plain tables, bit for bit and NaN for NaN: ids 0 and V-1, a
    vocabulary that is no multiple of the pack, and the ids ``jnp.take``
    wraps (-1, -V) or fills (V, which lies in the appended zero rows,
    -V-1, the int32 extremes).  A width under 128 packs ``128 // D`` rows
    to a row; one that does not divide 128 (D = 10 and 18) leaves the
    lanes over zero and packs a whole number of 8-row tiles; a width that
    fills 128 stays as it is; the pooled lookup agrees to float32
    rounding."""
    n_tables, batch, hot = 3, 5, 7
    table = jax.random.normal(KEY, (n_tables, vocab, dim))
    packed = emb_lib.pack_rows(table)
    p = 128 // dim
    vp = -(-vocab // p)
    if 128 % dim:
        vp = (vp + 7) // 8 * 8
    want_shape = ((n_tables, vp, 128) if dim < 128
                  else (n_tables, vocab, dim))
    assert packed.shape == want_shape
    if dim < 128:
        rows = np.asarray(packed)[:, :, :p * dim].reshape(n_tables, -1, dim)
        np.testing.assert_array_equal(rows[:, :vocab], np.asarray(table))
        assert not rows[:, vocab:].any()
        assert not np.asarray(packed)[:, :, p * dim:].any()
    sparse = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, n_tables, hot), 0, vocab)
    edge = [0, vocab - 1, -1, -vocab, vocab, -vocab - 1,
            np.iinfo(np.int32).max, np.iinfo(np.int32).min]
    sparse = sparse.at[0, :, :4].set(jnp.array(edge[:4])[None])
    sparse = sparse.at[1, :, :4].set(jnp.array(edge[4:])[None])
    plain = np.stack([np.asarray(jnp.take(table[t], sparse[:, t], axis=0))
                      for t in range(n_tables)], axis=1)       # (B, F, H, D)
    assert np.isnan(plain[1, :, :4]).all() and np.isfinite(plain[0]).all()
    np.testing.assert_array_equal(
        np.asarray(emb_lib.take_rows(packed, sparse, dim, vocab)), plain)
    cfg = recsys.RecConfig(name="packed", interaction="concat",
                           n_tables=n_tables, vocab=vocab, embed_dim=dim,
                           hotness=hot, pooling=pooling)
    np.testing.assert_allclose(
        np.asarray(recsys._sparse_pooled({"tables": packed}, cfg, sparse)),
        np.asarray(recsys._sparse_pooled({"tables": table}, cfg, sparse)),
        rtol=1e-6, atol=1e-7)


# recorded from the tree before tables of any width under 128 lanes were
# packed: a (3, 1003, 32) table from PRNGKey(5) and ids from PRNGKey(6)
# over [-1010, 1010); sha256 of the arrays' bytes
PINNED_32 = {
    "packed": "75822275d22cdc0a2a1bf5c765756bba917250b37a7ddd1e3d931150bdd94b92",
    "rows": "7f2657982b302b176606be7edd17981f1991759553aca953f2c462830cab16ea",
}


def test_packing_32_wide_tables_is_as_before():
    """At D = 32 the packed table is the array it was before, and the
    lookup reads the same values, bad ids included."""
    import hashlib

    def sha(a):
        return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
    table = jax.random.normal(jax.random.PRNGKey(5), (3, 1003, 32))
    packed = np.asarray(emb_lib.pack_rows(table))
    assert packed.shape == (3, 251, 128)
    assert sha(packed) == PINNED_32["packed"]
    idx = jax.random.randint(jax.random.PRNGKey(6), (5, 3, 7), -1010, 1010)
    assert sha(np.asarray(emb_lib.take_rows(packed, idx, 32, 1003))) == \
        PINNED_32["rows"]
    whole = jax.random.normal(jax.random.PRNGKey(5), (2, 4 * 8 * 5, 32))
    np.testing.assert_array_equal(np.asarray(emb_lib.pack_rows(whole)),
                                  np.asarray(whole).reshape(2, 40, 128))


def test_served_dien_packs_every_table():
    """DIEN at its 18-wide rows is served with its eight field tables and
    its item and category tables packed seven rows to a 128-lane row,
    holding the values ``recsys.init`` draws; the served forward reads
    every row from a packed table (every gather takes whole 128-lane
    rows) and gives what ``recsys.forward`` gives on the unpacked
    tables."""
    smoke = configs.get("dien").smoke_config
    cfg = dataclasses.replace(smoke, vocab=101, item_vocab=103, embed_dim=18,
                              gru_hidden=36)
    apply_fn, make_batch, params = recsys_model(cfg, seed=3, max_rows=16)
    plain = jax.jit(recsys.init, static_argnums=1)(jax.random.PRNGKey(3),
                                                   cfg)
    assert plain["item_tables"].shape == (2, 103, 18)
    assert params["tables"].shape == (cfg.n_tables, 16, 128)
    assert params["item_tables"].shape == (2, 16, 128)
    shapes = served_param_shapes(cfg)
    for k in ("tables", "item_tables"):
        assert shapes[k].shape == params[k].shape
        np.testing.assert_array_equal(np.asarray(params[k]),
                                      np.asarray(emb_lib.pack_rows(plain[k])))
    batch = make_batch(16, 0)
    assert batch["history"].shape == (16, cfg.seq_len, 2)
    np.testing.assert_allclose(np.asarray(apply_fn(batch)),
                               np.asarray(recsys.forward(plain, cfg, batch)),
                               rtol=1e-5, atol=1e-5)
    hlo = served_forward("cpu").lower(params, cfg, batch).compile().as_text()
    gathers = re.findall(r" gather\(.*slice_sizes=\{([0-9,]+)\}", hlo)
    assert gathers and set(gathers) == {"1,128"}


def test_served_model_packs_its_tables():
    """The served DLRM-RMC1 holds its tables packed four rows to a 128-lane
    row, with the values ``recsys.init`` draws, and serves what
    ``recsys.forward`` computes on the unpacked tables."""
    smoke = configs.get("dlrm-rmc1").smoke_config
    cfg = dataclasses.replace(smoke, vocab=101, embed_dim=32,
                              dense_fc=smoke.dense_fc[:-1] + (32,))
    apply_fn, make_batch, params = recsys_model(cfg, seed=3, max_rows=16)
    plain = jax.jit(recsys.init, static_argnums=1)(jax.random.PRNGKey(3),
                                                   cfg)
    assert plain["tables"].shape == (cfg.n_tables, 101, 32)
    assert params["tables"].shape == (cfg.n_tables, 26, 128)
    assert served_param_shapes(cfg)["tables"].shape == (cfg.n_tables, 26, 128)
    np.testing.assert_array_equal(np.asarray(params["tables"]),
                                  np.asarray(emb_lib.pack_rows(
                                      plain["tables"])))
    batch = make_batch(16, 0)
    np.testing.assert_allclose(np.asarray(apply_fn(batch)),
                               np.asarray(recsys.forward(plain, cfg, batch)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_smoke_train_and_decode(arch):
    nprng = np.random.default_rng(0)
    cfg = configs.get(arch).smoke_config
    params = lm.init(KEY, cfg)
    batch = syn.lm_batch(nprng, cfg, 2, 16)
    loss, grads = jax.value_and_grad(lambda p: lm.loss_fn(p, cfg, batch))(params)
    assert np.isfinite(float(loss))
    logits, caches = lm.prefill(params, cfg, batch["tokens"][:, :8], 16)
    assert logits.shape == (2, cfg.vocab)
    nxt, caches = lm.decode_step(params, cfg, batch["tokens"][:, 8], caches)
    assert nxt.shape == (2, cfg.vocab)
    assert np.isfinite(np.asarray(nxt)).all()


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "granite-moe-1b-a400m"])
def test_lm_scan_equals_unrolled(arch):
    nprng = np.random.default_rng(0)
    cfg = configs.get(arch).smoke_config
    cfg_scan = dataclasses.replace(cfg, scan_layers=True)
    params_u = lm.init(KEY, cfg)
    params_s = lm.init(KEY, cfg_scan)
    batch = syn.lm_batch(nprng, cfg, 2, 16)
    lu = lm.loss_fn(params_u, cfg, batch)
    ls = lm.loss_fn(params_s, cfg_scan, batch)
    np.testing.assert_allclose(float(lu), float(ls), rtol=1e-5)


def test_lm_prefill_decode_consistent_with_forward():
    """prefill(t[:k]) + decode(t[k]) logits == forward(t[:k+1]) last logits."""
    cfg = configs.get("qwen2-0.5b").smoke_config
    params = lm.init(KEY, cfg)
    nprng = np.random.default_rng(0)
    batch = syn.lm_batch(nprng, cfg, 2, 8)
    toks = batch["tokens"]
    logits_full, _ = lm.forward(params, cfg, toks)
    logits_pre, caches = lm.prefill(params, cfg, toks[:, :7], 8)
    np.testing.assert_allclose(np.asarray(logits_pre),
                               np.asarray(logits_full[:, 6]),
                               rtol=5e-4, atol=5e-4)
    logits_dec, _ = lm.decode_step(params, cfg, toks[:, 7], caches)
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_full[:, 7]),
                               rtol=5e-4, atol=5e-4)


def test_lm_param_count_analytics():
    cfg = configs.get("qwen2-0.5b").smoke_config
    params = lm.init(KEY, cfg)
    from repro.utils import param_count
    assert abs(param_count(params) - cfg.param_count) / cfg.param_count < 0.02


# ---------------------------------------------------------------- gnn


def test_gcn_full_batch_smoke():
    cfg = configs.get("gcn-cora").smoke_config
    params = gnn.init(KEY, cfg)
    nprng = np.random.default_rng(0)
    g = syn.random_graph(nprng, 60, 240, cfg.d_feat, cfg.n_classes)
    logits = gnn.forward(params, cfg, g["x"], g["edge_index"])
    assert logits.shape == (60, cfg.n_classes)
    loss, grads = jax.value_and_grad(lambda p: gnn.loss_fn(p, cfg, g))(params)
    assert np.isfinite(float(loss))


def test_gcn_minibatch_sampler_and_blocks():
    cfg = configs.get("gcn-cora").smoke_config
    params = gnn.init(KEY, cfg)
    nprng = np.random.default_rng(0)
    g = syn.random_graph(nprng, 100, 500, cfg.d_feat, cfg.n_classes)
    indptr, indices = syn.graph_to_csr(100, np.asarray(g["edge_index"]))
    blocks, input_nodes = gnn.sample_neighbors(indptr, indices,
                                               np.arange(16), [4, 3], nprng)
    # fanout bound holds per block
    for (ei, n_src, n_dst), fan in zip(blocks, [3, 4]):
        per_dst = np.bincount(np.asarray(ei[1]), minlength=n_dst)
        assert per_dst.max() <= fan
    x_in = jnp.asarray(np.asarray(g["x"])[input_nodes])
    out = gnn.forward_blocks(params, cfg, x_in, blocks)
    assert out.shape == (16, cfg.n_classes)


def test_gcn_molecule_batched():
    cfg = configs.get("gcn-cora").smoke_config
    params = gnn.init(KEY, cfg)
    nprng = np.random.default_rng(0)
    mb = syn.molecule_batch(nprng, 8, 10, 20, cfg.d_feat, cfg.n_classes)
    loss = gnn.graph_loss_fn(params, cfg, mb)
    assert np.isfinite(float(loss))


def test_gcn_aggregation_averages_neighbors():
    """A node whose neighbors all carry feature v aggregates toward v."""
    cfg = dataclasses.replace(configs.get("gcn-cora").smoke_config,
                              n_layers=1, d_feat=4, n_classes=4)
    x = jnp.zeros((4, 4)).at[1:, :].set(1.0)
    ei = jnp.array([[1, 2, 3], [0, 0, 0]])          # 1,2,3 → 0
    agg = gnn.gcn_aggregate(x, ei, 4, norm="mean")
    assert float(agg[0, 0]) > 0.7                   # pulled toward neighbors
