"""Generalized neural recommendation model (paper Fig. 2).

One configurable architecture realizes all eight paper models (NCF, WnD,
MT-WnD, DLRM-RMC1/2/3, DIN, DIEN) **and** the assigned recsys archs
(xDeepFM, AutoInt, MIND, BERT4Rec): dense-FC stack, per-field embedding
bags, a pluggable feature-interaction op, and predict-FC stack(s).

Batch layout (all dense arrays → shardable under pjit):
    dense      (B, n_dense)            float   — continuous features
    sparse     (B, F, H)               int32   — H lookups per field
    history    (B, T)                  int32   — behavior sequence (DIN/MIND/
                                                 BERT4Rec)
               (B, T, K)               int32   — DIEN: K ids per behavior
                                                 (item, category)
    hist_mask  (B, T)                  bool    — valid positions, right-padded
    target     (B,)                    int32   — candidate item id
               (B, K)                  int32   — DIEN: its K ids
    candidates (B, C)                  int32   — retrieval scoring
    label      (B,) / (B, n_tasks)     float
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

import jax
import jax.numpy as jnp

from repro.layers import attention as attn_lib
from repro.layers import embedding as emb_lib
from repro.layers import interactions as ix
from repro.layers import rnn as rnn_lib
from repro.layers.mlp import init_linear, init_mlp, linear, mlp
from repro.layers.norms import init_layer_norm, layer_norm


@dataclasses.dataclass(frozen=True)
class RecConfig:
    name: str
    interaction: str                     # concat|dot|gmf|fm|cin|self-attn|din|dien|mind|bidir-seq
    n_dense: int = 0
    dense_fc: Sequence[int] = ()
    predict_fc: Sequence[int] = (256, 64, 1)
    n_tasks: int = 1
    # sparse fields
    n_tables: int = 0
    vocab: int = 100_000
    embed_dim: int = 32
    hotness: int = 1
    pooling: str = "sum"
    # sequence models
    seq_len: int = 0
    item_vocab: int = 0
    item_fields: int = 0                 # ids per behavior, one table each (DIEN:
                                         # item, category); 0: one item id
                                         # and no field axis
    # CIN (xDeepFM)
    cin_layers: Sequence[int] = ()
    dnn_widths: Sequence[int] = ()
    # AutoInt
    n_attn_layers: int = 0
    n_heads: int = 0
    d_attn: int = 0
    # MIND
    n_interests: int = 0
    capsule_iters: int = 3
    # DIEN
    gru_hidden: int = 0
    dtype: str = "float32"

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    @property
    def has_history(self) -> bool:
        return self.interaction in ("din", "dien", "mind", "bidir-seq")

    @property
    def item_width(self) -> int:
        """Width of one behavior's embedding: its ids' rows side by side
        (one row where a behavior is one item id)."""
        return max(self.item_fields, 1) * self.embed_dim


# ------------------------------------------------------------------- init


def init(rng, cfg: RecConfig):
    rs = jax.random.split(rng, 16)
    dt = cfg.jdtype
    p: dict = {}
    if cfg.n_tables:
        # stacked tables (F, V, D): dim axis shardable over `model`
        keys = jax.random.split(rs[0], cfg.n_tables)
        p["tables"] = jnp.stack(
            [emb_lib.init_table(k, cfg.vocab, cfg.embed_dim, dtype=dt) for k in keys])
    if cfg.item_fields:
        # (K, V, D): one table per id field of a behavior
        keys = jax.random.split(rs[1], cfg.item_fields)
        p["item_tables"] = jnp.stack(
            [emb_lib.init_table(k, cfg.item_vocab, cfg.embed_dim, dtype=dt)
             for k in keys])
    elif cfg.has_history:
        p["item_table"] = emb_lib.init_table(rs[1], cfg.item_vocab, cfg.embed_dim, dtype=dt)
    if cfg.dense_fc:
        p["dense_mlp"] = init_mlp(rs[2], cfg.n_dense, cfg.dense_fc, dtype=dt)

    if cfg.interaction == "cin":
        p["cin"] = ix.init_cin(rs[3], _num_feature_rows(cfg), cfg.embed_dim,
                               cfg.cin_layers, dtype=dt)
        p["cin_linear"] = init_linear(rs[4], sum(cfg.cin_layers), 1, dtype=dt)
        p["dnn"] = init_mlp(rs[5], _num_feature_rows(cfg) * cfg.embed_dim,
                            list(cfg.dnn_widths) + [1], dtype=dt)
        p["lin_w"] = jnp.zeros((cfg.n_tables,), dt)                  # linear logit term
    elif cfg.interaction == "self-attn":
        p["attn"] = []
        dim = cfg.embed_dim
        for i in range(cfg.n_attn_layers):
            p["attn"].append(ix.init_autoint_layer(jax.random.fold_in(rs[6], i),
                                                   dim, cfg.n_heads, cfg.d_attn, dtype=dt))
            dim = cfg.n_heads * cfg.d_attn
    elif cfg.interaction == "din":
        p["din"] = ix.init_din_attention(rs[7], cfg.embed_dim, dtype=dt)
    elif cfg.interaction == "dien":
        p["gru"] = rnn_lib.init_gru(rs[8], cfg.item_width, cfg.gru_hidden, dtype=dt)
        p["augru"] = rnn_lib.init_gru(rs[9], cfg.gru_hidden, cfg.gru_hidden, dtype=dt)
        # the bilinear attention's W: a_t ∝ exp(h_t W e_a)
        p["att"] = init_linear(rs[10], cfg.gru_hidden, cfg.item_width,
                               bias=False, dtype=dt)
    elif cfg.interaction == "mind":
        p["capsule"] = ix.init_capsule_routing(rs[11], cfg.embed_dim, dtype=dt)
    elif cfg.interaction == "bidir-seq":
        p["pos_emb"] = (jax.random.normal(rs[12], (cfg.seq_len, cfg.embed_dim)) * 0.02).astype(dt)
        p["blocks"] = []
        hd = cfg.embed_dim // cfg.n_heads
        for i in range(cfg.n_attn_layers):
            ri = jax.random.fold_in(rs[13], i)
            r1, r2, r3 = jax.random.split(ri, 3)
            p["blocks"].append({
                "ln1": init_layer_norm(cfg.embed_dim, dt),
                "attn": attn_lib.init_attention(r1, cfg.embed_dim, cfg.n_heads,
                                                cfg.n_heads, hd, dtype=dt),
                "ln2": init_layer_norm(cfg.embed_dim, dt),
                "ffn": init_mlp(r2, cfg.embed_dim,
                                [4 * cfg.embed_dim, cfg.embed_dim], dtype=dt),
            })
        p["ln_f"] = init_layer_norm(cfg.embed_dim, dt)

    if cfg.interaction != "cin":                       # cin carries its own heads
        d_int = _interaction_dim(cfg)
        keys = jax.random.split(rs[14], cfg.n_tasks)
        p["predict"] = [init_mlp(k, d_int, list(cfg.predict_fc), dtype=dt)
                        for k in keys]
    return p


def _num_feature_rows(cfg: RecConfig) -> int:
    """Rows entering a (B, F', D) interaction: per-table pooled + dense row."""
    extra = 1 if cfg.dense_fc else 0
    return cfg.n_tables + extra


def _interaction_dim(cfg: RecConfig) -> int:
    dense_out = (cfg.dense_fc[-1] if cfg.dense_fc else cfg.n_dense)
    if cfg.interaction == "concat":
        return dense_out + cfg.n_tables * cfg.embed_dim
    if cfg.interaction == "gmf":                      # NCF: gmf ⊕ mlp-concat
        return cfg.embed_dim + 2 * cfg.embed_dim
    if cfg.interaction == "dot":
        f = _num_feature_rows(cfg)
        return f * (f - 1) // 2 + dense_out
    if cfg.interaction == "fm":
        return cfg.embed_dim + dense_out
    if cfg.interaction == "self-attn":
        return cfg.n_tables * cfg.n_heads * cfg.d_attn
    if cfg.interaction == "din":                      # pooled hist + target + tables
        return (2 + cfg.n_tables) * cfg.embed_dim
    if cfg.interaction == "dien":                     # h'_T ⊕ target ⊕ fields
        return cfg.gru_hidden + cfg.item_width + cfg.n_tables * cfg.embed_dim
    if cfg.interaction == "mind":
        return 2 * cfg.embed_dim                      # interest ⊕ target
    if cfg.interaction == "bidir-seq":
        return cfg.embed_dim
    raise ValueError(cfg.interaction)


# ---------------------------------------------------------------- forward


def _sparse_pooled(params, cfg: RecConfig, sparse: jax.Array) -> jax.Array:
    """sparse (B, F, H) → (B, F, D) per-table pooled embeddings."""
    rows = emb_lib.take_rows(params["tables"], sparse, cfg.embed_dim,
                             cfg.vocab)                              # (B, F, H, D)
    if cfg.pooling == "sum":
        return rows.sum(axis=2)
    if cfg.pooling == "mean":
        return rows.mean(axis=2)
    if cfg.pooling == "concat":                                      # hotness-1 concat
        b, f, h, d = rows.shape
        return rows.reshape(b, f, h * d)
    raise ValueError(cfg.pooling)


def forward(params, cfg: RecConfig, batch: dict) -> jax.Array:
    """→ CTR logits (B,) (or (B, n_tasks) for MT models)."""
    dense_out = None
    if cfg.n_dense:
        dense_out = batch["dense"].astype(cfg.jdtype)
        if cfg.dense_fc:
            with jax.named_scope("bottom_mlp"):
                dense_out = mlp(params["dense_mlp"], dense_out, act="relu",
                                final_act="relu")

    emb = seq = None
    with jax.named_scope("embedding_gather"):
        if cfg.n_tables:
            emb = _sparse_pooled(params, cfg, batch["sparse"])
        if cfg.interaction == "dien":
            seq = _behaviors(params, cfg, batch)

    if cfg.interaction == "cin":                      # carries its own heads
        return _xdeepfm_forward(params, cfg, emb, batch)
    with jax.named_scope("interaction"):
        if cfg.interaction == "dien":
            z = _dien_interest(params, cfg, emb, seq, batch.get("hist_mask"))
        else:
            z = _interact(params, cfg, emb, dense_out, batch)
    with jax.named_scope("top_mlp"):
        outs = [mlp(pp, z, act="relu") for pp in params["predict"]]
    out = jnp.concatenate(outs, axis=-1) if cfg.n_tasks > 1 else outs[0]
    return out[..., 0] if cfg.n_tasks == 1 else out


def _interact(params, cfg: RecConfig, emb, dense_out, batch):
    """The feature interaction: the top MLP's input."""
    it = cfg.interaction
    if it == "concat":
        parts = [] if dense_out is None else [dense_out]
        parts.append(emb.reshape(emb.shape[0], -1))
        z = jnp.concatenate(parts, axis=-1)
    elif it == "gmf":                                 # NCF: tables [u_mf,i_mf,u_mlp,i_mlp]
        gmf = ix.gmf(emb[:, 0], emb[:, 1])
        z = jnp.concatenate([gmf, emb[:, 2], emb[:, 3]], axis=-1)
    elif it == "dot":
        feats = emb
        if dense_out is not None:
            feats = jnp.concatenate([dense_out[:, None, :], emb], axis=1)
        z = jnp.concatenate([ix.dot_interaction(feats)]
                            + ([] if dense_out is None else [dense_out]), axis=-1)
    elif it == "fm":
        z = ix.fm_interaction(emb)
        if dense_out is not None:
            z = jnp.concatenate([z, dense_out], axis=-1)
    elif it == "self-attn":
        x = emb
        dim = cfg.embed_dim
        for lp in params["attn"]:
            x = ix.autoint_layer(lp, x, n_heads=cfg.n_heads, d_attn=cfg.d_attn)
            dim = cfg.n_heads * cfg.d_attn
        z = x.reshape(x.shape[0], -1)
    elif it == "din":
        hist = jnp.take(params["item_table"], batch["history"], axis=0)
        tgt = jnp.take(params["item_table"], batch["target"], axis=0)
        pooled = ix.din_attention(params["din"], hist, tgt,
                                  mask=batch.get("hist_mask"))
        parts = [pooled, tgt]
        if emb is not None:
            parts.append(emb.reshape(emb.shape[0], -1))
        z = jnp.concatenate(parts, axis=-1)
    elif it == "mind":
        caps = _mind_interests(params, cfg, batch)                   # (B, K, D)
        tgt = jnp.take(params["item_table"], batch["target"], axis=0)
        # label-aware attention (pow 2 sharpening), then soft-pool interests
        w = jax.nn.softmax(
            (jnp.einsum("bkd,bd->bk", caps, tgt)
             / jnp.sqrt(cfg.embed_dim)).astype(jnp.float32) * 2.0, axis=-1)
        interest = jnp.einsum("bk,bkd->bd", w.astype(caps.dtype), caps)
        z = jnp.concatenate([interest, tgt], axis=-1)
    elif it == "bidir-seq":
        h = _bert4rec_encode(params, cfg, batch)                     # (B, T, D)
        # score the target item at the final position (inference = next-item)
        tgt = jnp.take(params["item_table"], batch["target"], axis=0)
        z = h[:, -1] * tgt                                            # elementwise match
    else:
        raise ValueError(it)
    return z


def _behaviors(params, cfg: RecConfig, batch: dict):
    """DIEN's behavior and target embeddings, each the rows of its K ids
    side by side (item ⊕ category): history (B, T, K) and target (B, K)
    ids → (B, T, K·D) and (B, K·D), read by one lookup of the packed or
    plain ``item_tables``."""
    ids = jnp.concatenate([batch["history"], batch["target"][:, None]], 1)
    b, t1, k = ids.shape
    rows = emb_lib.take_rows(params["item_tables"], jnp.swapaxes(ids, 1, 2),
                             cfg.embed_dim, cfg.item_vocab)   # (B, K, T+1, D)
    rows = jnp.swapaxes(rows, 1, 2).reshape(b, t1, k * cfg.embed_dim)
    return rows[:, :-1], rows[:, -1]


def _dien_interest(params, cfg: RecConfig, emb, seq, mask):
    """DIEN's interest path (Zhou et al., arXiv:1809.03672, §4) → the head's
    input ``concat(h'_T, e_a, field embeddings)``.

    * interest extractor: a GRU over the behaviors ``e_t`` → ``h_t``
      (``layers/rnn.py`` has the paper's gate convention);
    * attention: ``a_t = exp(h_t W e_a) / Σ_j exp(h_j W e_a)``, a float32
      softmax over each row's valid positions, ``a_t = 0`` on padding
      (histories are right-padded; ``mask`` None: every position valid);
    * interest evolving: the AUGRU over all T steps, so ``h'_T`` is the
      state after the last valid step.

    Departures from the paper, for serving: the head (``top_mlp``) takes
    ReLU where the paper takes PReLU/Dice (Dice's batch statistics have no
    serving meaning) and gives one logit where Table I's head is a 2-way
    softmax (whose click probability is σ of the two logits' difference);
    no auxiliary loss (it exists only in training); the public code's
    extra head inputs (the history's sum and its product with the target)
    are not taken."""
    hist, tgt = seq
    with jax.named_scope("interest_extractor"):
        hs = rnn_lib.gru(params["gru"], hist)                        # (B, T, H)
    with jax.named_scope("interest_attention"):
        q = jnp.einsum("hw,bw->bh", params["att"]["w"], tgt)          # W e_a
        s = jnp.einsum("bth,bh->bt", hs, q).astype(jnp.float32)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        att = jax.nn.softmax(s, axis=-1)
        if mask is not None:
            att = jnp.where(mask, att, 0.0)
    with jax.named_scope("interest_evolution"):
        hT = rnn_lib.augru(params["augru"], hs, att.astype(hs.dtype))  # (B, H)
    parts = [hT, tgt]
    if emb is not None:
        parts.append(emb.reshape(emb.shape[0], -1))
    return jnp.concatenate(parts, axis=-1)


def _xdeepfm_forward(params, cfg, emb, batch):
    b = emb.shape[0]
    cin_out = ix.cin(params["cin"], emb)                             # (B, ΣH)
    logit_cin = linear(params["cin_linear"], cin_out)[..., 0]
    logit_dnn = mlp(params["dnn"], emb.reshape(b, -1), act="relu")[..., 0]
    logit_lin = jnp.einsum("bfd,f->b", emb, params["lin_w"]) / cfg.embed_dim
    return logit_cin + logit_dnn + logit_lin


def _mind_interests(params, cfg, batch):
    hist = jnp.take(params["item_table"], batch["history"], axis=0)
    return ix.capsule_routing(params["capsule"], hist,
                              n_interests=cfg.n_interests,
                              n_iters=cfg.capsule_iters,
                              mask=batch.get("hist_mask"))


def _bert4rec_encode(params, cfg, batch):
    x = jnp.take(params["item_table"], batch["history"], axis=0)
    x = x + params["pos_emb"][None, : x.shape[1]]
    hd = cfg.embed_dim // cfg.n_heads
    for blk in params["blocks"]:
        h = attn_lib.attention(blk["attn"], layer_norm(blk["ln1"], x),
                               n_heads=cfg.n_heads, n_kv_heads=cfg.n_heads,
                               head_dim=hd, causal=False)
        x = x + h
        x = x + mlp(blk["ffn"], layer_norm(blk["ln2"], x), act="gelu")
    return layer_norm(params["ln_f"], x)


def bulk_forward(params, cfg: RecConfig, batch: dict, *, chunk: int = 16_384):
    """Offline/bulk scoring: lax.map over batch chunks so the interaction
    intermediates (CIN builds (B, H·F, D)) never materialize for the whole
    262k/1M-row batch at once.  Chunking is over the GLOBAL batch; each chunk
    keeps the same per-device sharding."""
    from repro import flags
    b = next(iter(batch.values())).shape[0]
    if b <= chunk:
        return forward(params, cfg, batch)
    # round the chunk down to a divisor of b (1M % 65536 != 0 …)
    n = -(-b // chunk)
    while b % n:
        n += 1
    chunk = b // n
    chunked = {k: v.reshape((n, chunk) + v.shape[1:]) for k, v in batch.items()}
    if flags.SCAN_UNROLL:         # exact cost accounting: no while loop
        outs = [forward(params, cfg,
                        {k: v[i] for k, v in chunked.items()}) for i in range(n)]
        out = jnp.stack(outs)
    else:
        out = jax.lax.map(lambda mb: forward(params, cfg, mb), chunked)
    return out.reshape((b,) + out.shape[2:])


# --------------------------------------------------------- retrieval scoring


def score_candidates(params, cfg: RecConfig, batch: dict) -> jax.Array:
    """Retrieval-mode scoring: (B, C) scores for B users × C candidate items.

    Batched dot — never a loop.  For MIND the score is the max over interest
    capsules (the paper's serving rule); for bert4rec the dot of the final
    hidden state with candidate embeddings; other models fall back to running
    ``forward`` with candidates tiled into the target slot.
    """
    cand = jnp.take(params["item_table"], batch["candidates"], axis=0)  # (B,C,D)
    if cfg.interaction == "mind":
        caps = _mind_interests(params, cfg, batch)                   # (B,K,D)
        return jnp.einsum("bkd,bcd->bkc", caps, cand).max(axis=1)
    if cfg.interaction == "bidir-seq":
        h = _bert4rec_encode(params, cfg, batch)[:, -1]              # (B,D)
        return jnp.einsum("bd,bcd->bc", h, cand)
    raise ValueError(f"{cfg.name} has no two-tower retrieval head")


# ------------------------------------------------------------------- loss


def loss_fn(params, cfg: RecConfig, batch: dict) -> jax.Array:
    logits = forward(params, cfg, batch)
    labels = batch["label"].astype(jnp.float32)
    logits = logits.astype(jnp.float32)
    # binary cross-entropy with logits (CTR task); MT models average tasks
    per = jnp.maximum(logits, 0) - logits * labels + jnp.log1p(jnp.exp(-jnp.abs(logits)))
    return per.mean()
