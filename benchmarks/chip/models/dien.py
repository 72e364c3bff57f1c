"""DIEN, the Deep Interest Evolution Network (Zhou et al., arXiv:1809.03672),
as DeepRecSys Table I sizes it: what the benchmark knows of the model,
found by the configuration file's ``"model": "dien"``.

The plain reference is written from the paper's §4 and imports nothing of
the program.  With ``e_t`` the t-th behaviour of a user's history (its
item and category embeddings side by side) and ``e_a`` the candidate's:

* interest extractor, a GRU whose update gate ``u`` weights the new
  candidate:
  ``u_t = σ(W^u e_t + U^u h_{t-1} + b^u)``,
  ``r_t = σ(W^r e_t + U^r h_{t-1} + b^r)``,
  ``h̃_t = tanh(W^h e_t + r_t ∘ U^h h_{t-1} + b^h)``,
  ``h_t = (1 − u_t) ∘ h_{t-1} + u_t ∘ h̃_t``, from ``h_0 = 0``;
* attention, ``a_t = exp(h_t W e_a) / Σ_j exp(h_j W e_a)`` over the
  row's valid positions (histories are right-padded), 0 on padding;
* interest evolving, an AUGRU over the ``h_t``: the same cell with its
  own weights and ``ũ'_t = a_t · u'_t`` in place of ``u'_t``, over all T
  steps, so ``h'_T`` is the state after the last valid step;
* head: ``concat(h'_T, e_a, the field embeddings)`` through the predict
  MLP, ReLU between layers, none after the last; its one output is the
  item's logit.

Departures from the paper, the program's too: ReLU where the paper's head
takes PReLU/Dice (Dice's batch statistics have no serving meaning); one
logit where Table I's head is a 2-way softmax, whose click probability is
σ of the two logits' difference; no auxiliary loss, which exists only in
training; the public code's extra head inputs (the history's sum and its
product with the target) are not taken.

The weights follow the published initialisation recipe of this repo's
served models, re-derived from the seed here: the run's seed gives a
threefry key, split 16 ways; key 0 splits into one key per field table,
key 1 into one per behaviour id table (item, category), each table normal
/ sqrt(embed_dim); key 8 gives the GRU, key 9 the AUGRU, each split into
an input and a recurrent key, each matrix normal * sqrt(1 / fan_in) with
its gates' columns in the order reset, update, candidate, and the biases
(of the input projection) zero; key 10 gives the attention's W (H, K·D);
key 14 splits into one (the single task) and that into one per head
layer, each weight normal * sqrt(1 / fan_in), each bias zero.

Costs are what the algorithm needs, whatever implements it:

* flops per item: 2 * fan_in * fan_out + fan_out (bias) per projection of
  every step of both recurrences (GRU: input and recurrent projections of
  three gates; AUGRU: the same on the GRU's states), the attention's
  ``W e_a`` and its T scores, and every head layer; one-hot fields pool
  nothing;
* bytes per call at bucket ``b``: every distinct embedding row the call's
  ids name, once (``embed_dim * 4`` bytes each), the ids, masks and
  outputs of the ``b`` rows, and every weight and bias once;
* the two recurrences alone (``recurrence_*``): their flops per item, and
  at bucket ``b`` the behaviours they read, the GRU states they write and
  the AUGRU reads back, the attention scores, the final state and both
  cells' weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference
import traffic


def rec_config(cfg: dict):
    """The program's ``RecConfig`` for a configuration file."""
    from repro.models.recsys import RecConfig
    if cfg["interaction"] != "dien":
        raise ValueError(f"the DIEN reference takes the dien interaction, "
                         f"not {cfg['interaction']!r}")
    return RecConfig(name=cfg["name"], interaction=cfg["interaction"],
                     predict_fc=tuple(cfg["predict_fc"]),
                     n_tables=cfg["n_tables"], vocab=cfg["vocab"],
                     embed_dim=cfg["embed_dim"], hotness=cfg["hotness"],
                     pooling=cfg["pooling"], seq_len=cfg["seq_len"],
                     item_vocab=cfg["item_vocab"],
                     item_fields=cfg["item_fields"],
                     gru_hidden=cfg["gru_hidden"], dtype=cfg["dtype"])


def draw_pool(cfg: dict, rows: int, key) -> dict[str, np.ndarray]:
    """The payload pool as host numpy arrays, drawn on the default device.

    ``sparse`` (rows, n_tables, hotness) int32 field ids over every row of
    every table; ``history`` (rows, seq_len, item_fields) and ``target``
    (rows, item_fields) int32 ids over every row of the item and category
    tables, all by ``traffic.log_uniform_ids``; ``hist_mask`` (rows,
    seq_len) bool, the first ``n`` positions valid for a length ``n``
    uniform over 1 to seq_len.  Padded positions keep their drawn ids,
    which name real rows: only the mask keeps them out."""
    t, k = cfg["seq_len"], cfg["item_fields"]

    @jax.jit
    def draw(key):
        ks, kh, kl, kt = jax.random.split(key, 4)
        lengths = jax.random.randint(kl, (rows, 1), 1, t + 1)
        return {"sparse": traffic.log_uniform_ids(
                    ks, (rows, cfg["n_tables"], cfg["hotness"]), cfg["vocab"]),
                "history": traffic.log_uniform_ids(kh, (rows, t, k),
                                                   cfg["item_vocab"]),
                "hist_mask": jnp.arange(t)[None] < lengths,
                "target": traffic.log_uniform_ids(kt, (rows, k),
                                                  cfg["item_vocab"])}
    return jax.device_get(draw(key))


# ----------------------------------------------------------- the reference


def _tables(key, n: int, vocab: int, d: int) -> list[jax.Array]:
    draw = jax.jit(lambda k: jax.random.normal(k, (vocab, d))
                   * (1.0 / d ** 0.5))
    return [draw(k) for k in jax.random.split(key, n)]


def _normal(key, d_in: int, d_out: int) -> jax.Array:
    return jax.random.normal(key, (d_in, d_out)) * (1.0 / max(d_in, 1)) ** 0.5


def _cell(key, d_in: int, h: int) -> dict:
    """One GRU cell's weights by gate: ``W`` (input), ``U`` (recurrent),
    ``b`` (bias), each a dict over reset ``r``, update ``u``, candidate
    ``c``."""
    ki, kh = jax.random.split(key)
    wi, wh = _normal(ki, d_in, 3 * h), _normal(kh, h, 3 * h)
    gates = ("r", "u", "c")
    return {"W": {g: wi[:, i * h:(i + 1) * h] for i, g in enumerate(gates)},
            "U": {g: wh[:, i * h:(i + 1) * h] for i, g in enumerate(gates)},
            "b": {g: jnp.zeros((h,), jnp.float32) for g in gates}}


def init_weights(seed: int, cfg: dict) -> dict:
    """Reference weights on the default device, one table at a time."""
    rs = jax.random.split(jax.random.PRNGKey(seed), 16)
    d, h, w = cfg["embed_dim"], cfg["gru_hidden"], _item_width(cfg)
    return {"tables": _tables(rs[0], cfg["n_tables"], cfg["vocab"], d),
            "items": _tables(rs[1], cfg["item_fields"], cfg["item_vocab"], d),
            "gru": _cell(rs[8], w, h),
            "augru": _cell(rs[9], h, h),
            "att": _normal(rs[10], h, w),
            "top": _init_mlp(jax.random.split(rs[14], 1)[0],
                             _head_width(cfg), cfg["predict_fc"])}


def _init_mlp(key, d_in: int, widths) -> list[tuple[jax.Array, jax.Array]]:
    layers = []
    for k, width in zip(jax.random.split(key, len(widths)), widths):
        layers.append((_normal(k, d_in, width),
                       jnp.zeros((width,), jnp.float32)))
        d_in = width
    return layers


def forward(w: dict, batch: dict, *, store: str = "float32",
            precision: str = "highest") -> jax.Array:
    """Logits (B,) of the items of ``batch`` (``draw_pool``'s entries), at
    ``store`` and ``precision`` as ``reference.rounder`` and
    ``reference.contraction`` name them.  Each recurrence is a loop over
    the T steps (``lax.scan``, so that the reference compiles in seconds
    whatever T, at any numerics).

    Lookups clip their ids (``draw_pool`` draws every id in range): on a
    TPU v5e, XLA's gather in ``jnp.take``'s default fill mode read wrong
    history rows for the last item of a 4,096-row block, the block
    ``reference.logits`` evaluates the pool in."""
    rs, mul = reference.rounder(store), reference.contraction(precision)

    def dot(a, b):
        return rs(mul("bk,kn->bn", a, b))

    def take(table, ids):
        return jnp.take(table, ids, axis=0, mode="clip")

    def embed(tables, ids):                     # ids (..., K) → (..., K·D)
        return jnp.concatenate([rs(take(t, ids[..., i]))
                                for i, t in enumerate(tables)], axis=-1)

    def step(cell, x, h):
        """(update gate, candidate) of one step of a GRU cell."""
        def gate(g):
            return rs(dot(x, rs(cell["W"][g])) + dot(h, rs(cell["U"][g]))
                      + rs(cell["b"][g]))
        u = rs(jax.nn.sigmoid(gate("u")))
        r = rs(jax.nn.sigmoid(gate("r")))
        c = rs(jnp.tanh(rs(dot(x, rs(cell["W"]["c"])) + rs(cell["b"]["c"]))
                        + rs(r * dot(h, rs(cell["U"]["c"])))))
        return u, c

    e = embed(w["items"], batch["history"])                   # (B, T, K·D)
    e_a = embed(w["items"], batch["target"])                  # (B, K·D)
    h0 = jnp.zeros((e.shape[0], w["gru"]["U"]["u"].shape[0]), jnp.float32)

    def extract(h, e_t):                        # interest extractor
        u, c = step(w["gru"], e_t, h)
        h = rs(rs((1.0 - u) * h) + rs(u * c))
        return h, h

    _, hs = jax.lax.scan(extract, h0, jnp.swapaxes(e, 0, 1))  # (T, B, H)

    mask = jnp.swapaxes(batch["hist_mask"], 0, 1)             # attention
    hw = rs(mul("tbh,hk->tbk", hs, rs(w["att"])))             # h_t W
    s = rs(mul("tbk,bk->tb", hw, e_a))                        # (T, B)
    s = jnp.where(mask, s, -jnp.inf)
    z = jnp.exp(s - s.max(axis=0, keepdims=True))
    a = rs(jnp.where(mask, z / z.sum(axis=0, keepdims=True), 0.0))

    def evolve(h, inp):                         # interest evolving
        h_t, a_t = inp
        u, c = step(w["augru"], h_t, h)
        u = rs(a_t[:, None] * u)
        return rs(rs((1.0 - u) * h) + rs(u * c)), None

    h, _ = jax.lax.scan(evolve, h0, (hs, a))

    fields = [rs(rs(take(table, batch["sparse"][:, f, :]))
                 .sum(axis=1)) for f, table in enumerate(w["tables"])]
    x = jnp.concatenate([h, e_a] + fields, axis=1)
    for k, (wt, bias) in enumerate(w["top"]):
        x = rs(dot(x, rs(wt)) + rs(bias))
        if k < len(w["top"]) - 1:
            x = jax.nn.relu(x)
    return x[:, 0]


# ---------------------------------------------------------------- costs


def _item_width(cfg: dict) -> int:
    return cfg["item_fields"] * cfg["embed_dim"]


def _head_width(cfg: dict) -> int:
    return cfg["gru_hidden"] + _item_width(cfg) \
        + cfg["n_tables"] * cfg["embed_dim"]


def _mlp(d_in: int, widths) -> tuple[int, int]:
    """(flops per row, parameters) of an MLP stack."""
    flops = params = 0
    for w in widths:
        flops += 2 * d_in * w + w
        params += d_in * w + w
        d_in = w
    return flops, params


def _cells(cfg: dict) -> tuple[int, int]:
    """(flops per step, parameters) of the GRU and the AUGRU together."""
    h, w = cfg["gru_hidden"], _item_width(cfg)
    gru, _ = _mlp(w, [3 * h])
    augru, _ = _mlp(h, [3 * h])
    recurrent = 2 * (2 * h * 3 * h)
    return (gru + augru + recurrent,
            (w * 3 * h + 3 * h + h * 3 * h) + (h * 3 * h + 3 * h + h * 3 * h))


def recurrence_flops_per_item(cfg: dict) -> int:
    """Operations of the two recurrences for one candidate item."""
    return cfg["seq_len"] * _cells(cfg)[0]


def flops_per_item(cfg: dict) -> int:
    """Forward operations for one candidate item."""
    h, w, t = cfg["gru_hidden"], _item_width(cfg), cfg["seq_len"]
    attention = 2 * h * w + 2 * t * h
    head, _ = _mlp(_head_width(cfg), cfg["predict_fc"])
    pool = cfg["n_tables"] * (cfg["hotness"] - 1) * cfg["embed_dim"]
    return recurrence_flops_per_item(cfg) + attention + head + pool


def weight_bytes(cfg: dict) -> int:
    """Bytes of every weight and bias outside the tables (float32)."""
    _, cells = _cells(cfg)
    _, head = _mlp(_head_width(cfg), cfg["predict_fc"])
    return 4 * (cells + cfg["gru_hidden"] * _item_width(cfg) + head)


def distinct_rows(inputs: dict) -> int:
    """Distinct (table, id) pairs among the ids of ``inputs``: each field's
    table, and the item and category tables that the history and the
    target share."""
    sparse = np.asarray(inputs["sparse"], np.int64)
    f = sparse.shape[1]
    beh = np.concatenate([np.asarray(inputs["history"], np.int64),
                          np.asarray(inputs["target"], np.int64)[:, None]],
                         axis=1)                      # (rows, T + 1, K)
    keys = [sparse + (np.arange(f, dtype=np.int64) << 32)[None, :, None],
            beh + (np.arange(f, f + beh.shape[2], dtype=np.int64)
                   << 32)[None, None, :]]
    return len(np.unique(np.concatenate([k.ravel() for k in keys])))


def call_bytes(cfg: dict, bucket: int, inputs: dict) -> int:
    """Bytes one forward call at ``bucket`` rows, whose real rows are
    ``inputs``, has to move."""
    t, k = cfg["seq_len"], cfg["item_fields"]
    per_row = 4 * (cfg["n_tables"] * cfg["hotness"] + t * k + k + 1) + t
    return (4 * cfg["embed_dim"] * distinct_rows(inputs) + bucket * per_row
            + weight_bytes(cfg))


def recurrence_bytes(cfg: dict, bucket: int) -> int:
    """Bytes the two recurrences of one call at ``bucket`` rows have to
    move: the behaviours in, the GRU's states out and back into the
    AUGRU, the attention scores in, the final state out, both cells'
    weights once (all float32)."""
    h, w, t = cfg["gru_hidden"], _item_width(cfg), cfg["seq_len"]
    _, cells = _cells(cfg)
    return 4 * (bucket * (t * w + 2 * t * h + t + h) + cells)
