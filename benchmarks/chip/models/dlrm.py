"""DLRM (Naumov et al., arXiv:1906.00091) as DeepRecSys Table I sizes it:
what the benchmark knows of the model, found by the configuration file's
``"model": "dlrm"``.

The plain reference is written from the DLRM description and imports
nothing of the program:

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

Costs are what the algorithm needs, whatever implements it:

* flops per item: 2 * fan_in * fan_out + fan_out (bias) per MLP layer;
  ``n_tables * (hotness - 1) * embed_dim`` adds to pool the bags; the dot
  interaction as the product of the (R, D) feature rows with themselves,
  ``2 * R * R * D`` with ``R = n_tables + 1``;
* bytes per call at bucket ``b``: every distinct embedding row the call's
  ids name, once (``embed_dim * 4`` bytes each; a row named twice need not
  be read twice, so no gather, however it is built, needs fewer), the ids
  and the dense inputs of the ``b`` rows, every MLP weight and bias once,
  and the ``b`` outputs (all float32 / int32).
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
    if cfg["interaction"] != "dot":
        raise ValueError(f"the DLRM reference takes the dot interaction, "
                         f"not {cfg['interaction']!r}")
    return RecConfig(name=cfg["name"], interaction=cfg["interaction"],
                     n_dense=cfg["n_dense"], dense_fc=tuple(cfg["dense_fc"]),
                     predict_fc=tuple(cfg["predict_fc"]),
                     n_tables=cfg["n_tables"], vocab=cfg["vocab"],
                     embed_dim=cfg["embed_dim"], hotness=cfg["hotness"],
                     pooling=cfg["pooling"], dtype=cfg["dtype"])


def draw_pool(cfg: dict, rows: int, key) -> dict[str, np.ndarray]:
    """The payload pool as host numpy arrays, drawn on the default device.

    ``dense`` (rows, n_dense) float32 standard normal; ``sparse`` (rows,
    n_tables, hotness) int32 ids over every row of every table, by
    ``traffic.log_uniform_ids``."""
    n_dense, vocab = cfg["n_dense"], cfg["vocab"]
    shape = (rows, cfg["n_tables"], cfg["hotness"])

    @jax.jit
    def draw(key):
        kd, ks = jax.random.split(key)
        dense = jax.random.normal(kd, (rows, n_dense), jnp.float32)
        return dense, traffic.log_uniform_ids(ks, shape, vocab)

    dense, sparse = jax.device_get(draw(key))
    return {"dense": dense, "sparse": sparse}


# ----------------------------------------------------------- the reference


def init_weights(seed: int, cfg: dict) -> dict:
    """Reference weights on the default device, one table at a time."""
    rs = jax.random.split(jax.random.PRNGKey(seed), 16)
    d = cfg["embed_dim"]
    table_keys = jax.random.split(rs[0], cfg["n_tables"])
    draw = jax.jit(lambda k: jax.random.normal(k, (cfg["vocab"], d))
                   * (1.0 / d ** 0.5))
    tables = [draw(k) for k in table_keys]
    return {"tables": tables,
            "bottom": _init_mlp(rs[2], cfg["n_dense"], cfg["dense_fc"]),
            "top": _init_mlp(jax.random.split(rs[14], 1)[0],
                             _interaction_width(cfg), cfg["predict_fc"])}


def _init_mlp(key, d_in: int, widths) -> list[tuple[jax.Array, jax.Array]]:
    layers = []
    for k, w in zip(jax.random.split(key, len(widths)), widths):
        scale = (1.0 / max(d_in, 1)) ** 0.5
        layers.append((jax.random.normal(k, (d_in, w)) * scale,
                       jnp.zeros((w,), jnp.float32)))
        d_in = w
    return layers


def forward(w: dict, batch: dict, *, store: str = "float32",
            precision: str = "highest") -> jax.Array:
    """Logits (B,) of the items ``batch["dense"]`` (B, n_dense) and
    ``batch["sparse"]`` (B, F, H), at ``store`` and ``precision`` as
    ``reference.rounder`` and ``reference.contraction`` name them."""
    rs, mul = reference.rounder(store), reference.contraction(precision)

    def dot(a, b):
        return rs(mul("bk,kn->bn", a, b))

    def linear(x, layer):
        return rs(dot(x, rs(layer[0])) + rs(layer[1]))

    x = rs(batch["dense"])
    for layer in w["bottom"]:
        x = jax.nn.relu(linear(x, layer))
    feats = [x]
    for f, table in enumerate(w["tables"]):
        rows = rs(jnp.take(table, batch["sparse"][:, f, :], axis=0))
        feats.append(rs(rows.sum(axis=1)))
    dots = [rs(mul("bd,bd->b", feats[i], feats[j]))
            for i in range(len(feats)) for j in range(i)]
    z = jnp.concatenate([jnp.stack(dots, axis=1), x], axis=1)
    for k, layer in enumerate(w["top"]):
        z = linear(z, layer)
        if k < len(w["top"]) - 1:
            z = jax.nn.relu(z)
    return z[:, 0]


# ---------------------------------------------------------------- costs


def _mlp(d_in: int, widths) -> tuple[int, int]:
    """(flops per row, parameters) of an MLP stack."""
    flops = params = 0
    for w in widths:
        flops += 2 * d_in * w + w
        params += d_in * w + w
        d_in = w
    return flops, params


def _interaction_width(cfg: dict) -> int:
    r = cfg["n_tables"] + 1
    return r * (r - 1) // 2 + cfg["dense_fc"][-1]


def flops_per_item(cfg: dict) -> int:
    """Forward operations for one candidate item."""
    bottom, _ = _mlp(cfg["n_dense"], cfg["dense_fc"])
    top, _ = _mlp(_interaction_width(cfg), cfg["predict_fc"])
    f, h, d = cfg["n_tables"], cfg["hotness"], cfg["embed_dim"]
    r = f + 1
    return bottom + f * (h - 1) * d + 2 * r * r * d + top


def weight_bytes(cfg: dict) -> int:
    """Bytes of every MLP weight and bias (float32)."""
    _, bottom = _mlp(cfg["n_dense"], cfg["dense_fc"])
    _, top = _mlp(_interaction_width(cfg), cfg["predict_fc"])
    return 4 * (bottom + top)


def distinct_rows(sparse: np.ndarray) -> int:
    """Distinct (table, id) pairs among the ids ``sparse`` (rows, F, H)."""
    f = sparse.shape[1]
    keys = (sparse.astype(np.int64)
            + (np.arange(f, dtype=np.int64) << 32)[None, :, None])
    return len(np.unique(keys))


def call_bytes(cfg: dict, bucket: int, inputs: dict) -> int:
    """Bytes one forward call at ``bucket`` rows, whose real rows are
    ``inputs``, has to move."""
    f, h, d = cfg["n_tables"], cfg["hotness"], cfg["embed_dim"]
    per_row = 4 * (f * h + cfg["n_dense"] + 1)
    return (4 * d * distinct_rows(inputs["sparse"]) + bucket * per_row
            + weight_bytes(cfg))
