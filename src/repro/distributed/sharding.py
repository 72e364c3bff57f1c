"""Sharding rules: param-tree path → PartitionSpec, per model family.

Axis convention (launch/mesh.py):
    pod   — slow inter-pod axis (DP only)
    data  — intra-pod DP (batch) axis; FSDP weight sharding when enabled
    model — TP / EP axis

Families
  * LM: Megatron TP — qkv/ffn-in column-sharded, wo/ffn-out row-sharded over
    ``model``; embeddings vocab-sharded; MoE experts sharded over ``model``
    (EP).  Optional ``fsdp=True`` additionally shards the largest weight dim
    over ``data`` (ZeRO-3-style; XLA inserts per-layer all-gathers).
  * RecSys: DLRM hybrid — embedding tables model-parallel (embedding dim over
    ``model``: lookups stay local, the only collective is the small pooled-
    feature all-gather), dense towers data-parallel (replicated weights).
  * GNN: weights replicated; graph sharded over the batch axes.
"""
from __future__ import annotations

import re
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

PyTree = Any


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


# rules: (regex, ndim | None, PartitionSpec factory(batch_axes))
def _lm_rules(fsdp: bool, model_size: int):
    d2 = ("data",) if fsdp else (None,)

    def fit(spec: P, shape) -> P:
        """Drop mesh axes from dims whose size doesn't divide (e.g. granite's
        vocab 49155 on a 16-way axis): move 'model' to the next divisible
        free dim, else replicate that dim."""
        dims = list(tuple(spec) + (None,) * (len(shape) - len(tuple(spec))))
        for i, (d, sz) in enumerate(zip(dims, shape)):
            if d == "model" and sz % model_size != 0:
                dims[i] = None
                for j, (dj, sj) in enumerate(zip(dims, shape)):
                    if dj is None and sj % model_size == 0 and sj >= model_size:
                        dims[j] = "model"
                        break
        return P(*dims)

    def rules(path: str, ndim: int, shape):
        spec = None
        if re.search(r"\['embed'\]", path) and ndim == 2:
            spec = P("model", d2[0])
        elif re.search(r"\['unembed'\].*\['w'\]", path):
            spec = P(d2[0], "model")
        elif re.search(r"\['(wq|wk|wv)'\].*\['w'\]", path):
            spec = P(d2[0], "model")
        elif re.search(r"\['(wq|wk|wv)'\].*\['b'\]", path):
            spec = P("model")
        elif re.search(r"\['wo'\].*\['w'\]", path):
            spec = P("model", d2[0])
        # MoE expert stacks (E, d, f) / (E, f, d) — EP over model
        elif re.search(r"\['(wg|wu|wd)'\]$", path) and ndim == 3:
            spec = P("model", None, d2[0])
        # dense SwiGLU
        elif re.search(r"\['(wg|wu)'\].*\['w'\]", path):
            spec = P(d2[0], "model")
        elif re.search(r"\['wd'\].*\['w'\]", path):
            spec = P("model", d2[0])
        if spec is None:
            return None                  # router, norms, biases → replicated
        return fit(spec, shape)
    return rules


def lm_param_pspecs(params: PyTree, *, scan_layers: bool, fsdp: bool = False,
                    model_axis_size: int = 16) -> PyTree:
    base = _lm_rules(fsdp, model_axis_size)

    def one(path, leaf):
        p = _path_str(path)
        if scan_layers and "['layers']" in p:
            # scan stacking adds a leading L axis — apply the rule to the
            # trailing dims, then shift right
            spec = base(p, leaf.ndim - 1, leaf.shape[1:])
            return P(*(None,) + tuple(spec)) if spec is not None else P()
        return base(p, leaf.ndim, leaf.shape) or P()
    return jax.tree_util.tree_map_with_path(one, params)


def recsys_param_pspecs(params: PyTree, *, model_axis_size: int = 16) -> PyTree:
    """Embedding tables model-parallel: column (dim) sharding when the
    embedding dim divides the axis (local lookups, tiny all-gather at
    interaction); otherwise row (vocab) sharding — the classic table
    placement for narrow tables (xdeepfm's dim-10 tables)."""
    def one(path, leaf):
        p = _path_str(path)
        if ("['tables']" in p or "['item_tables']" in p) \
                and leaf.ndim == 3:                   # (F, V, D)
            if leaf.shape[2] % model_axis_size == 0:
                return P(None, None, "model")
            return P(None, "model", None)             # row-sharded
        if "['item_table']" in p and leaf.ndim == 2:  # (V, D)
            if leaf.shape[1] % model_axis_size == 0:
                return P(None, "model")
            return P("model", None)
        return P()                                    # dense towers replicated
    return jax.tree_util.tree_map_with_path(one, params)


def gnn_param_pspecs(params: PyTree) -> PyTree:
    return jax.tree_util.tree_map(lambda _: P(), params)


def param_pspecs(family: str, cfg, params: PyTree, *, fsdp: bool = False) -> PyTree:
    if family == "lm":
        return lm_param_pspecs(params, scan_layers=getattr(cfg, "scan_layers", False),
                               fsdp=fsdp)
    if family == "recsys":
        return recsys_param_pspecs(params)
    if family == "gnn":
        return gnn_param_pspecs(params)
    raise ValueError(family)


# ------------------------------------------------------------- batch specs


def recsys_batch_pspecs(batch: PyTree, baxes: tuple[str, ...]) -> PyTree:
    bx = baxes if len(baxes) > 1 else baxes[0]

    def one(path, leaf):
        p = _path_str(path)
        if "candidates" in p:                          # (B=1, C): shard C
            return P(None, bx)
        if leaf.shape and leaf.shape[0] == 1:          # retrieval: B=1 leaves
            return P(*((None,) * leaf.ndim))           # stay replicated
        return P(*((bx,) + (None,) * (leaf.ndim - 1)))
    return jax.tree_util.tree_map_with_path(one, batch)


def lm_batch_pspecs(batch: PyTree, baxes: tuple[str, ...]) -> PyTree:
    bx = baxes if len(baxes) > 1 else baxes[0]
    return jax.tree_util.tree_map(
        lambda leaf: P(*((bx,) + (None,) * (leaf.ndim - 1))), batch)


def lm_cache_pspecs(caches: PyTree, baxes: tuple[str, ...],
                    *, model_axis_size: int = 0) -> PyTree:
    """KV caches (B, T, Hkv, D): batch over data axes; kv heads over `model`
    when divisible.  When kv heads (2/4/8) cannot split a 16-way axis, shard
    the TIME dim over `model` instead (decode-time context parallelism: each
    model rank scans its slice of the cache, softmax reduces across ranks) —
    a replicated 32k cache would otherwise cost model_axis× the HBM
    (measured 195 GiB/dev for yi-34b decode)."""
    bx = baxes if len(baxes) > 1 else baxes[0]

    def one(path, leaf):
        if leaf.ndim == 4:
            hkv = leaf.shape[2]
            if model_axis_size and hkv % model_axis_size == 0:
                return P(bx, None, "model", None)
            return P(bx, "model", None, None)          # time-sharded
        return P(bx)                                   # pos (B,)
    return jax.tree_util.tree_map_with_path(one, caches)


def gnn_batch_pspecs(batch: PyTree, baxes: tuple[str, ...]) -> PyTree:
    bx = baxes if len(baxes) > 1 else baxes[0]

    def one(path, leaf):
        p = _path_str(path)
        if "edge_index" in p and leaf.ndim == 2:       # (2, E): shard edges
            return P(None, bx)
        if "edge_index" in p and leaf.ndim == 3:       # (G, 2, E): shard graphs
            return P(bx, None, None)
        return P(*((bx,) + (None,) * (leaf.ndim - 1)))
    return jax.tree_util.tree_map_with_path(one, batch)


def to_shardings(mesh, pspecs: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), pspecs,
        is_leaf=lambda x: isinstance(x, P))
