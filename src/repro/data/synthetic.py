"""Synthetic data: batch builders (real arrays, planted learnable signal) and
``ShapeDtypeStruct`` spec builders (dry-run stand-ins, no allocation).

The spec builders and batch builders share one layout function per family, so
the dry-run lowers exactly the shapes the runtime feeds.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.gnn import GCNConfig
from repro.models.lm import LMConfig
from repro.models.recsys import RecConfig

Spec = jax.ShapeDtypeStruct


# ------------------------------------------------------------------ recsys


def recsys_layout(cfg: RecConfig, batch: int, *, n_candidates: int = 0,
                  with_label: bool = True) -> dict[str, tuple[tuple, Any]]:
    """name → (shape, dtype) for every input leaf."""
    out: dict[str, tuple[tuple, Any]] = {}
    if cfg.n_dense:
        out["dense"] = ((batch, cfg.n_dense), jnp.float32)
    if cfg.n_tables:
        out["sparse"] = ((batch, cfg.n_tables, cfg.hotness), jnp.int32)
    if cfg.has_history:
        ids = _behavior_ids(cfg)
        out["history"] = ((batch, cfg.seq_len) + ids, jnp.int32)
        out["hist_mask"] = ((batch, cfg.seq_len), jnp.bool_)
        if n_candidates == 0:
            out["target"] = ((batch,) + ids, jnp.int32)
    if n_candidates:
        out["candidates"] = ((batch, n_candidates), jnp.int32)
    if with_label and not n_candidates:
        shape = (batch,) if cfg.n_tasks == 1 else (batch, cfg.n_tasks)
        out["label"] = (shape, jnp.float32)
    return out


def _behavior_ids(cfg: RecConfig) -> tuple:
    """Trailing axis of one behavior's ids: its K id fields (DIEN's item
    and category), none where a behavior is one item id."""
    return (cfg.item_fields,) if cfg.item_fields else ()


def recsys_specs(cfg: RecConfig, batch: int, **kw) -> dict[str, Spec]:
    return {k: Spec(s, d) for k, (s, d) in recsys_layout(cfg, batch, **kw).items()}


def recsys_batch(rng: np.random.Generator, cfg: RecConfig, batch: int, *,
                 n_candidates: int = 0, with_label: bool = True) -> dict:
    """Real batch with a planted signal: the label depends linearly on the
    dense features and on a per-id latent propensity, so training reduces
    loss measurably.  Leaves are host numpy arrays: a jitted consumer
    transfers them, and the serving runtime slices and pads them on the
    host without compiling anything per request shape."""
    out: dict = {}
    logit = np.zeros(batch, np.float32)
    if cfg.n_dense:
        dense = rng.normal(size=(batch, cfg.n_dense)).astype(np.float32)
        w = _planted_w(cfg.n_dense)
        logit += dense @ w
        out["dense"] = dense
    if cfg.n_tables:
        # power-law id popularity (production embedding access pattern)
        sparse = _zipf_ids(rng, (batch, cfg.n_tables, cfg.hotness), cfg.vocab)
        logit += ((sparse.sum(axis=(1, 2)) % 7) - 3) * 0.3
        out["sparse"] = sparse.astype(np.int32)
    if cfg.has_history:
        ids = _behavior_ids(cfg)
        hist = _zipf_ids(rng, (batch, cfg.seq_len) + ids, cfg.item_vocab)
        out["history"] = hist.astype(np.int32)
        lengths = rng.integers(1, cfg.seq_len + 1, size=batch)
        out["hist_mask"] = (np.arange(cfg.seq_len)[None] < lengths[:, None])
        if n_candidates == 0:
            tgt = _zipf_ids(rng, (batch,) + ids, cfg.item_vocab).astype(np.int32)
            out["target"] = tgt
            logit += ((tgt.reshape(batch, -1)[:, 0] % 5) - 2) * 0.2
    if n_candidates:
        out["candidates"] = _zipf_ids(
            rng, (batch, n_candidates), cfg.item_vocab or cfg.vocab).astype(np.int32)
    if with_label and not n_candidates:
        p = 1.0 / (1.0 + np.exp(-logit))
        lab = (rng.random(batch) < p).astype(np.float32)
        if cfg.n_tasks > 1:
            lab = np.stack([lab] + [(rng.random(batch) < p).astype(np.float32)
                                    for _ in range(cfg.n_tasks - 1)], axis=1)
        out["label"] = lab
    return out


def _planted_w(n: int) -> np.ndarray:
    r = np.random.default_rng(1234)
    return (r.normal(size=n) / np.sqrt(n)).astype(np.float32)


def _zipf_ids(rng, shape, vocab: int) -> np.ndarray:
    """Zipf-ish ids in [0, vocab): heavy head, long tail."""
    u = rng.random(size=shape)
    ids = np.floor(vocab ** u).astype(np.int64) - 1
    return np.clip(ids, 0, vocab - 1)


# ---------------------------------------------------------------------- lm


def lm_specs(cfg: LMConfig, batch: int, seq: int) -> dict[str, Spec]:
    return {"tokens": Spec((batch, seq), jnp.int32),
            "labels": Spec((batch, seq), jnp.int32)}


def lm_batch(rng: np.random.Generator, cfg: LMConfig, batch: int, seq: int) -> dict:
    """Markov-chain token stream (learnable next-token structure)."""
    v = cfg.vocab
    toks = np.empty((batch, seq + 1), np.int64)
    toks[:, 0] = rng.integers(0, v, size=batch)
    noise = rng.random(size=(batch, seq)) < 0.15
    rand = rng.integers(0, v, size=(batch, seq))
    for t in range(seq):
        nxt = (toks[:, t] * 31 + 17) % v
        toks[:, t + 1] = np.where(noise[:, t], rand[:, t], nxt)
    return {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "labels": jnp.asarray(toks[:, 1:], jnp.int32)}


def decode_specs(cfg: LMConfig, batch: int, cache_len: int):
    """Specs for one decode step: token + per-layer KV caches."""
    tok = Spec((batch,), jnp.int32)
    dt = jnp.dtype(cfg.dtype)
    cache = [{"k": Spec((batch, cache_len, cfg.n_kv_heads, cfg.hd), dt),
              "v": Spec((batch, cache_len, cfg.n_kv_heads, cfg.hd), dt),
              "pos": Spec((batch,), jnp.int32)} for _ in range(cfg.n_layers)]
    return tok, cache


# --------------------------------------------------------------------- gnn


def gnn_full_specs(cfg: GCNConfig, n_nodes: int, n_edges: int,
                   with_label: bool = True) -> dict[str, Spec]:
    out = {"x": Spec((n_nodes, cfg.d_feat), jnp.float32),
           "edge_index": Spec((2, n_edges), jnp.int32)}
    if with_label:
        out["labels"] = Spec((n_nodes,), jnp.int32)
        out["train_mask"] = Spec((n_nodes,), jnp.bool_)
    return out


def random_graph(rng: np.random.Generator, n_nodes: int, n_edges: int,
                 d_feat: int, n_classes: int) -> dict:
    """Community-structured random graph: features and labels correlate, so
    GCN training measurably improves accuracy."""
    comm = rng.integers(0, n_classes, size=n_nodes)
    # ~80% intra-community edges
    src = rng.integers(0, n_nodes, size=n_edges)
    intra = rng.random(n_edges) < 0.8
    dst = np.where(
        intra,
        _same_comm_partner(rng, comm, src, n_classes, n_nodes),
        rng.integers(0, n_nodes, size=n_edges))
    x = np.eye(n_classes, dtype=np.float32)[comm]
    x = np.pad(x, ((0, 0), (0, max(0, d_feat - n_classes))))[:, :d_feat]
    x = x + rng.normal(scale=0.5, size=x.shape).astype(np.float32)
    mask = rng.random(n_nodes) < 0.6
    return {"x": jnp.asarray(x), "edge_index": jnp.asarray(
                np.stack([src, dst]).astype(np.int32)),
            "labels": jnp.asarray(comm.astype(np.int32)),
            "train_mask": jnp.asarray(mask)}


def _same_comm_partner(rng, comm, src, n_classes, n_nodes):
    # pick a random node, then shift it into src's community block heuristic:
    # nodes are unordered, so just resample from nodes with matching label
    order = np.argsort(comm, kind="stable")
    sorted_comm = comm[order]
    starts = np.searchsorted(sorted_comm, np.arange(n_classes), side="left")
    ends = np.searchsorted(sorted_comm, np.arange(n_classes), side="right")
    c = comm[src]
    lo, hi = starts[c], np.maximum(ends[c], starts[c] + 1)
    pick = lo + (rng.random(len(src)) * (hi - lo)).astype(np.int64)
    return order[np.minimum(pick, n_nodes - 1)]


def graph_to_csr(n_nodes: int, edge_index: np.ndarray):
    src, dst = np.asarray(edge_index)
    order = np.argsort(dst, kind="stable")
    indices = src[order]
    indptr = np.searchsorted(dst[order], np.arange(n_nodes + 1))
    return indptr, indices


def molecule_batch(rng: np.random.Generator, batch: int, n_nodes: int,
                   n_edges: int, d_feat: int, n_classes: int) -> dict:
    x = rng.normal(size=(batch, n_nodes, d_feat)).astype(np.float32)
    ei = rng.integers(0, n_nodes, size=(batch, 2, n_edges)).astype(np.int32)
    mask = np.ones((batch, n_nodes), bool)
    # label correlates with mean feature sign (learnable)
    labels = (x.mean(axis=(1, 2)) > 0).astype(np.int32)
    return {"x": jnp.asarray(x), "edge_index": jnp.asarray(ei),
            "node_mask": jnp.asarray(mask), "labels": jnp.asarray(labels)}


def molecule_specs(cfg: GCNConfig, batch: int, n_nodes: int, n_edges: int):
    return {"x": Spec((batch, n_nodes, cfg.d_feat), jnp.float32),
            "edge_index": Spec((batch, 2, n_edges), jnp.int32),
            "node_mask": Spec((batch, n_nodes), jnp.bool_),
            "labels": Spec((batch,), jnp.int32)}


def minibatch_block_specs(cfg: GCNConfig, batch_nodes: int, fanouts):
    """Worst-case (no-dedup) block shapes for the sampled-minibatch dry-run."""
    blocks = []
    n_dst = batch_nodes
    sizes = []
    for f in fanouts:
        n_edge = n_dst * f
        n_src = n_dst + n_edge
        sizes.append((n_edge, n_src, n_dst))
        n_dst = n_src
    # inner-first ordering like sample_neighbors
    for n_edge, n_src, n_dst_l in reversed(sizes):
        blocks.append((Spec((2, n_edge), jnp.int32), n_src, n_dst_l))
    x_input = Spec((sizes[-1][1], cfg.d_feat), jnp.float32)
    return x_input, blocks
