"""A second model family for the harness's tests, copied in as
``models/toy_seq.py``: the program's DIN smoke configuration (the
registry's ``din`` smoke config, whatever sizes a configuration file
gives), whose inputs are ``sparse``, ``history``, ``hist_mask`` and
``target`` and no ``dense``.

It tests the plumbing, not a model: its "reference" is the program's own
``recsys.forward`` at ``highest`` on the unpacked weights of
``recsys.init``, so a run against it shows that every pool entry reaches
the served forward and that each answer is checked against its own pool
row, not that DIN is computed right.  No cell of it reads a roofline, so
it has no cost functions.
"""
import jax
import jax.numpy as jnp

import traffic
from repro.configs.registry import get
from repro.models import recsys

SMOKE = get("din").smoke_config


def rec_config(cfg: dict):
    return SMOKE


def draw_pool(cfg: dict, rows: int, key) -> dict:
    rc = rec_config(cfg)

    @jax.jit
    def draw(key):
        ks, kh, kl, kt = jax.random.split(key, 4)
        lengths = jax.random.randint(kl, (rows, 1), 1, rc.seq_len + 1)
        return {"sparse": traffic.log_uniform_ids(
                    ks, (rows, rc.n_tables, rc.hotness), rc.vocab),
                "history": traffic.log_uniform_ids(
                    kh, (rows, rc.seq_len), rc.item_vocab),
                "hist_mask": jnp.arange(rc.seq_len)[None] < lengths,
                "target": traffic.log_uniform_ids(kt, (rows,),
                                                  rc.item_vocab)}
    return jax.device_get(draw(key))


def init_weights(seed: int, cfg: dict) -> dict:
    return recsys.init(jax.random.PRNGKey(seed), rec_config(cfg))


def forward(w: dict, batch: dict, *, store: str = "float32",
            precision: str = "highest") -> jax.Array:
    if store != "float32":
        raise ValueError("the toy reference computes in float32 only")
    with jax.default_matmul_precision(precision):
        return recsys.forward(w, SMOKE, batch)
