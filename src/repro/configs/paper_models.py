"""The eight industry-representative recommendation models of DeepRecInfra
(paper Table I) with their SLA tail-latency targets (paper Table II).

Parameter choices follow Table I exactly where given; where the paper says
"Tens" of tables or "~80" lookups we use the concrete values from the cited
sources ([10] for DLRM-RMC*, [5]/[6] for DIN/DIEN).
"""
from __future__ import annotations

import dataclasses

from repro.configs.registry import ArchSpec, register
from repro.models.recsys import RecConfig


@dataclasses.dataclass(frozen=True)
class SLATarget:
    """p95 tail-latency target in ms (paper Table II).  low/high = ∓50%."""
    medium_ms: float

    @property
    def low_ms(self) -> float:
        return self.medium_ms * 0.5

    @property
    def high_ms(self) -> float:
        return self.medium_ms * 1.5

    def get(self, tier: str) -> float:
        return {"low": self.low_ms, "medium": self.medium_ms,
                "high": self.high_ms}[tier]


SLA_TARGETS: dict[str, SLATarget] = {
    "dlrm-rmc1": SLATarget(100.0),
    "dlrm-rmc2": SLATarget(400.0),
    "dlrm-rmc3": SLATarget(100.0),
    "ncf": SLATarget(5.0),
    "wnd": SLATarget(25.0),
    "mt-wnd": SLATarget(25.0),
    "din": SLATarget(100.0),
    "dien": SLATarget(35.0),
}

# runtime bottleneck classes from paper Table II (used by benchmarks)
BOTTLENECK = {
    "dlrm-rmc1": "embedding", "dlrm-rmc2": "embedding", "dlrm-rmc3": "mlp",
    "ncf": "mlp", "wnd": "mlp", "mt-wnd": "mlp",
    "din": "embedding+attention", "dien": "attention-gru",
}

_V = 1_000_000          # rows per table (paper: tens of MBs–GBs per table)

PAPER_MODELS: dict[str, RecConfig] = {
    "ncf": RecConfig(
        name="ncf", interaction="gmf", n_tables=4, vocab=_V, embed_dim=64,
        hotness=1, predict_fc=(256, 256, 128, 1)),
    "wnd": RecConfig(
        name="wnd", interaction="concat", n_dense=1024, n_tables=20,
        vocab=_V, embed_dim=32, hotness=1, predict_fc=(1024, 512, 256, 1)),
    "mt-wnd": RecConfig(
        name="mt-wnd", interaction="concat", n_dense=1024, n_tables=20,
        vocab=_V, embed_dim=32, hotness=1, predict_fc=(1024, 512, 256, 1),
        n_tasks=4),
    "dlrm-rmc1": RecConfig(
        name="dlrm-rmc1", interaction="dot", n_dense=256,
        dense_fc=(256, 128, 32), predict_fc=(256, 64, 1), n_tables=10,
        vocab=_V, embed_dim=32, hotness=80),
    "dlrm-rmc2": RecConfig(
        name="dlrm-rmc2", interaction="dot", n_dense=256,
        dense_fc=(256, 128, 32), predict_fc=(512, 128, 1), n_tables=40,
        vocab=_V, embed_dim=32, hotness=80),
    "dlrm-rmc3": RecConfig(
        name="dlrm-rmc3", interaction="dot", n_dense=2560,
        dense_fc=(2560, 512, 32), predict_fc=(512, 128, 1), n_tables=10,
        vocab=_V, embed_dim=32, hotness=20),
    "din": RecConfig(
        name="din", interaction="din", n_tables=8, vocab=_V, embed_dim=64,
        hotness=1, seq_len=256, item_vocab=_V, predict_fc=(200, 80, 1)),
    # widths Table I leaves out from the authors' code (mouna99/dien,
    # script/train.py): 18 per id, a behavior is item ⊕ category (36),
    # GRU hidden 36, history of 100
    "dien": RecConfig(
        name="dien", interaction="dien", n_tables=8, vocab=_V, embed_dim=18,
        hotness=1, seq_len=100, item_vocab=_V, item_fields=2, gru_hidden=36,
        predict_fc=(200, 80, 1)),
}


def _smoke(cfg: RecConfig) -> RecConfig:
    """Reduced config of the same family for CPU tests."""
    embed_dim = min(cfg.embed_dim, 8)
    dense_fc = tuple(min(w, 16) for w in cfg.dense_fc)
    if dense_fc:
        # DLRM invariant: bottom-MLP output feeds the dot interaction as a
        # feature row, so its width must equal embed_dim
        dense_fc = dense_fc[:-1] + (embed_dim,)
    return dataclasses.replace(
        cfg, name=cfg.name + "-smoke",
        n_tables=min(cfg.n_tables, 4), vocab=min(cfg.vocab, 100),
        embed_dim=embed_dim, hotness=min(cfg.hotness, 4),
        n_dense=min(cfg.n_dense, 16), dense_fc=dense_fc,
        predict_fc=tuple(min(w, 16) for w in cfg.predict_fc),
        seq_len=min(cfg.seq_len, 8), item_vocab=min(cfg.item_vocab, 100),
        gru_hidden=min(cfg.gru_hidden, 8))


for _name, _cfg in PAPER_MODELS.items():
    register(ArchSpec(
        arch_id=_name, family="recsys", config=_cfg, smoke_config=_smoke(_cfg),
        source="DeepRecSys Table I", notes=f"bottleneck: {BOTTLENECK[_name]}; "
        f"SLA medium {SLA_TARGETS[_name].medium_ms} ms"))
