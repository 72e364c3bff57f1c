"""The fleet driver's window loop and its stages.

``drive_fleet`` is a window loop with one stage per opt-in layer
(telemetry, cache, offload tuning, SLO, autoscaling).  These tests pin
the two properties the split rests on: with no option set the loop
builds no stage and drives a live node with exactly the calls the chip
benchmark's harness relies on, and each stage folds a query the same
way whichever engine path (grouped or per node) completed it.
"""
import numpy as np
import pytest

import repro.cluster.cluster_sim as cluster_sim
from repro.cluster import (Fleet, NodeSpec, Pool, drive_fleet, make_router,
                           sim_backends, simulate_fleet)
from repro.cluster.backend import CompletedQuery, NodeBackend
from repro.cluster.cluster_sim import OffloadTuning
from repro.cluster.live import BucketedDeviceModel
from repro.core.latency_model import (GPU_1080TI, AnalyticalDeviceModel,
                                      TableDeviceModel)
from repro.core.query_gen import PRODUCTION, sample_trace
from repro.obs import BurnRateRule, SloEngine, SloObjective

pytestmark = pytest.mark.cluster

CPU = TableDeviceModel(np.array([1., 4, 16, 64, 256, 1024]),
                       np.array([.0008, .001, .0018, .0045, .015, .058]))
ACCEL = AnalyticalDeviceModel(
    flops_per_sample=5e6, mem_bytes_per_sample=1e5, in_bytes_per_sample=4e3,
    **GPU_1080TI)


class _Recording(NodeBackend):
    """A realtime node that completes each query 1 ms after its arrival
    and logs every backend call the driver makes on it."""

    realtime = True

    def __init__(self):
        self.spec = NodeSpec(cpu=CPU)
        self.pool = "chip"
        self.calls: list[tuple] = []
        self._done: list[CompletedQuery] = []
        self._cursor = 0

    def _log(self, name, *args):
        self.calls.append((name, *args))

    def start(self, t0):
        self._log("start")

    def enable_spans(self):
        self._log("enable_spans")

    def dead(self):
        self._log("dead")
        return False

    def submit(self, idx, times, sizes, model_ids=None):
        self._log("submit", len(idx))
        self._done += [CompletedQuery(int(i), float(t), float(t) + 1e-3,
                                      t_released=float(t),
                                      t_exec_start=float(t))
                       for i, t in zip(idx, times)]

    def advance_to(self, t):
        self._log("advance_to")

    def take_new_records(self):
        self._log("take_new_records")
        fresh = self._done[self._cursor:]
        self._cursor = len(self._done)
        return fresh

    def take_retry_s(self):
        self._log("take_retry_s")
        return 0.0

    def drain(self, timeout=120.0):
        self._log("drain", timeout)

    def completed_records(self):
        self._log("completed_records")
        return list(self._done)

    def idle(self, t):
        self._log("idle")
        return True

    def cancel_pending(self, t):
        self._log("cancel_pending")
        return []

    def set_offload_threshold(self, threshold):
        self._log("set_offload_threshold")

    def close(self):
        self._log("close")


def test_chip_path_builds_no_stage(monkeypatch):
    """One live node and no option (the chip benchmark's call): the loop
    must build no stage at all."""
    built = []
    real = cluster_sim._stages

    def spy(*a, **kw):
        stages, tel = real(*a, **kw)
        built.extend(stages)
        return stages, tel

    monkeypatch.setattr(cluster_sim, "_stages", spy)
    b = _Recording()
    times = np.sort(np.random.default_rng(0).uniform(0.0, 0.05, 40))
    r = drive_fleet(times, np.full(40, 8), [b], make_router("round_robin"),
                    drain_timeout=0.25)
    assert built == []
    assert r.n_queries == 40 and r.telemetry is None and r.slo is None


def test_chip_path_calls_the_node_in_the_harness_order():
    """The calls, in order, that ``benchmarks/chip/harness.serve_window``
    relies on: start, the health probe, one submit of the whole trace,
    one advance to the window's end, one poll, the drain with the given
    timeout and the final collection.  ``close`` stays the caller's."""
    b = _Recording()
    times = np.sort(np.random.default_rng(1).uniform(0.0, 0.05, 30))
    r = drive_fleet(times, np.full(30, 4), [b], make_router("round_robin"),
                    drain_timeout=0.5)
    assert b.calls == [("start",), ("dead",), ("submit", 30),
                       ("advance_to",), ("take_new_records",),
                       ("drain", 0.5), ("completed_records",)]
    assert r.n_queries == 30 and r.dropped == 0
    np.testing.assert_allclose(r.p50_ms, 1.0)


def _span_hist(reg, name):
    h = reg.histogram(name).total
    return h.n, h.n_zero, h.counts, h.vmin, h.vmax, h.total


def _overdriven(layer):
    """Grouped and per-node runs of one trace with one layer on."""
    rng = np.random.default_rng(4)
    times, sizes = sample_trace(rng, 1200, PRODUCTION)
    times = times / 3000.0
    if layer == "offload_tuning":
        fleet = Fleet([Pool("gpu", NodeSpec(cpu=CPU, accel=ACCEL,
                                            batch_size=8,
                                            offload_threshold=450),
                            count=3)])
        kw = dict(telemetry=True, offload_tuning=OffloadTuning(sla_ms=0.05))
    else:
        spec = NodeSpec(cpu=BucketedDeviceModel(
            np.array([1, 2, 4, 8, 16, 32, 64]), np.full(7, 4e-3)),
            n_executors=2, batch_size=16, request_overhead_s=0.0)
        fleet = Fleet([Pool("cpu", spec, count=3)])
        kw = {}
    window = float(times[-1]) / 20

    def run(grouped):
        extra = dict(kw)
        if layer == "slo":
            extra["slo"] = SloEngine(SloObjective("p95", latency_ms=20.0),
                                     rules=(BurnRateRule(2, 1, 1.0),))
        return simulate_fleet(times, sizes, fleet, make_router("round_robin"),
                              window_s=window, grouped=grouped, **extra)
    return run(True), run(False)


@pytest.mark.parametrize("layer", ["offload_tuning", "slo"])
def test_grouped_and_per_node_folds_agree(layer):
    """The offload queueing fold and the SLO span-component fold see the
    same queries whether a window was advanced in one grouped pass or
    node by node: same threshold trajectory, same span histograms (the
    grouped pass sums a window in one batch, the per-node loop node by
    node, so only the float total may differ in its last bits), same
    incident log."""
    grouped, per_node = _overdriven(layer)
    tg, tp = grouped.telemetry, per_node.telemetry

    def thresholds(tel):
        return [sorted((k, v) for k, v in w.metrics.items()
                       if k.startswith(("offload_threshold",
                                        "offload_fraction",
                                        "queries_offloaded")))
                for w in tel.timeline.windows]
    assert thresholds(tg) == thresholds(tp)
    if layer == "offload_tuning":
        moved = {v for row in thresholds(tg) for k, v in row
                 if k.startswith("offload_threshold")}
        assert moved - {450.0}, "the controller must leave its first rung"
        for name in ("node_queue_cpu_ms", "node_queue_acc_ms"):
            for node in ("gpu[0]", "gpu[1]", "gpu[2]"):
                a = tg.registry.histogram(name, node=node).total
                b = tp.registry.histogram(name, node=node).total
                assert (a.n, a.counts, a.vmin, a.vmax) == \
                    (b.n, b.counts, b.vmin, b.vmax)
    else:
        for name in ("span_queueing_ms", "span_service_ms"):
            a, b = _span_hist(tg.registry, name), _span_hist(tp.registry,
                                                               name)
            assert a[0] > 0 and a[:5] == b[:5]
            assert a[5] == pytest.approx(b[5], rel=1e-9)
        eg, ep = grouped.slo, per_node.slo
        assert eg.incidents, "the overdriven trace must open an incident"
        assert [(a.t_s, a.kind) for a in eg.alerts] == \
            [(a.t_s, a.kind) for a in ep.alerts]
        assert [(d.t_s, d.verdict) for d in eg.diagnoses] == \
            [(d.t_s, d.verdict) for d in ep.diagnoses]
        assert [(i.t_start, i.t_end, i.dominant_verdict)
                for i in eg.incidents] == \
            [(i.t_start, i.t_end, i.dominant_verdict) for i in ep.incidents]


def test_explicit_sim_nodes_report_exec_starts_without_chunks():
    """A per-node sim window reports its exec starts through the
    backend's public surface; the driver's span table then matches what
    the node's own completion records say."""
    rng = np.random.default_rng(2)
    times = np.sort(rng.uniform(0.0, 0.2, 200))
    sizes = rng.integers(1, 64, 200).astype(np.int64)
    nodes = sim_backends(Fleet([Pool("sky", NodeSpec(cpu=CPU, batch_size=8),
                                     count=1)]).node_views())
    r = drive_fleet(times, sizes, nodes, make_router("round_robin"),
                    window_s=0.05, telemetry=True)
    recs = sorted(nodes[0].completed_records(), key=lambda q: q.index)
    spans = r.telemetry.spans
    assert [q.index for q in recs] == list(range(200))
    np.testing.assert_array_equal(spans.t_exec_start,
                                  [q.t_exec_start for q in recs])
    np.testing.assert_array_equal(spans.t_done, [q.t_done for q in recs])
