"""The fleet driver's stage protocol, and its telemetry stage.

``cluster_sim.drive_fleet`` is a window loop; each opt-in layer is a
:class:`FleetStage` whose hooks the loop calls at its events, stage by
stage in one fixed order.  Obs reads the loop's objects by attribute
only, so it stays importable without the cluster package.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs.attribution import AttributionReport, latency_attribution
from repro.obs.export import to_prometheus
from repro.obs.metrics import FleetTimeline, MetricsRegistry, observe_fanout
from repro.obs.spans import SpanTable

__all__ = ["FleetStage", "RunTelemetry", "TelemetryStage", "node_name"]


@dataclasses.dataclass
class RunTelemetry:
    """Everything one ``drive_fleet(telemetry=True)`` run observed."""
    spans: SpanTable
    registry: MetricsRegistry
    timeline: FleetTimeline

    def attribution(self, percentiles: tuple[float, ...] = (50.0, 95.0, 99.0),
                    band_frac: float = 0.02) -> AttributionReport:
        return latency_attribution(self.spans, percentiles,
                                   band_frac=band_frac)

    def prometheus(self) -> str:
        return to_prometheus(self.registry)


class FleetStage:
    """One opt-in layer of the fleet loop; every hook is a no-op here.
    ``run`` is the loop's per-run state (``times``, ``done``,
    ``horizon``, ``realtime``, ``controller``, ``errors_by_node`` and
    ``collected``, the realtime nodes' final batches); ``rec`` is a
    ``cluster.backend.Completions`` batch."""

    def start(self, run) -> None:
        """Once, after the initial fleet exists."""

    def begin_window(self, active) -> None:
        """The window's SERVING nodes are known."""

    def lookup(self, idx: np.ndarray, t: np.ndarray):
        """Before routing: ``(hit_mask, t_done, lat_ms)`` for queries the
        stage answers itself (only the cache does), else None."""

    def answered(self, idx, t_done, lat_ms) -> None:
        """Queries a stage answered before routing."""

    def completed(self, rec, nodes) -> None:
        """Finished queries; ``rec.node`` indexes ``nodes``."""

    def exchanged(self, b, idx) -> None:
        """One submit to ``b`` (``idx`` its queries) or poll (None)."""

    def rerouted(self, idx: np.ndarray, t: np.ndarray) -> None:
        """Orphans re-submitted at ``t``."""

    def shed(self, idx: np.ndarray) -> None:
        """Queries dropped unserved."""

    def end_window(self, w) -> None:
        """The boundary: ``w`` is the window record stages read and
        write (``snap``, ``diagnoses``)."""

    def finish(self, run) -> None:
        """After the final drain and collection."""


def node_name(b) -> str:
    return f"{b.pool}[{b.index_in_pool}]"


def observe_by_node(reg: MetricsRegistry, name: str, rec, nodes,
                    values: np.ndarray, mask=None, also=()) -> None:
    """Fold a batch's per-query ``values`` (rows ``mask``, default all)
    into ``name{node=...}`` and the ``also`` rollups: one node's batch as
    one digest, a grouped batch carved per node by its segmentation."""
    if rec.bounds is None:
        observe_fanout(values if mask is None else values[mask],
                       reg.histogram(name, node=node_name(nodes[0])), *also)
        return
    groups, extra = rec.node, {"order": rec.order, "bounds": rec.bounds}
    if mask is not None:
        groups, values, extra = groups[mask], values[mask], {}
    reg.observe_grouped(name, "node", groups, values,
                        fmt=lambda i: node_name(nodes[int(i)]), also=also,
                        **extra)


class TelemetryStage(FleetStage):
    """``drive_fleet(telemetry=True)``: the :class:`RunTelemetry` —
    spans, per-node (and fleet) and per-model latency, error, re-route
    and retry counters, fleet-shape gauges, a snapshot per boundary.
    Latency runs from when a node was handed the query."""

    def __init__(self, times: np.ndarray, model_ids=None):
        self.tel = RunTelemetry(spans=SpanTable(times),
                                registry=MetricsRegistry(),
                                timeline=FleetTimeline())
        self.reg = self.tel.registry
        self.spans = self.tel.spans
        self.fleet_hist = self.reg.histogram("fleet_latency_ms")
        self.model_ids = model_ids
        self._spans_on: set[tuple] = set()
        self._retries: dict[tuple, int] = {}     # per-node retry_count seen

    def _origin(self, rec) -> np.ndarray:
        return rec.t_arrival

    def begin_window(self, active) -> None:
        for b in active:
            if b.key not in self._spans_on:
                b.enable_spans()
                self._spans_on.add(b.key)

    def answered(self, idx, t_done, lat_ms) -> None:
        self.spans.mark_cache_hit(idx, t_done)
        observe_fanout(lat_ms, self.fleet_hist)

    def completed(self, rec, nodes) -> None:
        ok = None if rec.ok.all() else rec.ok        # None: every row
        if rec.ok.any():
            observe_by_node(self.reg, "node_latency_ms", rec, nodes,
                            (rec.t_done - self._origin(rec)) * 1e3, ok,
                            (self.fleet_hist,))
        if ok is not None:
            for i, n in zip(*np.unique(rec.node[~ok], return_counts=True)):
                self.reg.counter("node_errors",
                                 node=node_name(nodes[int(i)])).inc(int(n))
        self._stamp(rec, ok)

    def _stamp(self, rec, ok) -> None:
        sel = slice(None) if ok is None else ok
        xs = rec.t_exec_start
        self.spans.record_many(rec.idx[sel], rec.t_released[sel],
                               np.nan if xs is None else xs[sel],
                               rec.t_done[sel])

    def exchanged(self, b, idx) -> float:
        """Book ``b``'s RPC retry stall on the queries a submit carried
        and in the fleet counters; returns its seconds."""
        s = b.take_retry_s()
        if s > 0.0:
            if idx is not None:
                self.spans.add_retry(idx, s)
            self.reg.counter("rpc_retry_seconds").inc(s)
        d = b.retry_count - self._retries.get(b.key, 0)
        if d:
            self.reg.counter("rpc_retries").inc(d)
            self._retries[b.key] = b.retry_count
        return s

    def rerouted(self, idx, t) -> None:
        self.spans.mark_reroute(idx, t)
        self.reg.counter("queries_rerouted").inc(len(idx))

    def shed(self, idx) -> None:
        self.spans.mark_shed(idx)

    def end_window(self, w) -> None:
        if self.model_ids is not None and len(w.lat_s):
            # per-model e2e latency of what completed in the window
            self.reg.observe_grouped("model_latency_ms", "model",
                                     self.model_ids[w.lat_idx],
                                     w.lat_s * 1e3)
        self.reg.gauge("serving_nodes").set(len(w.active))
        self.reg.gauge("booting_nodes").set(w.booting)
        self.reg.counter("booting_node_seconds").inc(w.booting * w.width)
        w.snap = self.tel.timeline.snapshot(
            self.reg, w.t_s, w.width,
            extra={"offered_qps": w.offered, "n_active": len(w.active),
                   "p95_ms": w.p95, "ctl_s": w.ctl_s})

    def finish(self, run) -> None:
        for rec in run.collected:
            self._stamp(rec, None if rec.ok.all() else rec.ok)
        # the driver's done array is authoritative (kill rollbacks,
        # errored-query drops): adopt it and book the run-level counters
        self.spans.finalize(run.done)
        n_done = int((~np.isnan(run.done)).sum())
        self.reg.counter("queries_completed").inc(n_done)
        self.reg.counter("queries_dropped").inc(len(run.done) - n_done)
        for name, cnt in run.errors_by_node.items():
            c = self.reg.counter("node_errors", node=name)
            if c.value < cnt:        # drain-time errors the window
                c.inc(cnt - c.value)  # monitor never saw
