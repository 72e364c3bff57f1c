"""Fleet-level serving on a shared timeline, generic over node backends.

``drive_fleet`` is one window loop over the ``NodeBackend`` contract
(``cluster.backend``): at each boundary the lifecycle controller
(``cluster.lifecycle``) names the SERVING nodes and hands back what dead
nodes surrendered, a router splits the window across the nodes, each
node accepts its share with ``submit``, and completions are folded into
fleet-wide latencies — for ``SimNodeBackend``s in virtual time (one
grouped ``node_pass_many`` advance per window when every node is a
plain sim node) and for realtime backends (``cluster.live``,
``cluster.remote``) on the wall clock.  Each opt-in layer is a stage the
loop calls at fixed events without naming it (``repro.obs.telemetry
.FleetStage``): telemetry, SLO evaluation (``obs.slo``), the fleet-front
cache (``cluster.cache``), offload tuning (here) and autoscaling
(``cluster.autoscaler``); every engine hands finished queries to them as
one ``Completions`` batch.

``simulate_fleet`` runs a simulated fleet through ``drive_fleet``, or the
event-driven reference per node when per-node faults/contention are on;
``cluster_max_qps`` is the paper's y-axis lifted to the cluster: the
largest stationary rate whose fleet-wide p95 meets the SLA.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable

import numpy as np

from repro.cluster.autoscaler import Autoscaler, ScalingEvent, ScalingStage
from repro.cluster.backend import (BackendDied, Completions, NodeBackend,
                                   SimNodeBackend, grouped_eligible,
                                   submit_grouped)
from repro.cluster.cache import CacheConfig, CacheStage, FleetCache
from repro.cluster.fleet import Fleet
from repro.cluster.lifecycle import (FleetController, FleetFaults,
                                     LifecycleEvent, NodeState,
                                     SelfHealPolicy)
from repro.cluster.router import Router
from repro.core.latency_model import ContentionModel
from repro.core.query_gen import (PRODUCTION, PopularityDist, SizeDist,
                                  keyed_sizes, queries_from_arrays,
                                  rescale_trace, sample_trace)
from repro.core.scheduler import THRESHOLD_LADDER
from repro.core.simulator import (SUSTAIN_FRACTION, FaultConfig,
                                  _fast_eligible, bracket_bisect,
                                  event_done_times, latency_percentiles_ms,
                                  warm_bracket)
from repro.obs.slo import SloStage
from repro.obs.telemetry import (FleetStage, RunTelemetry, TelemetryStage,
                                 node_name, observe_by_node)
from repro.serve.runtime import OffloadController


@dataclasses.dataclass(frozen=True)
class OffloadTuning:
    """Enable the per-node online offload-threshold controller in
    ``drive_fleet`` (:class:`TuningStage`).  Requires ``telemetry=True``
    and ``window_s``."""
    sla_ms: float
    ladder: tuple = THRESHOLD_LADDER
    relax_frac: float = 0.6


def _threshold(b) -> float:
    t = b.spec.offload_threshold
    return float(t) if (t is not None and b.spec.accel is not None) \
        else np.inf


class TuningStage(FleetStage):
    """``drive_fleet(offload_tuning=...)``: an
    :class:`~repro.serve.runtime.OffloadController` per accelerator node,
    stepped at each boundary from the window's p99s of the node's e2e
    latency and its CPU-path vs accelerator-path queueing
    (``node_queue_cpu_ms``/``node_queue_acc_ms``, each batch split at the
    node's current threshold); a new threshold applies from the node's
    next window (``set_offload_threshold``) — paper Fig. 10's static
    per-node tuning, closed online."""

    def __init__(self, cfg: OffloadTuning, registry, sizes: np.ndarray):
        self.cfg, self.reg, self.sizes = cfg, registry, sizes
        self.tuners: dict[tuple, OffloadController] = {}
        self.offloaded = self.folded = 0

    def completed(self, rec, nodes) -> None:
        if rec.t_exec_start is None:
            return
        thr = np.fromiter((_threshold(b) for b in nodes), float, len(nodes))
        big = self.sizes[rec.idx] >= thr[rec.node]
        acc, cpu = big & rec.ok, ~big & rec.ok
        q = (rec.t_exec_start - rec.t_released) * 1e3
        self.offloaded += int(acc.sum())
        self.folded += int(rec.ok.sum())
        if acc.any():
            observe_by_node(self.reg, "node_queue_acc_ms", rec, nodes, q, acc)
        if cpu.any():
            observe_by_node(self.reg, "node_queue_cpu_ms", rec, nodes, q, cpu)

    def end_window(self, w) -> None:
        for b in w.active:       # before the snapshot steals the sketches
            if b.spec.accel is None:
                continue
            ctl = self.tuners.get(b.key)
            if ctl is None:
                ctl = self.tuners[b.key] = OffloadController(
                    sla_ms=self.cfg.sla_ms,
                    threshold=b.spec.offload_threshold,
                    ladder=self.cfg.ladder, relax_frac=self.cfg.relax_frac)
            name = node_name(b)
            thr = ctl.step(*(self.reg.histogram(m, node=name)
                             .window.quantile(0.99)
                             for m in ("node_latency_ms", "node_queue_cpu_ms",
                                       "node_queue_acc_ms")))
            if thr != b.spec.offload_threshold:
                b.set_offload_threshold(thr)
            self.reg.gauge("offload_threshold", node=name).set(thr)
        self.reg.gauge("offload_fraction").set(
            self.offloaded / self.folded if self.folded else 0.0)
        self.reg.counter("queries_offloaded").inc(self.offloaded)
        self.offloaded = self.folded = 0


@dataclasses.dataclass
class PoolStats:
    n_nodes: int
    n_queries: int
    p95_ms: float


@dataclasses.dataclass
class ModelStats:
    """Per-tenant latency summary (``model_ids`` labeled traffic)."""
    n_queries: int
    p95_ms: float


@dataclasses.dataclass
class ClusterResult:
    qps: float
    p50_ms: float
    p95_ms: float
    p99_ms: float
    mean_ms: float
    n_queries: int
    dropped: int
    n_nodes: int                      # fleet size at the end of the run
    node_hours: float
    per_pool: dict[str, PoolStats]
    events: list[ScalingEvent] = dataclasses.field(default_factory=list)
    # one row per window, (t_start_s, offered_qps, n_nodes, p95_ms,
    # width_s, ctl_s): the last width is the truncated remainder; ctl_s
    # is the *wall* seconds of control work (lifecycle, routing,
    # submits) before the window was released — the driver stall; empty
    # in the unwindowed events mode
    timeline: list[tuple] = dataclasses.field(default_factory=list)
    # per-model-id latency breakdown when the trace carries tenant labels
    per_model: dict[int, ModelStats] = dataclasses.field(default_factory=dict)
    # live only: apply_fn failures; errored queries also count as dropped
    # (they were not actually served)
    errors: int = 0
    # fleet-fault accounting: queries a killed node surrendered that were
    # re-submitted to survivors (with reroute=False they count as dropped)
    rerouted: int = 0
    # node state transitions (BOOTING/SERVING/DRAINING/DEAD) on the trace
    # timeline, from the lifecycle controller
    lifecycle: list[LifecycleEvent] = dataclasses.field(default_factory=list)
    # per-node apply_fn failure counts ("pool[idx]" → count), first-class
    # regardless of the telemetry switch — `errors` is their sum
    errors_by_node: dict[str, int] = dataclasses.field(default_factory=dict)
    # drive_fleet(telemetry=True): spans + metrics registry + per-window
    # timeline (repro.obs.RunTelemetry); None with the kill switch off
    telemetry: RunTelemetry | None = None
    # fleet-front result cache accounting (drive_fleet(cache=...)); hits
    # complete without touching a node and count toward qps/percentiles
    # under the "cache" pool label
    cache_hits: int = 0
    cache_misses: int = 0
    cache_evictions: int = 0
    # drive_fleet(slo=...): the run's SloEngine, carrying alerts,
    # diagnoses, control actions and stitched incidents (repro.obs.slo);
    # repro.obs.export serializes it and repro.obs.report renders it
    slo: object | None = None

    @property
    def cache_hit_rate(self) -> float:
        n = self.cache_hits + self.cache_misses
        return self.cache_hits / n if n else 0.0

    @property
    def error_rate(self) -> float:
        """Errored fraction of the offered trace (errors also count as
        dropped — an errored query was never actually served)."""
        total = self.n_queries + self.dropped
        return self.errors / total if total else 0.0

    def meets(self, sla_ms: float) -> bool:
        return self.p95_ms <= sla_ms and self.dropped == 0

    def sla_violation_minutes(self, sla_ms: float) -> float:
        """Window-minutes the fleet spent above the SLA — the per-window
        p95 rows of ``timeline`` weighted by each window's width.  The
        resilience benchmark's comparison axis for predictive-vs-reactive
        scaling (a run-wide p95 hides *when* the fleet was late)."""
        return sum(row[4] for row in self.timeline
                   if row[3] > sla_ms) / 60.0

    def driver_stall_s(self) -> list[float]:
        """Per-window wall-clock seconds of driver control work (the
        ``ctl_s`` timeline column) — the chaos benchmark's zero-stall
        gate reads its max/p95 against the window width."""
        return [row[5] for row in self.timeline if len(row) > 5]


def _result(times: np.ndarray, done: np.ndarray, pool_of: np.ndarray,
            pool_counts: dict[str, int], model_ids: np.ndarray | None = None,
            **fields) -> ClusterResult:
    """Fleet, per-pool and per-model latency stats from the per-query
    completions (NaN = dropped); ``fields`` pass through to the result."""
    completed = ~np.isnan(done)

    def p95(sel: np.ndarray) -> float:
        sel = sel & completed
        return float(np.percentile(done[sel] - times[sel], 95) * 1e3) \
            if sel.any() else 0.0

    per_pool = {name: PoolStats(count, int((pool_of == name).sum()),
                                p95(pool_of == name))
                for name, count in pool_counts.items()}
    per_model: dict[int, ModelStats] = {}
    if model_ids is not None and len(times):
        per_model = {int(m): ModelStats(int((model_ids == m).sum()),
                                        p95(model_ids == m))
                     for m in np.unique(model_ids)}
    n_done = int(completed.sum())
    qps = p50 = p95_ms = p99 = mean = 0
    if n_done:
        dur = float(done[completed].max()) - float(times[0])
        qps = n_done / max(dur, 1e-12)
        p50, p95_ms, p99, mean = latency_percentiles_ms(
            done[completed] - times[completed])
    return ClusterResult(qps, p50, p95_ms, p99, mean, n_done,
                         len(times) - n_done, per_pool=per_pool,
                         per_model=per_model, **fields)


def _window_grid(times: np.ndarray, window_s: float | None
                 ) -> tuple[float, float, float, int]:
    """(t_start, horizon, window_s, n_windows) — the window grid starts at
    the first arrival and node-hours are billed over the arrival span
    [times[0], times[-1]], matching the events path and never iterating
    phantom windows for a shifted trace."""
    n = len(times)
    t_start = float(times[0]) if n else 0.0
    horizon = float(times[-1]) if n else 0.0
    span = horizon - t_start
    if window_s is None or window_s >= span:
        # no epsilon: an exact-multiple span must not grow a phantom
        # empty window (the last window is inclusive of t == horizon)
        return t_start, horizon, max(span, 1e-9), 1
    return t_start, horizon, window_s, int(np.ceil(span / window_s))


def _checked_trace(times, sizes, window_s, autoscaler
                   ) -> tuple[np.ndarray, np.ndarray]:
    """The trace as arrays, refused unless sorted; an autoscaler needs
    windows.  Shared by ``drive_fleet`` and ``simulate_fleet``."""
    times = np.asarray(times, float)
    sizes = np.asarray(sizes, np.int64)
    if len(times) and np.any(np.diff(times) < 0):
        raise ValueError("times must be sorted (routers and the per-node "
                         "FCFS advance assume arrival order)")
    if autoscaler is not None and window_s is None:
        raise ValueError("autoscaling requires window_s — scaling happens "
                         "at window boundaries, and a single-window run "
                         "would only observe after all queries completed")
    return times, sizes


def _stages(times, sizes, model_ids, window_s, telemetry, autoscaler, fleet,
            factory, cache, query_keys, tuning, slo, fleet_faults):
    """Validate the options; return one stage per layer, in the loop's
    order (tuning reads the window sketches and the cache books its
    counters before the telemetry or SLO stage snapshots them; the
    autoscaler reads the diagnoses last), and the ``RunTelemetry``."""
    if autoscaler is not None and (fleet is None or factory is None):
        raise ValueError("autoscaling resizes the fleet between "
                         "windows — pass the fleet ledger and a "
                         "backend factory(view, t0)")
    if cache is not None:
        if query_keys is None:
            raise ValueError("cache needs query_keys — per-query "
                             "popularity keys aligned with the trace "
                             "(Traffic.generate_keyed); without them no "
                             "query can ever repeat")
        query_keys = np.asarray(query_keys, np.int64)
        if len(query_keys) != len(times):
            raise ValueError(f"query_keys misaligned with trace: "
                             f"{len(query_keys)} keys for {len(times)} "
                             f"queries")
    if tuning is not None and (not telemetry or window_s is None):
        raise ValueError("offload_tuning is telemetry-driven — the "
                         "controller reads per-window p99-by-component "
                         "from the metrics registry, so it needs "
                         "telemetry=True and window_s")
    if slo is not None and window_s is None:
        raise ValueError("slo evaluation is per-window — burn rates, "
                         "alerting and diagnosis all consume window "
                         "snapshots, so pass window_s")
    if fleet_faults is not None and fleet_faults.kills and window_s is None:
        raise ValueError("fleet_faults kills need window_s — kills are "
                         "detected at window boundaries, and a single-"
                         "window run would only notice after the trace "
                         "ended (every orphan dropped, nothing re-routed)")
    tel = SloStage(slo, times, model_ids) if slo is not None else \
        TelemetryStage(times, model_ids) if telemetry else None
    reg, run_tel = (tel.reg, tel.tel) if tel is not None else (None, None)
    stages: list[FleetStage] = []
    if tuning is not None:
        stages.append(TuningStage(tuning, reg, sizes))
    if cache is not None:
        stages.append(CacheStage(cache, query_keys, reg))
    if tel is not None:
        stages.append(tel)
    if autoscaler is not None:
        stages.append(ScalingStage(autoscaler, run_tel, slo))
    return stages, run_tel


@dataclasses.dataclass
class _WindowEnd:
    """A window at its boundary, as the stages see it."""
    t_s: float
    t_end: float
    width: float
    active: list
    offered: float
    p95: float
    ctl_s: float
    lat_idx: np.ndarray       # queries whose completion the window saw
    lat_s: np.ndarray         # and their latency, in seconds
    state_counts: Callable[[], dict]
    snap: object = None
    diagnoses: list = dataclasses.field(default_factory=list)

    @functools.cached_property
    def booting(self) -> int:
        return self.state_counts().get(NodeState.BOOTING.name, 0)


class _Run:
    """One ``drive_fleet`` run: the trace, the result arrays, and the
    steps the loop takes on the nodes."""

    def __init__(self, times, sizes, model_ids, controller, stages, horizon,
                 grouped: bool, keep_records: bool):
        self.times, self.sizes, self.model_ids = times, sizes, model_ids
        self.controller, self.stages, self.horizon = controller, stages, \
            horizon
        self.grouped, self.keep_records = grouped, keep_records
        self.done = np.full(len(times), np.nan)
        self.pool_of = np.empty(len(times), object)
        self.rerouted = 0
        self.errors_by_node: dict[str, int] = {}
        self.collected: list[Completions] = []
        # grouped-pass parts keyed on the serving list *object*, which the
        # controller keeps while membership is unchanged
        self._grp: tuple = (None, None)

    def void(self, idx) -> None:
        self.done[idx] = np.nan
        self.pool_of[idx] = None

    def shed(self, idx: np.ndarray) -> None:
        for s in self.stages:
            s.shed(idx)

    def completed(self, rec: Completions, nodes) -> None:
        for s in self.stages:
            s.completed(rec, nodes)

    def _grouped_parts(self, active):
        if self._grp[0] is not active:
            ok = len(active) > 1 and grouped_eligible(active)
            self._grp = (active, ([b.engine for b in active],
                                  np.array([b.pool for b in active], object))
                         if ok else None)
        return self._grp[1]

    def submit(self, active, router: Router, idx: np.ndarray, t: np.ndarray,
               allow_grouped: bool = False) -> dict[tuple, np.ndarray]:
        """Route and submit queries ``idx`` arriving at ``t``; returns
        ``{key: lost indices}`` of nodes that died inside submit."""
        sizes = self.sizes[idx]
        mids = self.model_ids[idx] if self.model_ids is not None else None
        assign = router.assign(t, sizes, active, model_ids=mids)
        parts = self._grouped_parts(active) \
            if allow_grouped and self.grouped else None
        if parts is not None:
            ret, order, bounds, xs = submit_grouped(
                active, assign, idx, t, sizes, mids, engines=parts[0],
                keep_records=self.keep_records)
            self.done[idx] = ret
            self.pool_of[idx] = parts[1][assign]
            if self.stages:
                self.completed(Completions(
                    assign, idx, t, t, xs, ret,
                    np.ones(len(idx), bool), t, order, bounds), active)
            return {}
        lost: dict[tuple, np.ndarray] = {}
        for i, b in enumerate(active):
            sel = assign == i
            if not sel.any():
                continue
            g, tg = idx[sel], t[sel]
            try:
                ret = b.submit(g, tg, sizes[sel],
                               mids[sel] if mids is not None else None)
            except BackendDied:
                lost[b.key], ret = g, None
            for s in self.stages:
                s.exchanged(b, g)
            if ret is not None:
                self.done[g] = ret
                self.pool_of[g] = b.pool
                if self.stages:
                    self.completed(Completions(
                        np.zeros(len(g), np.int64), g, tg, tg,
                        b.exec_starts(), ret,
                        np.ones(len(g), bool), self.times[g]), [b])
        return lost

    def reroute(self, active, router: Router, idx: np.ndarray,
                t: np.ndarray) -> dict[tuple, np.ndarray]:
        self.rerouted += len(idx)
        for s in self.stages:
            s.rerouted(idx, t)
        return self.submit(active, router, idx, t)

    def poll(self, t: float) -> tuple[np.ndarray, np.ndarray]:
        """Realtime: advance the nodes to ``t`` and fold what completed
        (in-flight queries report later; a node dying mid-poll is the
        next health pass's).  Returns the queries and latencies (s)."""
        nodes = self.controller.advance_targets()
        for b in nodes:
            b.advance_to(t)
        idx, lat = [np.empty(0, np.int64)], [np.empty(0)]
        for b in nodes:
            try:
                rec = Completions.of_records(b.take_new_records(), self.times)
            except BackendDied:
                continue
            self.completed(rec, [b])
            for s in self.stages:
                s.exchanged(b, None)
            idx.append(rec.idx[rec.ok])
            lat.append((rec.t_done - rec.t_arrival)[rec.ok])
        return np.concatenate(idx), np.concatenate(lat)

    def collect(self, drain_timeout: float) -> int:
        """Realtime: drain, then collect every node's records.  A failed
        drain is recorded, not fatal; an errored query counts dropped.
        Returns the error count."""
        ctl = self.controller
        for b in ctl.advance_targets():
            try:
                b.drain(drain_timeout)
            except (TimeoutError, BackendDied):
                ctl.events.append(LifecycleEvent(
                    self.horizon, b.pool, b.index_in_pool, NodeState.SUSPECT))
        errors = 0
        for b in ctl.all_created():
            rec = Completions.of_records(b.completed_records(), self.times)
            n_err = len(rec.ok) - int(rec.ok.sum())
            if n_err:
                errors += n_err
                name = node_name(b)
                self.errors_by_node[name] = \
                    self.errors_by_node.get(name, 0) + n_err
            self.done[rec.idx[rec.ok]] = rec.t_done[rec.ok]
            self.pool_of[rec.idx[rec.ok]] = b.pool
            self.collected.append(rec)
        return errors


def drive_fleet(times: np.ndarray, sizes: np.ndarray,
                backends: list[NodeBackend] | None, router: Router, *,
                window_s: float | None = None,
                autoscaler: Autoscaler | None = None,
                fleet: Fleet | None = None,
                factory=None,
                model_ids: np.ndarray | None = None,
                fleet_faults: FleetFaults | None = None,
                self_heal: SelfHealPolicy | None = None,
                drain_timeout: float = 120.0,
                telemetry: bool = False,
                grouped: bool | None = None,
                cache: FleetCache | None = None,
                query_keys: np.ndarray | None = None,
                offload_tuning: OffloadTuning | None = None,
                slo=None
                ) -> ClusterResult:
    """Run one trace through a fleet of node backends.  ``times`` must be
    sorted; ``model_ids`` labels each query with its tenant.

    The fleet is ``backends``, an explicit node list (the live tier), or
    a :class:`Fleet` ledger plus ``factory(view, t0) -> NodeBackend``,
    whose nodes are materialized per window so an ``autoscaler`` can
    resize it.  A ``fleet_faults`` kill or an unplanned death surrenders
    a node's unfinished queries, re-routed to the survivors at the
    boundary (or, with ``reroute=False``, dropped); ``self_heal``
    restarts dead nodes.  Autoscaling and kills need ``window_s``.

    Simulated backends complete work at ``submit`` (virtual time);
    realtime ones are paced to each boundary (``advance_to``), polled,
    and collected after a final drain (``drain_timeout``); mixed fleets
    are refused.  An all-sim window of two or more nodes is advanced in
    one grouped pass, except where a kill landed or ``grouped=False``
    (the ``fleet_speed`` benchmark's baseline).

    One stage per opt-in layer: ``telemetry``
    (:class:`~repro.obs.telemetry.TelemetryStage`, attached as
    ``ClusterResult.telemetry``), ``slo`` (a :class:`repro.obs.SloEngine`;
    needs ``window_s``, implies telemetry), ``cache`` + ``query_keys``,
    ``offload_tuning`` (needs ``telemetry=True`` and ``window_s``) and
    ``autoscaler``.  With none, no stage is built.
    """
    times, sizes = _checked_trace(times, sizes, window_s, autoscaler)
    stages, tel = _stages(times, sizes, model_ids, window_s, telemetry,
                          autoscaler, fleet, factory, cache, query_keys,
                          offload_tuning, slo, fleet_faults)
    if fleet is not None and fleet_faults is not None and fleet_faults.kills:
        # kills are written back to pool membership (ledger-owned node
        # identity): run them against a copy so back-to-back runs on the
        # caller's fleet stay fair.  Autoscaler-only mutations keep the
        # long-standing contract — the caller sees the final ledger.
        fleet = fleet.copy()
    controller = FleetController(fleet=fleet, factory=factory,
                                 backends=backends, faults=fleet_faults,
                                 heal=self_heal)
    router.reset()
    t_start, horizon, window_s, n_windows = _window_grid(times, window_s)
    controller.start(t_start)
    # per-node chunk histories, read by kills and chaos (rollback),
    # DRAINING's idle probe and a caller's own completed_records
    keep = (backends is not None or autoscaler is not None
            or self_heal is not None or bool(controller.faults.kills)
            or bool(controller.faults.injections()))
    run = _Run(times, sizes, model_ids, controller, stages, horizon,
               grouped is not False, keep)
    for s in stages:
        s.start(run)
    node_hours = 0.0
    timeline: list[tuple] = []
    for w in range(n_windows):
        w0, w1 = t_start + w * window_s, t_start + (w + 1) * window_s
        idx = np.flatnonzero((times >= w0) & (times < w1 if w < n_windows - 1
                                              else times <= horizon))
        ctl0 = time.perf_counter()
        active, orphans = controller.begin_window(w0)
        for s in stages:
            s.begin_window(active)
        lost: dict[tuple, np.ndarray] = {}
        if orphans:
            # a killed node's unfinished queries: void their completions
            # and re-submit them to the survivors at this boundary
            oidx = np.array([q.index for q in orphans], np.int64)
            run.void(oidx)
            if controller.faults.reroute and active:
                lost = run.reroute(active, router, oidx,
                                   np.full(len(oidx), w0))
            else:
                run.shed(oidx)
        width = min(w1, horizon) - w0     # last window may be truncated
        node_hours += controller.billable_n * width / 3600.0
        midx = idx
        for s in stages:                  # the cache answers hits itself
            ans = s.lookup(midx, times[midx])
            if ans is not None:
                hit, t_hit, lat_ms = ans
                run.done[midx[hit]] = t_hit
                run.pool_of[midx[hit]] = "cache"
                for s2 in stages:
                    s2.answered(midx[hit], t_hit, lat_ms)
                midx = midx[~hit]
        if active:
            # a kill window stays on the per-node path end to end
            lost.update(run.submit(active, router, midx, times[midx],
                                   allow_grouped=not orphans))
        elif len(midx):
            run.shed(midx)                # no SERVING node: dropped
        while lost:
            # mid-submit deaths: retire each victim through the controller
            # (the heal policy decides on a restart), then re-route its
            # failed batch plus the work it had already accepted —
            # repeatedly, in case a survivor dies absorbing the re-route
            dead = set(lost)
            resub = {int(g) for sel in lost.values() for g in sel}
            for key in dead:
                for q in controller.node_died(key, w0):
                    run.void(q.index)
                    resub.add(q.index)
            active = [b for b in active if b.key not in dead]
            if not controller.faults.reroute or not active or not resub:
                break
            ridx = np.array(sorted(resub), np.int64)
            lost = run.reroute(active, router, ridx,
                               np.maximum(times[ridx], w0))
        ctl_s = time.perf_counter() - ctl0
        if controller.realtime:
            lat_idx, lat_s = run.poll(w1)
        else:
            wl = run.done[idx] - times[idx]
            ok = ~np.isnan(wl)
            lat_idx, lat_s = idx[ok], wl[ok]
        p95 = float(np.percentile(lat_s, 95) * 1e3) if len(lat_s) else 0.0
        offered = len(idx) / max(width, 1e-9)
        timeline.append((w0, offered, len(active), p95, width, ctl_s))
        end = _WindowEnd(w0, w1, width, active, offered, p95, ctl_s,
                         lat_idx, lat_s, controller.state_counts)
        for s in stages:
            s.end_window(end)
    # kills that landed after the last window boundary: no windows remain
    # to re-route in, so their orphans can only drop
    for q in controller.finish(horizon):
        run.void(q.index)
    errors = run.collect(drain_timeout) if controller.realtime else 0
    # factory-built backends are owned by the driver (the caller never
    # sees them): a no-op for sim nodes, runtime shutdown for live ones
    controller.close_all()
    for s in stages:
        s.finish(run)
    pool_counts = {p.name: p.count for p in fleet.pools} \
        if fleet is not None else controller.pool_counts()
    cached = {} if cache is None else {
        "cache_hits": cache.hits, "cache_misses": cache.misses,
        "cache_evictions": cache.evictions}
    return _result(times, run.done, run.pool_of, pool_counts, model_ids,
                   n_nodes=controller.n_nodes, node_hours=node_hours,
                   events=list(autoscaler.events) if autoscaler else [],
                   timeline=timeline, errors=errors, rerouted=run.rerouted,
                   lifecycle=list(controller.events),
                   errors_by_node=run.errors_by_node, telemetry=tel,
                   slo=slo, **cached)


def simulate_fleet(times: np.ndarray, sizes: np.ndarray, fleet: Fleet,
                   router: Router, *, window_s: float | None = None,
                   autoscaler: Autoscaler | None = None,
                   faults: FaultConfig | None = None,
                   fleet_faults: FleetFaults | None = None,
                   self_heal: SelfHealPolicy | None = None,
                   contention: ContentionModel | None = None,
                   model_ids: np.ndarray | None = None,
                   seed: int = 0,
                   telemetry: bool = False,
                   grouped: bool | None = None,
                   cache: FleetCache | None = None,
                   query_keys: np.ndarray | None = None,
                   offload_tuning: OffloadTuning | None = None,
                   slo=None
                   ) -> ClusterResult:
    """Run one trace through a simulated fleet.  ``times`` must be sorted.

    By default: ``drive_fleet`` over one ``SimNodeBackend`` per node of a
    copy of ``fleet`` (the other options are ``drive_fleet``'s).  With
    per-node ``faults``/``contention`` every node runs the event-driven
    reference instead: one window, and none of the windowed options.
    """
    times, sizes = _checked_trace(times, sizes, window_s, autoscaler)
    events_mode = not _fast_eligible(contention, faults or FaultConfig())
    if events_mode:
        if autoscaler is not None or window_s is not None:
            raise ValueError("windowing/autoscaling need the fast path; "
                             "faults/contention force the (unwindowed) "
                             "event engine")
        if telemetry:
            raise ValueError("telemetry (spans/registry) needs the "
                             "windowed fast path; per-node faults/"
                             "contention force the unwindowed event "
                             "engine, which has no window loop to stamp "
                             "spans or snapshot metrics from")
        if fleet_faults is not None:
            raise ValueError("fleet_faults (whole-node kills) need the "
                             "windowed fast path; per-node faults/"
                             "contention force the unwindowed event "
                             "engine — use one fault layer per run")
        if cache is not None or offload_tuning is not None \
                or slo is not None:
            raise ValueError("the fleet-front cache, online offload "
                             "tuning and SLO evaluation need the "
                             "windowed fast path; per-node faults/"
                             "contention force the unwindowed event "
                             "engine")
        router.reset()
        n = len(times)
        done = np.full(n, np.nan)
        pool_of = np.empty(n, object)
        nodes = fleet.node_views()
        assign = router.assign(times, sizes, nodes, model_ids=model_ids)
        for i, nv in enumerate(nodes):
            sel = assign == i
            if not sel.any():
                continue
            qs = queries_from_arrays(times[sel], sizes[sel])
            done[sel] = event_done_times(
                qs, nv.spec.cpu, nv.spec.scheduler_config(),
                accel=nv.spec.accel, contention=contention,
                faults=faults or FaultConfig(), seed=seed + i)
            pool_of[sel] = nv.pool
        horizon = float(times[-1]) - float(times[0]) if n else 0.0
        return _result(times, done, pool_of,
                       {p.name: p.count for p in fleet.pools}, model_ids,
                       n_nodes=fleet.n_nodes,
                       node_hours=fleet.n_nodes * horizon / 3600.0)

    # autoscaler resizes mutate the ledger — never the caller's fleet
    # (kill write-back is already copy-guarded inside drive_fleet)
    work_fleet = fleet.copy() if autoscaler is not None else fleet
    return drive_fleet(times, sizes, None, router, window_s=window_s,
                       autoscaler=autoscaler, fleet=work_fleet,
                       factory=SimNodeBackend, model_ids=model_ids,
                       fleet_faults=fleet_faults, self_heal=self_heal,
                       telemetry=telemetry, grouped=grouped,
                       cache=cache, query_keys=query_keys,
                       offload_tuning=offload_tuning, slo=slo)


def cluster_max_qps(fleet: Fleet, router: Router, sla_ms: float, *,
                    size_dist: SizeDist = PRODUCTION, n_queries: int = 1500,
                    seed: int = 0, lo: float = 1.0, hi: float | None = None,
                    iters: int = 9, hint: float | None = None,
                    popularity: PopularityDist | None = None,
                    cache_cfg: CacheConfig | None = None,
                    offload_tuning: OffloadTuning | None = None,
                    window_s: float | None = None,
                    n_windows: int | None = None) -> float:
    """Largest stationary arrival rate whose fleet-wide p95 meets the SLA.

    Same discipline as the per-node ``max_qps_under_sla`` (the shared
    ``warm_bracket``/``bracket_bisect`` helpers): one trace draw per seed,
    rescaled per λ step (``rescale_trace``), sustain guard against backlog
    hiding in a finite trace, exponential bracket then bisection.
    ``hint`` warm-starts the bracket around a known-nearby rate — e.g.
    another policy's answer on the same fleet — instead of doubling up
    from ``lo``.

    ``popularity`` draws the trace with popularity keys (sizes coherent
    per key), so ``cache_cfg`` can put a *fresh* cache in front of each
    candidate rate and ``offload_tuning`` can run (with telemetry).
    ``n_windows`` fixes the window *count* rather than the width, so
    every candidate rate gets the same number of boundaries."""
    rng = np.random.default_rng(seed)
    unit_times, sizes = sample_trace(rng, n_queries, size_dist)
    keys = None
    if popularity is not None:
        keys = popularity.sample(rng, n_queries)
        sizes = keyed_sizes(rng, keys, size_dist)
    if cache_cfg is not None and popularity is None:
        raise ValueError("cache_cfg needs popularity — without keys no "
                         "query can ever repeat, so a cache can never hit")
    _memo: dict[float, bool] = {}

    def ok(qps: float) -> bool:
        hit = _memo.get(qps)
        if hit is not None:
            return hit
        ws_ = window_s
        if ws_ is None and n_windows:
            ws_ = float(unit_times[-1]) / qps / n_windows
        r = simulate_fleet(
            rescale_trace(unit_times, qps), sizes, fleet, router, seed=seed,
            window_s=ws_, telemetry=offload_tuning is not None,
            cache=FleetCache(cache_cfg) if cache_cfg is not None else None,
            query_keys=keys, offload_tuning=offload_tuning)
        v = r.meets(sla_ms) and r.qps >= SUSTAIN_FRACTION * qps
        _memo[qps] = v
        return v

    if not ok(lo):
        return 0.0                # even the floor rate misses the SLA
    # the runaway-doubling cap guards both branches: an explicit hi is a
    # bracket start like a hint (bracket_bisect doubles past a hi that is
    # still feasible), not an unguarded ceiling
    cap = 4e6 * max(fleet.n_nodes, 1)
    if hi is None:
        lo, hi = warm_bracket(ok, lo, hint)
    return bracket_bisect(ok, lo, hi, iters, cap=cap)
