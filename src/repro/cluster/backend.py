"""The ``NodeBackend`` interface: one serving-node contract, two engines.

A backend is what the fleet driver (``cluster_sim.drive_fleet``) and the
routers see of a node — the same capabilities regardless of whether the
node is simulated or real:

  * ``submit(idx, times, sizes, model_ids)`` — a sorted window of queries
    routed to this node (``idx`` are global trace indices; ``model_ids``
    carry the per-query tenant label from
    ``MultiTenantTraffic.generate_labeled``);
  * ``advance_to(t)`` — advance the node to timeline time ``t`` (a no-op
    for simulated nodes, whose completion times are computed analytically
    at submit; a wall-clock wait for live nodes);
  * ``completed_records()`` / ``take_new_records()`` — per-query
    completion facts (``CompletedQuery``), in trace-time coordinates for
    both engines;
  * ``weight`` — the capacity weight routers consume (per-node achievable
    QPS, from ``Fleet.tune``/``estimate_capacity`` or live calibration).

Whatever the engine, finished queries reach the driver's stages as one
:class:`Completions` batch.

``SimNodeBackend`` wraps the stateful numpy fast-engine entry points in
``core.simulator`` (``node_pass`` carrying executor/accelerator free times
across traffic windows — exactly the pipeline ``simulate_arrays`` runs).
An all-sim window can skip the per-node loop entirely: ``submit_grouped``
advances every node of a routed window in one ``node_pass_many`` pass
(``grouped_eligible`` gates it), writing the same per-node histories the
per-node path would — the fleet driver's fast path at 1k+ nodes.
``cluster.live.LiveNodeBackend`` wraps a real ``serve.runtime
.ServingRuntime`` executing jitted models on this host.  Routers are
engine-blind: they read only the ``NodeHandle`` surface (identity, spec,
weight), so the same policy object produces the same routing decisions
against either backend kind — the property ``benchmarks/live_parity.py``
exploits to close the sim-vs-real loop.
"""
from __future__ import annotations

import dataclasses
from typing import Protocol, runtime_checkable

import numpy as np

from repro.cluster.fleet import NodeSpec, NodeView
from repro.core.simulator import NodeEngine, node_pass, node_pass_many


@runtime_checkable
class NodeHandle(Protocol):
    """The router-facing surface of a node: stable identity, the spec the
    cost estimators price work with, and a capacity weight.  Satisfied by
    ``fleet.NodeView`` and by every ``NodeBackend``."""
    pool: str
    index_in_pool: int
    spec: NodeSpec
    weight: float


class BackendDied(RuntimeError):
    """The execution engine behind a node is gone mid-run — the process
    crashed, the transport failed past its retry budget, or the runtime
    shut down underneath the driver.  The windowed driver catches this
    (never a bare ``RuntimeError``, which still means a caller bug),
    re-routes the victim's work, and lets the lifecycle controller's
    health pass decide whether to heal the node."""


@dataclasses.dataclass
class PendingQuery:
    """One query a backend accepted but had not completed when it was
    killed (``NodeBackend.cancel_pending``) — everything the fleet
    controller needs to re-route it to a surviving node."""
    index: int                  # global index into the driver's trace
    t_arrival: float
    size: int
    model_id: int = -1


@dataclasses.dataclass
class CompletedQuery:
    """One query's completion facts, in trace-time seconds (live backends
    convert wall clock back to the trace timeline so sim and live results
    are directly comparable)."""
    index: int                  # global index into the driver's trace
    t_arrival: float
    t_done: float               # NaN = dropped / never completed
    model_id: int = -1          # tenant label; -1 = unlabeled traffic
    error: str | None = None    # live only: the apply_fn failure, if any
    # span stamps (trace time; NaN = engine did not stamp): when the
    # query was released into the node's executor queue, and when an
    # executor first picked it up — what the obs layer's queueing/service
    # decomposition is built from
    t_released: float = float("nan")
    t_exec_start: float = float("nan")

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_arrival) * 1e3


@dataclasses.dataclass
class Completions:
    """Finished queries as arrays, the one form every engine reports in.
    ``node`` indexes the batch's node list; ``t_arrival`` is when the
    node got the query (a re-route's instant), ``t_obs`` its original
    arrival; ``t_released`` defaults to ``t_arrival``; ``t_exec_start``
    is NaN where not stamped (None: no stamps at all); ``t_done`` is NaN
    if never completed; ``ok`` is False where the apply_fn failed.  A
    grouped pass's batch keeps its ``order`` and per-node ``bounds``."""
    node: np.ndarray
    idx: np.ndarray
    t_arrival: np.ndarray
    t_released: np.ndarray
    t_exec_start: np.ndarray | None
    t_done: np.ndarray
    ok: np.ndarray
    t_obs: np.ndarray
    order: np.ndarray | None = None
    bounds: np.ndarray | None = None

    @classmethod
    def of_records(cls, recs: list[CompletedQuery],
                   times: np.ndarray) -> "Completions":
        """One node's records; ``times`` is the trace."""
        a = np.array([(r.index, r.t_arrival, r.t_released, r.t_exec_start,
                       r.t_done, r.error is None) for r in recs],
                     float).reshape(-1, 6)
        idx = a[:, 0].astype(np.int64)
        rel = np.where(np.isnan(a[:, 2]), a[:, 1], a[:, 2])
        return cls(np.zeros(len(a), np.int64), idx, a[:, 1], rel, a[:, 3],
                   a[:, 4], a[:, 5] > 0.0, times[idx])


class NodeBackend:
    """Base class for serving-node backends (see module docstring).

    ``realtime`` distinguishes the two timeline semantics: simulated
    backends complete work analytically the moment it is submitted, live
    backends complete work when the wall clock does.  A fleet must be
    homogeneous in this flag — the driver refuses to mix virtual and wall
    time on one timeline.
    """

    realtime = False

    pool: str = "node"
    index_in_pool: int = 0
    spec: NodeSpec
    weight: float = 1.0
    # transport degraded but the node may still be alive (an RPC ran past
    # its deadline): the health pass verifies SUSPECT nodes instead of
    # declaring them dead on one bad exchange
    suspect: bool = False
    # failed RPC attempts so far (remote nodes; see take_retry_s)
    retry_count: int = 0

    @property
    def key(self) -> tuple[str, int]:
        """Stable node identity — what router state and the driver's
        backend pool are keyed by across fleet resizes."""
        return (self.pool, self.index_in_pool)

    def start(self, t0: float) -> None:
        """Anchor the backend's timeline at trace time ``t0`` (live
        backends pin the shared wall clock here; sim backends need
        nothing — their free times were seeded at construction)."""

    def enable_spans(self) -> None:
        """Stamp ``t_released``/``t_exec_start`` from here on (idempotent;
        live/remote nodes always stamp, a sim node only when asked)."""

    def submit(self, idx: np.ndarray, times: np.ndarray, sizes: np.ndarray,
               model_ids: np.ndarray | None = None) -> np.ndarray | None:
        """Accept a sorted window of queries routed to this node.
        Simulated backends return the completion times; realtime ones
        return ``None`` (completions surface through the records)."""
        raise NotImplementedError

    def exec_starts(self) -> np.ndarray | None:
        """Exec-start stamps of the queries of the last ``submit`` that
        returned completion times (None: not stamped)."""
        return None

    def take_retry_s(self) -> float:
        """Wall seconds lost to RPC retries since the last call."""
        return 0.0

    def advance_to(self, t: float) -> None:
        """Advance the node's timeline to trace time ``t``."""

    def drain(self, timeout: float = 120.0) -> None:
        """Block until all submitted work has completed."""

    def completed_records(self) -> list[CompletedQuery]:
        """Everything this node has completed so far."""
        raise NotImplementedError

    def take_new_records(self) -> list[CompletedQuery]:
        """Completions since the last call — the windowed driver's
        monitoring feed.  The base implementation diffs
        ``completed_records`` against a seen-set (correct for any
        backend); ``LiveNodeBackend`` overrides it with an O(new
        completions) cursor into the runtime's append-only completion
        log, so per-window polls don't rescan a long run's full history.
        """
        seen = self._taken = getattr(self, "_taken", set())
        out = []
        for r in self.completed_records():
            if r.index not in seen:
                seen.add(r.index)
                out.append(r)
        return out

    def cancel_pending(self, t: float) -> list[PendingQuery]:
        """Kill the node at trace time ``t``: return every accepted query
        it had not completed (a sim node keeps ``done <= t``, a live one
        what its runtime finished before shutting down) and accept no
        further ``submit``.  A query is in exactly one of
        ``completed_records()`` or the returned list."""
        raise NotImplementedError

    def dead(self) -> bool:
        """Has the engine behind this node gone away unplanned?  Polled by
        the controller's health pass every window (sim nodes never die
        on their own)."""
        return False

    def idle(self, t: float) -> bool:
        """Is every accepted query complete at trace time ``t``?  Drives
        terminate-after-idle for DRAINING nodes.  The base answer is
        ``False`` — a backend that cannot tell must never be terminated
        early (closing it would strand in-flight work)."""
        return False

    def set_offload_threshold(self, threshold: int | None) -> None:
        """Re-knob the node's offload threshold mid-run (the online
        ``OffloadController``'s write side) for windows submitted from
        now on.  The frozen spec is replaced, so router cost caches
        re-price it; for live/remote nodes that is the whole effect."""
        if threshold == self.spec.offload_threshold:
            return
        self.spec = dataclasses.replace(self.spec,
                                        offload_threshold=threshold)

    def close(self) -> None:
        """Release node resources (worker threads, devices)."""


class SimNodeBackend(NodeBackend):
    """A simulated node: the numpy fast engine behind the backend contract.

    Wraps ``core.simulator.node_pass`` statefully — executor and
    accelerator free times persist across ``submit`` calls, so queued work
    from one traffic window delays the next, exactly as the windowed
    driver has always modeled it.  ``t0`` seeds the free times at the
    node's boot instant (autoscaled nodes boot idle at the window start
    they first appear in).
    """

    def __init__(self, view: NodeView, t0: float = 0.0):
        self.pool = view.pool
        self.index_in_pool = view.index_in_pool
        self.spec = view.spec
        self.weight = view.weight
        self.cfg = view.spec.scheduler_config()
        # executor/accelerator free times live in a NodeEngine so the
        # grouped fleet advance (submit_grouped) and the per-node path
        # below share one state representation — a window served by one
        # path leaves exactly the state the other resumes from
        self.engine = NodeEngine.make(self.spec.cpu, self.cfg,
                                      self.spec.accel, t0)
        # (idx, times, done, sizes, model_ids, exec_start-or-None)
        self._chunks: list[tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray, np.ndarray | None,
                                 np.ndarray | None]] = []
        self._killed = False
        self._spans = False
        self._starts: np.ndarray | None = None

    def enable_spans(self) -> None:
        self._spans = True

    def exec_starts(self) -> np.ndarray | None:
        return self._starts

    def submit(self, idx: np.ndarray, times: np.ndarray, sizes: np.ndarray,
               model_ids: np.ndarray | None = None) -> np.ndarray:
        if self._killed:
            raise RuntimeError(f"node {self.key} is dead (cancel_pending "
                               f"was called) — it accepts no new queries")
        eng = self.engine
        if self._spans:
            done, _, _, cpu_free, acc_free, starts = node_pass(
                times, sizes, self.spec.cpu, self.cfg,
                accel=self.spec.accel,
                cpu_free=eng.cpu_state.materialize(),
                acc_free=eng.acc_state.materialize(), want_starts=True)
        else:
            done, _, _, cpu_free, acc_free = node_pass(
                times, sizes, self.spec.cpu, self.cfg, accel=self.spec.accel,
                cpu_free=eng.cpu_state.materialize(),
                acc_free=eng.acc_state.materialize())
            starts = None
        self._starts = starts
        eng.cpu_state.set_free(cpu_free)
        eng.acc_state.set_free(acc_free)
        self.record(np.asarray(idx), np.asarray(times, float), done,
                    np.asarray(sizes, np.int64), model_ids, starts)
        return done

    def record(self, idx, times, done, sizes, model_ids, starts) -> None:
        """Append one served slice to the node's history (``submit`` and
        ``submit_grouped`` both serve through it)."""
        self._chunks.append((idx, times, done, sizes, model_ids, starts))

    def completed_records(self) -> list[CompletedQuery]:
        out = []
        for idx, times, done, _, mids, starts in self._chunks:
            for j in range(len(idx)):
                out.append(CompletedQuery(
                    index=int(idx[j]), t_arrival=float(times[j]),
                    t_done=float(done[j]),
                    model_id=int(mids[j]) if mids is not None else -1,
                    t_released=float(times[j]),
                    t_exec_start=float(starts[j]) if starts is not None
                    else float("nan")))
        return out

    def idle(self, t: float) -> bool:
        """All analytic completions at or before ``t`` (NaN drops never
        complete and never will — they don't hold the node open)."""
        return all(not np.any(c[2] > t) for c in self._chunks)

    def set_offload_threshold(self, threshold: int | None) -> None:
        """Spec swap plus a rebuilt ``SchedulerConfig``: ``NodeEngine
        .set_cfg`` drops the engine's class id and the grouped-pass
        parts built from the old knob."""
        if threshold == self.spec.offload_threshold:
            return
        super().set_offload_threshold(threshold)
        self.cfg = self.spec.scheduler_config()
        self.engine.set_cfg(self.cfg)

    def cancel_pending(self, t: float) -> list[PendingQuery]:
        """A simulated kill at trace time ``t``: the analytically computed
        completion at ``done > t`` never actually happened — strip those
        queries (and NaN drops) from the node's history and hand them
        back for re-routing; completions at ``done <= t`` stand."""
        self._killed = True
        orphans: list[PendingQuery] = []
        kept = []
        for idx, times, done, sizes, mids, starts in self._chunks:
            alive = done <= t            # NaN compares False → orphaned
            for j in np.flatnonzero(~alive):
                orphans.append(PendingQuery(
                    index=int(idx[j]), t_arrival=float(times[j]),
                    size=int(sizes[j]),
                    model_id=int(mids[j]) if mids is not None else -1))
            if alive.any():
                kept.append((idx[alive], times[alive], done[alive],
                             sizes[alive],
                             mids[alive] if mids is not None else None,
                             starts[alive] if starts is not None else None))
        self._chunks = kept
        return orphans


def sim_backends(views: list[NodeView], t0: float = 0.0
                 ) -> list[SimNodeBackend]:
    """One ``SimNodeBackend`` per node of a fleet, booted idle at ``t0``."""
    return [SimNodeBackend(v, t0=t0) for v in views]


# ---------------------------------------------------- grouped fleet path


def grouped_eligible(backends) -> bool:
    """Can this node list be advanced by ``submit_grouped``?  Exactly the
    plain simulated engine — a live/remote node (wall-clock timeline), a
    ``SimNodeBackend`` subclass with its own ``submit``, or an
    already-killed node all defer to the per-node loop."""
    return all(type(b) is SimNodeBackend and not b._killed
               for b in backends)


def submit_grouped(backends: list[SimNodeBackend], assign: np.ndarray,
                   idx: np.ndarray, times: np.ndarray, sizes: np.ndarray,
                   model_ids: np.ndarray | None = None,
                   engines: list | None = None,
                   keep_records: bool = True) -> tuple:
    """One batched numpy advance for a whole routed window of simulated
    nodes, in place of N per-node ``submit`` calls.  ``assign`` maps each
    query to its node in ``backends``; the window is stable-sorted by
    node (FIFO within each node, as per-node ``submit`` sees it),
    advanced in one ``node_pass_many`` pass, and — with
    ``keep_records`` — each node's slice is appended to its history,
    so ``completed_records``/``cancel_pending``/``idle`` behave as if the
    node had served it.  Only a caller that has proven nothing reads
    those histories may drop them (the completion times are unaffected).
    ``engines`` is an optional cached ``[b.engine for b in backends]``.

    Returns ``(done, order, seg_bounds, exec_starts)``: completion times
    in input order, the node-segmented permutation and its per-node end
    offsets (for the caller's per-node folds), and — when spans are
    enabled — each query's first executor dispatch time (else None).
    """
    assign = np.asarray(assign, np.int64)
    order = np.argsort(assign, kind="stable")
    seg_bounds = np.cumsum(np.bincount(assign, minlength=len(backends)))
    p_times = np.asarray(times, float)[order]
    p_sizes = np.asarray(sizes, np.int64)[order]
    p_idx = np.asarray(idx)[order]
    p_mids = model_ids[order] if model_ids is not None else None

    spans = False
    if engines is None:
        engines = []
        for b in backends:
            if b._killed:
                raise RuntimeError(f"node {b.key} is dead (cancel_pending "
                                   f"was called) — it accepts no new "
                                   f"queries")
            engines.append(b.engine)
            spans = spans or b._spans
    else:
        spans = backends[0]._spans if backends else False
    done_p, starts_p = node_pass_many(p_times, p_sizes, seg_bounds, engines,
                                      want_starts=spans)
    done = np.empty(len(p_times))
    done[order] = done_p
    starts = None
    if starts_p is not None:
        starts = np.empty(len(p_times))
        starts[order] = starts_p

    if keep_records:
        seg_starts = np.concatenate(([0], seg_bounds[:-1]))
        for i in np.flatnonzero(seg_bounds - seg_starts).tolist():
            b = backends[i]
            s, e = int(seg_starts[i]), int(seg_bounds[i])
            st = starts_p[s:e] if (starts_p is not None and b._spans) \
                else None
            b.record(p_idx[s:e], p_times[s:e], done_p[s:e], p_sizes[s:e],
                     p_mids[s:e] if p_mids is not None else None, st)
    return done, order, seg_bounds, starts
