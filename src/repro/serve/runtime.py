"""Live serving runtime: the real-execution counterpart of the simulator.

Queries → split into requests of ≤ batch_size → FIFO queue → workers run
the jitted model (bucketed shapes) → query completes when its last request
lands.  Each worker is two threads: a dispatcher that pads and dispatches
its requests, and a completer that waits for each result in dispatch order
and does the bookkeeping, with up to ``DEPTH`` of its requests dispatched
and not yet complete.  So the host dispatches request k+1 while the device
runs request k; where ``apply_fn`` returns only when its result is ready,
the completer's wait returns at once.  An online DeepRecSched controller
periodically hill-climbs the batch-size knob using the measured p95 over a
sliding window — the "deployed in production" form of the offline tuner
(paper §VI-B).

This runs the actual JAX models on this host; the simulator covers at-scale
what one machine cannot.

Each request is a row of the runtime's request log
(``serve.recorder.RequestLog``: ids and six monotonic stamps, read through
``ServingRuntime.request_log``) and, while a profiler session records, a
set of spans on the device trace's clock, each carrying the request's
``rid``, ``qid``, ``bucket`` and ``rows`` where known:

  ``runtime.dequeue``      a dispatcher blocked on the queue (idle)
  ``runtime.pad``          ``pad_batch`` to the request's bucket (dispatcher)
  ``runtime.dispatch``     the ``apply_fn`` call: enqueue, host-to-device copy
                           (dispatcher)
  ``runtime.device_wait``  ``block_until_ready`` on its result (completer)
  ``runtime.complete``     the bookkeeping under the lock (completer)
  ``feeder.release``       ``PacedFeeder`` releasing one query (``qid``)

The runtime also installs the process's garbage-collection pause counter
(``ServingRuntime.pauses``).
"""
from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Callable

import numpy as np

from repro.core.scheduler import BATCH_LADDER, THRESHOLD_LADDER
from repro.serve import recorder
from repro.serve.batching import bucket_for, pad_batch
from repro.serve.recorder import span

# requests a worker keeps dispatched and not yet complete: the least depth
# that hides one request's wait behind the next one's host work
DEPTH = 2


@dataclasses.dataclass
class _Request:
    rid: int                  # its row of the request log
    qid: int
    batch: dict
    size: int


@dataclasses.dataclass
class _InFlight:
    """A request from its pick-up to its completion, handed from a worker's
    dispatcher to its completer; NaN: a stage not reached."""
    req: _Request
    worker: int
    bucket: int
    ids: dict                 # the request's span stats
    t_pick: float
    t_pad: float = float("nan")
    t_disp: float = float("nan")
    out: object = None
    error: str | None = None


@dataclasses.dataclass
class QueryRecord:
    qid: int
    size: int
    t_arrival: float
    t_done: float = 0.0
    # the query's first pick-up in the request log — the span layer's
    # exec_start stamp; 0.0 until one of its requests has finished
    t_started: float = 0.0
    error: str | None = None   # first apply_fn failure among the requests

    @property
    def latency_ms(self) -> float:
        return (self.t_done - self.t_arrival) * 1e3


class ServingRuntime:
    """n_workers workers over a shared request queue, each a dispatcher
    and a completer thread with up to ``DEPTH`` requests in flight."""

    _JOIN_S = 5.0             # shutdown's wait for the workers' threads

    def __init__(self, apply_fn: Callable[[dict], object], *,
                 n_workers: int = 2, batch_size: int = 64,
                 max_bucket: int = 1024):
        self._apply = apply_fn
        self._q: queue.Queue = queue.Queue()
        self._lock = threading.Lock()
        self._outstanding: dict[int, int] = {}
        self._records: dict[int, QueryRecord] = {}
        self.batch_size = batch_size
        self.max_bucket = max_bucket
        self._n_done = 0
        self._fresh_done: list[QueryRecord] = []
        self._done_log: list[QueryRecord] = []
        self._stop = threading.Event()
        # picked up and not yet complete, by rid: whoever takes a request
        # out (its completer, or shutdown) closes its row
        self._in_flight: dict[int, _InFlight] = {}
        self._log = recorder.request_log()
        recorder.pauses()
        self._threads = []
        for w in range(n_workers):
            slots = threading.BoundedSemaphore(DEPTH)
            handoff: queue.SimpleQueue = queue.SimpleQueue()
            self._threads += [
                threading.Thread(target=self._dispatcher,
                                 args=(w, slots, handoff), daemon=True),
                threading.Thread(target=self._completer,
                                 args=(w, slots, handoff), daemon=True)]
        self._n_workers = n_workers
        for t in self._threads:
            t.start()

    # ---------------------------------------------------------------- api

    def submit(self, qid: int, batch: dict, size: int) -> None:
        """Split one query (leaves have leading dim ``size``) into requests.

        Requests are capped at ``max_bucket`` even when the batch-size knob
        climbs past it — ``bucket_for`` clamps there, and ``pad_batch``
        rejects oversize requests rather than dropping rows."""
        if size <= 0:
            # zero requests would leave a permanent _outstanding entry
            # that no worker ever clears, deadlocking drain()
            raise ValueError(f"query size must be >= 1, got {size}")
        bsz = min(self.batch_size, self.max_bucket)
        n_req = -(-size // bsz)
        now = time.monotonic()
        with self._lock:
            self._records[qid] = QueryRecord(qid, size, now)
            self._outstanding[qid] = n_req
        for i in range(n_req):
            lo, hi = i * bsz, min((i + 1) * bsz, size)
            sub = {k: v[lo:hi] for k, v in batch.items()}
            rid = self._log.open(qid, i, hi - lo, now)
            self._q.put(_Request(rid, qid, sub, hi - lo))

    def drain(self, timeout: float = 60.0) -> None:
        t0 = time.monotonic()
        while time.monotonic() - t0 < timeout:
            with self._lock:
                if not self._outstanding:
                    return
            time.sleep(0.005)
        raise TimeoutError("serving queue did not drain")

    def shutdown(self) -> None:
        """Stop the workers.  Requests in flight are completed if their
        results arrive within ``_JOIN_S``, else their log rows are closed
        with no ``ready`` stamp; requests still queued are abandoned, and
        their rows closed as never picked up."""
        self._stop.set()
        for _ in range(self._n_workers):
            self._q.put(None)
        deadline = time.monotonic() + self._JOIN_S
        for t in self._threads:
            t.join(timeout=max(deadline - time.monotonic(), 0.0))
        nan = float("nan")
        with self._lock:
            stuck, self._in_flight = self._in_flight, {}
        for f in stuck.values():
            self._log.close(f.req.rid, f.bucket, f.worker, f.t_pick,
                            f.t_pad, f.t_disp, nan, time.monotonic())
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                return
            if req is not None:
                self._log.close(req.rid, -1, -1, nan, nan, nan, nan,
                                time.monotonic())

    def completed(self) -> list[QueryRecord]:
        with self._lock:
            return [r for r in self._records.values() if r.t_done > 0]

    def record(self, qid: int) -> QueryRecord:
        with self._lock:
            return self._records[qid]

    @property
    def n_completed(self) -> int:
        """Completed-query count — an O(1) read (plain int, GIL-atomic)."""
        return self._n_done

    @property
    def n_pending(self) -> int:
        """Queries accepted but not yet fully completed — the idleness
        probe terminate-after-idle reads on a draining node."""
        with self._lock:
            return len(self._outstanding)

    def take_completed(self) -> list[QueryRecord]:
        """Atomically drain the completed-since-last-call buffer, in
        completion order.  This is the control loop's feed: per-query
        polls cost O(new completions), not an O(all records) rebuild
        under the lock (which would make a long-lived serving process
        quadratic in its own history)."""
        with self._lock:
            out, self._fresh_done = self._fresh_done, []
            return out

    def completed_log(self, start: int) -> list[QueryRecord]:
        """Completion-ordered records from position ``start`` of the
        append-only completion log — an O(new) read for callers keeping
        their own cursor (``len(previous) + start`` is the next cursor).
        Independent of ``take_completed``'s drain buffer, so a fleet
        driver's window monitor and a node's ``OnlineController`` can
        both consume completions without stealing each other's records.
        """
        with self._lock:
            return self._done_log[start:]

    def request_log(self, start: int = 0
                    ) -> tuple[dict[str, np.ndarray], int]:
        """Finished requests from position ``start`` of the runtime's
        request log, as numpy columns (``RequestLog.FIELDS`` and ``STAMPS``,
        monotonic seconds), and the cursor to pass next — an O(new) read
        like ``completed_log``."""
        return self._log.rows(start)

    @staticmethod
    def pauses() -> dict[str, list]:
        """The process's garbage collections so far, per generation:
        ``count``, ``total_s`` and ``max_s``; each one's interval is in
        ``recorder.pauses().rows()``."""
        return recorder.pauses().totals()

    def percentile_ms(self, p: float) -> float:
        lats = [r.latency_ms for r in self.completed()]
        return float(np.percentile(lats, p)) if lats else 0.0

    # ------------------------------------------------------------- worker

    def _dispatcher(self, w: int, slots: threading.BoundedSemaphore,
                    handoff: queue.SimpleQueue) -> None:
        """Worker ``w``'s first thread: takes a slot, then a request, pads
        and dispatches it, and hands it to the completer.  A failed pad
        or dispatch is handed on with its error, to be completed without
        a wait."""
        try:
            while True:
                slots.acquire()        # released by the completer
                if self._stop.is_set():
                    return
                with span("runtime.dequeue"):
                    req = self._q.get()
                t_pick = time.monotonic()
                if req is None:
                    return
                bucket = bucket_for(req.size, self.max_bucket)
                f = _InFlight(req, w, bucket,
                              {"rid": req.rid, "qid": req.qid,
                               "bucket": bucket, "rows": req.size}, t_pick)
                with self._lock:
                    self._in_flight[req.rid] = f
                try:
                    with span("runtime.pad", f.ids):
                        padded = pad_batch(req.batch, bucket)
                    f.t_pad = time.monotonic()
                    with span("runtime.dispatch", f.ids):
                        f.out = self._apply(padded)
                    f.t_disp = time.monotonic()
                except Exception as e:
                    # an apply_fn failure must not kill the worker or
                    # strand the query's _outstanding entry (which would
                    # deadlock drain()) — complete the query, carry the
                    # error
                    f.error = f"{type(e).__name__}: {e}"
                handoff.put(f)
        finally:
            handoff.put(None)          # the completer ends after the rest

    def _completer(self, w: int, slots: threading.BoundedSemaphore,
                   handoff: queue.SimpleQueue) -> None:
        """Worker ``w``'s second thread: in dispatch order, waits for each
        request's result and completes it, then frees its slot."""
        import jax
        while (f := handoff.get()) is not None:
            t_ready = float("nan")
            if f.error is None:
                try:
                    with span("runtime.device_wait", f.ids):
                        jax.block_until_ready(f.out)
                    t_ready = time.monotonic()
                except Exception as e:
                    f.error = f"{type(e).__name__}: {e}"
            f.out = None
            self._complete(f, t_ready)
            slots.release()

    def _complete(self, f: _InFlight, t_ready: float) -> None:
        with span("runtime.complete", f.ids):
            with self._lock:
                # stamped under the lock: of a query's requests, the one
                # that completes it is the last stamped
                now = time.monotonic()
                if self._in_flight.pop(f.req.rid, None) is None:
                    return             # shutdown has closed its row
                rec = self._records[f.req.qid]
                if f.error is not None and rec.error is None:
                    rec.error = f.error
                if rec.t_started == 0.0 or f.t_pick < rec.t_started:
                    rec.t_started = f.t_pick
                self._outstanding[f.req.qid] -= 1
                if self._outstanding[f.req.qid] == 0:
                    del self._outstanding[f.req.qid]
                    rec.t_done = now
                    self._n_done += 1
                    self._fresh_done.append(rec)
                    self._done_log.append(rec)
            self._log.close(f.req.rid, f.bucket, f.worker, f.t_pick,
                            f.t_pad, f.t_disp, t_ready, now)


class PacedFeeder:
    """Releases queries into a serving runtime at their trace arrival
    instants — the pacing half of a live node, shared by the in-process
    backend (``cluster.live.LiveNodeBackend``) and the remote worker
    (``serve.remote``), so the release/close/drain race handling lives in
    exactly one place.

    ``wall_of(t_trace) -> wall_instant`` maps trace time onto the wall
    clock (evaluated at release time, so a clock anchored after enqueue
    still paces correctly); ``release(qid, size, model_id)`` performs the
    submission; ``on_error`` (optional) observes a failed release — the
    query is dropped and feeding continues either way.  ``stop`` wakes
    the thread even mid-sleep: a close during the trace must not leave a
    thread pacing queries into a shut-down runtime for the rest of the
    trace's wall time; items still scheduled at stop are discarded."""

    def __init__(self, wall_of: Callable[[float], float],
                 release: Callable[[int, int, int], None],
                 on_error: Callable[[int, Exception], None] | None = None):
        self._wall_of = wall_of
        self._release = release
        self._on_error = on_error
        self._q: queue.Queue = queue.Queue()
        self._closing = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def put(self, t_trace: float, qid: int, size: int,
            model_id: int) -> None:
        self._q.put((t_trace, qid, size, model_id))

    @property
    def unfinished(self) -> int:
        """Items accepted but not yet released (or discarded) — the
        bounded-drain loop's wait condition."""
        return self._q.unfinished_tasks

    def stop(self, timeout: float = 5.0) -> None:
        self._closing.set()
        self._q.put(None)
        self._thread.join(timeout=timeout)

    def _run(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                self._q.task_done()
                return
            t, qid, size, mid = item
            try:
                if self._closing.is_set():
                    continue               # discard still-scheduled work
                delay = self._wall_of(t) - time.monotonic()
                if delay > 0 and self._closing.wait(delay):
                    continue               # woken by stop(), not arrival
                with span("feeder.release", {"qid": qid}):
                    self._release(qid, size, mid)
            except Exception as e:         # keep feeding; query → dropped
                if self._on_error is not None:
                    self._on_error(qid, e)
            finally:
                self._q.task_done()


class OnlineController:
    """Online hill climbing on the runtime's batch-size knob.

    Every ``window`` completed queries: if p95 is under the SLA, try the next
    larger batch (more batch-parallel efficiency); if over, step down
    (request parallelism).  The production deployment loop of paper §VI-B.
    """

    def __init__(self, runtime: ServingRuntime, sla_ms: float,
                 ladder=BATCH_LADDER, window: int = 50):
        self.rt = runtime
        self.sla_ms = sla_ms
        self.ladder = list(ladder)
        self.window = window
        self._pending: list[QueryRecord] = []
        self.history: list[tuple[int, float]] = []

    def step(self) -> None:
        # O(new completions) per poll, completion-ordered (take_completed
        # drains the runtime's fresh-done buffer — no full-record rescans,
        # no out-of-order double counting)
        self._pending += self.rt.take_completed()
        if len(self._pending) < self.window:
            return
        recent, self._pending = self._pending, []
        # errored queries complete near-instantly; feeding their fake
        # latencies to the controller would read as headroom and climb the
        # knob on a failing node — an all-errors window reads as a breach
        healthy = [r.latency_ms for r in recent if r.error is None]
        p95 = float(np.percentile(healthy, 95)) if healthy else float("inf")
        i = self._rung()
        if p95 > self.sla_ms and i > 0:
            self.rt.batch_size = self.ladder[i - 1]
        elif p95 < 0.7 * self.sla_ms and i < len(self.ladder) - 1:
            self.rt.batch_size = self.ladder[i + 1]
        self.history.append((self.rt.batch_size, p95))

    def _rung(self) -> int:
        """Ladder index of the current knob, snapping an off-ladder batch
        size (a runtime constructed with one, or an external knob write)
        to the nearest rung instead of raising ``ValueError``."""
        b = self.rt.batch_size
        if b in self.ladder:
            return self.ladder.index(b)
        i = min(range(len(self.ladder)), key=lambda k: abs(self.ladder[k] - b))
        self.rt.batch_size = self.ladder[i]
        return i


class OffloadController:
    """Online hill climbing on DeepRecSched's *second* knob — the
    query-size offload threshold (paper §V, Fig. 10) — fed by
    p99-by-component telemetry instead of a raw latency scalar.

    The boot-time ``tune()`` climb freezes the threshold against an
    offline profile; this controller re-runs the climb online, per node,
    so the knob tracks the traffic the node is actually seeing (the
    Hercules offline-profile + online-adjust split, arxiv 2203.07424).
    One decision per telemetry window:

      * **SLA breach** (e2e p99 > sla): move work toward the less-loaded
        path.  If the CPU-side queueing p99 dominates the accelerator's,
        step the threshold *down* one rung (offload more queries);
        otherwise the accelerator is the bottleneck — step *up* (keep
        more on CPU).
      * **Deep headroom** (e2e p99 < ``relax_frac``·sla): drift one rung
        back toward ``prefer`` — the offline-tuned operating point is
        the best throughput rung, so idle periods undo emergency moves.
      * otherwise hold.

    The controller is engine-agnostic: it owns no runtime, just the knob
    value.  Callers read ``threshold`` after each ``step`` and push it
    into their backend (``NodeBackend.set_offload_threshold`` for the
    fleet engines, ``SchedulerConfig`` rebuild for a bare runtime).
    ``threshold is None`` means "never offload" and snaps to the top
    rung, mirroring ``NodeSpec``'s convention."""

    def __init__(self, sla_ms: float, threshold: int | None = None,
                 ladder=THRESHOLD_LADDER, prefer: int | None = None,
                 relax_frac: float = 0.6):
        self.sla_ms = sla_ms
        self.ladder = list(ladder)
        self.threshold = self._snap(threshold)
        self.prefer = self._snap(prefer if prefer is not None else threshold)
        self.relax_frac = relax_frac
        # (threshold, e2e p99, cpu-queue p99, accel-queue p99) per step
        self.history: list[tuple[int, float, float, float]] = []

    def _snap(self, thr: int | None) -> int:
        if thr is None:
            return self.ladder[-1]
        if thr in self.ladder:
            return thr
        return min(self.ladder, key=lambda r: abs(r - thr))

    def step(self, p99_ms: float, cpu_queue_p99_ms: float,
             acc_queue_p99_ms: float) -> int:
        """One control decision from this window's component percentiles;
        returns the (possibly unchanged) threshold.  NaN inputs — an
        empty window — hold the knob."""
        i = self.ladder.index(self.threshold)
        if not np.isnan(p99_ms):
            if p99_ms > self.sla_ms:
                cpu_q = 0.0 if np.isnan(cpu_queue_p99_ms) else cpu_queue_p99_ms
                acc_q = 0.0 if np.isnan(acc_queue_p99_ms) else acc_queue_p99_ms
                if cpu_q >= acc_q and i > 0:
                    i -= 1                      # offload more
                elif cpu_q < acc_q and i < len(self.ladder) - 1:
                    i += 1                      # accel saturated: keep on CPU
            elif p99_ms < self.relax_frac * self.sla_ms:
                j = self.ladder.index(self.prefer)
                i += (i < j) - (i > j)          # drift one rung toward prefer
        self.threshold = self.ladder[i]
        self.history.append((self.threshold, float(p99_ms),
                             float(cpu_queue_p99_ms),
                             float(acc_queue_p99_ms)))
        return self.threshold
