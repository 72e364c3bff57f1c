"""What a serving process records about itself, at a fixed cost per request
and with no garbage-collected object per request.

* :class:`RequestLog` — one row per request a ``ServingRuntime`` splits a
  query into: its ids (``rid``, ``qid``, part, bucket, rows, worker) and
  six ``time.monotonic()`` stamps (enqueue, pick-up, pad end, dispatch
  end, device ready, done).  Each runtime owns one (:func:`request_log`);
  :func:`recent_logs` keeps the last few made, for a reader with no
  handle on the runtime that wrote one.
* :class:`PauseLog` — every garbage collection of the process, from a
  ``gc.callbacks`` hook: its generation and its interval, and per
  generation the count, the total seconds and the longest pause.  The
  collector is the process's, so there is one (:func:`pauses`).
* :func:`span` — a ``jax.profiler.TraceAnnotation`` while a profiler
  session records, else nothing: the request log's boundaries, on the
  device trace's clock.

Both logs are rings of preallocated numpy columns, written in place, so a
long-lived process holds the last ``capacity`` rows at a constant size.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import threading
import time

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["RequestLog", "PauseLog", "request_log", "recent_logs", "pauses",
           "span"]

NAN = float("nan")
_NO_SPAN = contextlib.nullcontext()
_recording = TraceAnnotation.is_enabled


def span(name: str, stats: dict | None = None):
    """A profiler span named ``name`` carrying ``stats``, or a no-op while
    no profiler session records."""
    if _recording():
        return TraceAnnotation(name, **(stats or {}))
    return _NO_SPAN


class RequestLog:
    """A runtime's requests, one row each, in the order they were
    enqueued; the ``rid`` of a row is its position."""

    FIELDS = ("rid", "qid", "part", "bucket", "rows", "worker")
    STAMPS = ("enqueue", "pickup", "pad", "dispatch", "ready", "done")

    def __init__(self, capacity: int = 1 << 16):
        self.capacity = capacity
        self._ids = np.full((capacity, len(self.FIELDS)), -1, np.int64)
        self._t = np.full((capacity, len(self.STAMPS)), np.nan)
        self._lock = threading.Lock()
        self._head = 0                 # rids handed out

    def open(self, qid: int, part: int, rows: int, t_enqueue: float) -> int:
        """A new row for a request being enqueued; returns its rid."""
        with self._lock:
            rid = self._head
            self._head += 1
            s = rid % self.capacity
            self._ids[s] = (rid, qid, part, -1, rows, -1)
            self._t[s] = (t_enqueue, NAN, NAN, NAN, NAN, NAN)
        return rid

    def close(self, rid: int, bucket: int, worker: int, pickup: float,
              pad: float, dispatch: float, ready: float,
              done: float) -> None:
        """The rest of a finished request's row (NaN: a stage it never
        reached, after a failure)."""
        s = rid % self.capacity
        with self._lock:
            if self._ids[s, 0] != rid:
                return                 # the ring has wrapped past it
            self._ids[s, 3] = bucket
            self._ids[s, 5] = worker
            self._t[s, 1:] = (pickup, pad, dispatch, ready, done)

    def rows(self, start: int = 0) -> tuple[dict[str, np.ndarray], int]:
        """Finished rows from position ``start`` on, as columns by field
        and stamp name, and the cursor to pass next.

        Rows come in rid order up to the first request still queued or
        running, so a reader that keeps the cursor reads each row once.  A
        cursor more than ``capacity`` rows behind resumes at the oldest
        row kept."""
        with self._lock:
            head = self._head
            start = max(start, head - self.capacity, 0)
            s = np.arange(start, head) % self.capacity
            ids, t = self._ids[s], self._t[s]
        waiting = np.isnan(t[:, -1])
        cut = int(np.argmax(waiting)) if waiting.any() else len(s)
        out = {f: ids[:cut, i] for i, f in enumerate(self.FIELDS)}
        out.update({f: t[:cut, i] for i, f in enumerate(self.STAMPS)})
        return out, start + cut


class PauseLog:
    """The process's garbage collections: a ``gc.callbacks`` hook."""

    GENERATIONS = 3

    def __init__(self, capacity: int = 1 << 14):
        self.capacity = capacity
        self._gen = np.full(capacity, -1, np.int8)
        self._t = np.full((capacity, 2), np.nan)
        self.count = np.zeros(self.GENERATIONS, np.int64)
        self.total_s = np.zeros(self.GENERATIONS)
        self.max_s = np.zeros(self.GENERATIONS)
        self._n = 0                    # collections seen
        self._t0 = NAN                 # start of the one running

    def __call__(self, phase: str, info: dict) -> None:
        now = time.monotonic()
        if phase == "start":
            self._t0 = now
            return
        g = info["generation"]
        s = self._n % self.capacity
        self._t[s] = (self._t0, now)
        self._gen[s] = g
        self._n += 1
        d = now - self._t0
        self.count[g] += 1
        self.total_s[g] += d
        if d > self.max_s[g]:
            self.max_s[g] = d

    def rows(self, start: int = 0) -> tuple[dict[str, np.ndarray], int]:
        """Collections from number ``start`` on: ``generation``, ``start``
        and ``end`` (monotonic seconds), and the cursor to pass next."""
        n = self._n
        start = max(start, n - self.capacity, 0)
        s = np.arange(start, n) % self.capacity
        return {"generation": self._gen[s].astype(np.int64),
                "start": self._t[s, 0], "end": self._t[s, 1]}, n

    def totals(self) -> dict[str, list]:
        """Per generation: collections, their seconds, the longest."""
        return {"count": self.count.tolist(),
                "total_s": self.total_s.tolist(),
                "max_s": self.max_s.tolist()}


_lock = threading.Lock()
_recent: collections.deque[RequestLog] = collections.deque(maxlen=4)
_pauses: PauseLog | None = None


def recent_logs() -> list[RequestLog]:
    """The request logs of the last few runtimes this process made, oldest
    first: how a reader with no handle on a runtime (one driven through a
    fleet of nodes) finds its rows, also after it has shut down."""
    with _lock:
        return list(_recent)


def request_log() -> RequestLog:
    """A new request log for a runtime, kept among :func:`recent_logs`."""
    log = RequestLog()
    with _lock:
        _recent.append(log)
    return log


def pauses() -> PauseLog:
    """The process's pause log; the first call installs its hook."""
    global _pauses
    with _lock:
        if _pauses is None:
            _pauses = PauseLog()
            gc.callbacks.append(_pauses)
        return _pauses
