"""What the serving runtime's request log, spans and pause hook cost per
request with no profiler session recording: host microseconds, on whatever
CPU runs it.

Readings:

* ``recorder_cost.ops`` — the per-request instrumentation alone, as the
  runtime and the feeder run it: a log row opened and closed, six spans
  entered and left, the extra clock reads;
* ``recorder_cost.runtime`` — one worker serving 20,000 one-row requests
  of a no-op model, split and queued first and then drained, per request,
  against the same runtime with the log, the spans and the pause hook
  stubbed out; each arm in a fresh process, so the stubbed one never has
  the hook installed; the difference is the cost in place;
* ``recorder_cost.gc`` — the pause hook's share of that: the collections
  per request the instrumented arm saw, times what the hook adds to one
  collection (generation-0 collections timed with and without it).

    PYTHONPATH=src python -m benchmarks.run --only recorder_cost
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

from benchmarks.common import emit
from repro.serve import recorder, runtime
from repro.serve.recorder import span

N_OPS = 200_000
N_REQUESTS = 20_000
N_COLLECTIONS = 20_000
REPEATS = 3
BUDGET_US = 10.0
ARMS = ("instrumented", "stubbed")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def ops_us(n: int = N_OPS) -> float:
    log = recorder.RequestLog()
    t0 = time.perf_counter()
    for i in range(n):
        rid = log.open(i, 0, 64, time.monotonic())
        ids = {"rid": rid, "qid": i, "bucket": 64, "rows": 64}
        with span("feeder.release", {"qid": i}):
            pass
        with span("runtime.dequeue"):
            pass
        t_pick = time.monotonic()
        with span("runtime.pad", ids):
            pass
        t_pad = time.monotonic()
        with span("runtime.dispatch", ids):
            pass
        t_disp = time.monotonic()
        with span("runtime.device_wait", ids):
            pass
        t_ready = time.monotonic()
        with span("runtime.complete", ids):
            pass
        log.close(rid, 64, 0, t_pick, t_pad, t_disp, t_ready,
                  time.monotonic())
    return (time.perf_counter() - t0) / n * 1e6


class _NoLog:
    def open(self, *a) -> int:
        return 0

    def close(self, *a) -> None:
        pass


def runtime_us(n: int = N_REQUESTS) -> float:
    """Host time per request of one worker serving a no-op model: the
    query is split and queued while the worker is held, then drained."""
    batch = {"x": np.zeros((n, 4), np.float32)}
    go = threading.Event()

    def apply_fn(b):
        go.wait()
        return b["x"]

    rt = runtime.ServingRuntime(apply_fn, n_workers=1, batch_size=1)
    try:
        t0 = time.perf_counter()
        rt.submit(0, batch, n)
        go.set()
        rt.drain(timeout=300)
        return (time.perf_counter() - t0) / n * 1e6
    finally:
        rt.shutdown()


def arm(name: str) -> dict:
    """One arm in this process: the median host µs per request over
    ``REPEATS`` servings, and the collections per request seen meanwhile."""
    if name == "stubbed":
        null = contextlib.nullcontext()
        runtime.span = lambda name, stats=None: null
        recorder.request_log = _NoLog
        recorder.pauses = lambda: None        # the hook is never installed
    seen = sum(s["collections"] for s in gc.get_stats())
    us = float(np.median([runtime_us() for _ in range(REPEATS)]))
    seen = sum(s["collections"] for s in gc.get_stats()) - seen
    return {"us": us,
            "collections_per_request": seen / (REPEATS * N_REQUESTS)}


def hook_us(n: int = N_COLLECTIONS) -> float:
    """Host µs the pause hook adds to one collection: ``n`` generation-0
    collections timed without it and with it, five times each way,
    medians."""
    log = recorder.PauseLog()

    def timed() -> float:
        t0 = time.perf_counter()
        for _ in range(n):
            gc.collect(0)
        return (time.perf_counter() - t0) / n * 1e6

    off, on = [], []
    for _ in range(5):
        off.append(timed())
        gc.callbacks.append(log)
        try:
            on.append(timed())
        finally:
            gc.callbacks.remove(log)
    return float(np.median(on) - np.median(off))


def _in_fresh_process(name: str) -> dict:
    env = dict(os.environ)
    src = os.path.join(REPO, "src")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (src, REPO, env.get("PYTHONPATH")) if p)
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.recorder_cost", "--arm", name],
        cwd=REPO, env=env, capture_output=True, text=True, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    emit("recorder_cost.ops", ops_us(), "us per request, alone")
    on, off = (_in_fresh_process(a) for a in ARMS)
    cost = on["us"] - off["us"]
    emit("recorder_cost.runtime", cost,
         f"{'PASS' if cost <= BUDGET_US else 'FAIL'} budget {BUDGET_US} "
         f"us per request in place ({on['us']:.3f} against "
         f"{off['us']:.3f} with log, spans and pause hook stubbed, fresh "
         f"processes)")
    per_gc = hook_us()
    per_req = on["collections_per_request"]
    emit("recorder_cost.gc", per_req * per_gc,
         f"us per request of the pause hook: {per_req:.5f} collections per "
         f"request ({off['collections_per_request']:.5f} stubbed) x "
         f"{per_gc:.3f} us per collection")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arm", choices=ARMS)
    args = ap.parse_args()
    if args.arm:
        print(json.dumps(arm(args.arm)))
    else:
        main()
