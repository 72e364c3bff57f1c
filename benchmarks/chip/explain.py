"""Where a traced window's idle device time went, by the program's own
spans: a diagnostic run of one cell that keeps what a benchmark run
throws away.

    python3 benchmarks/chip/explain.py --workload <cell> --seed <n> \
        --seconds <s> [--trace-seconds <s>] [--out <dir>] [--fixture <name>]

It sets up and serves one window as ``run.py --trace 1`` does, with a
trace of ``--trace-seconds`` in its middle, keeps the trace under
``--out``, and prints one JSON object:

* ``clock``: the offset that puts the request log's monotonic stamps on
  the trace's clock, fitted on ``runtime.dispatch`` spans matched to their
  log rows by ``rid``, and the rows' residuals; the shift that puts the
  device plane on the host's (``program.device_shift``) beside the bounds
  the TPU runtime's own host events set on it: no execution starts before
  its ``DoEnqueueProgram`` ends, none ends after its ``CompleteCallbacks``
  starts (matched by ``run_id``);
* ``idle``: the device's idle seconds, on the host's clock, split by what
  overlapped them, in this order: a request's ``runtime.device_wait`` (the
  host waits on the device), a garbage collection, the program span that
  overlaps it (``runtime.*``, ``feeder.release``), else ``none``;
* ``scopes_ms``: device milliseconds per forward execution by named scope;
* ``releases``: of the queries the feeder released more than 10 ms late,
  how many waited across the profiler's own start or stop call, across a
  garbage collection, or neither;
* ``pauses``: the window's collections by generation;
* ``metrics``: the cell's end-to-end and per-layer metrics, as ``run.py``
  reads them (the end-to-end ones here with the profiler on).

``--fixture <name>`` also writes ``<out>/<name>.xplane.pb`` and
``<name>.json`` (the traced stretch's calls, request log rows, pauses and
the forward's scope tables): what the benchmark's tests pin the readers
on, under ``tests/fixtures``.  It needs a TPU, as ``run.py`` does.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

import numpy as np  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(REPO, "src")]

LATE_S = 0.010
ORDER = ("runtime.device_wait", "gc", "runtime.dispatch", "runtime.pad",
         "runtime.complete", "runtime.dequeue", "feeder.release")


class ProfilerCalls:
    """Host-clock intervals of the profiler's start and stop calls."""

    def __init__(self):
        import jax
        self.intervals: list[tuple[str, float, float]] = []
        for name in ("start_trace", "stop_trace"):
            setattr(jax.profiler, name,
                    self._timed(name, getattr(jax.profiler, name)))

    def _timed(self, name, fn):
        def call(*a, **k):
            t0 = time.monotonic()
            try:
                return fn(*a, **k)
            finally:
                self.intervals.append((name, t0, time.monotonic()))
        return call


def idle_split(gaps, labelled: dict[str, np.ndarray]) -> dict[str, float]:
    """Idle seconds by the first label, in ``ORDER``, whose intervals
    (trace ns) overlap them; the rest is ``none``."""
    import program
    out, so_far, union = {}, 0.0, np.zeros((0, 2))
    for label in ORDER:
        union = np.concatenate([union, labelled.get(label, np.zeros((0, 2)))])
        covered = float(program.covered_ns(gaps, union).sum())
        out[label] = (covered - so_far) * 1e-9
        so_far = covered
    out["none"] = sum(b - a for a, b in gaps) * 1e-9 - so_far * 1e-9
    return out


def late_releases(w, profiler: ProfilerCalls, pauses) -> dict:
    """Queries released more than ``LATE_S`` late, by what their wait
    crossed, with the latest of each."""
    late = [(w.origin + r.t_arrival, w.origin + r.t_released)
            for r in w.records
            if np.isfinite(r.t_released) and r.t_released - r.t_arrival
            > LATE_S]
    calls = [(a, b) for _, a, b in profiler.intervals]
    gcs = list(zip(pauses["start"], pauses["end"])) if pauses else []

    def crosses(a, b, ivs):
        return any(x < b and y > a for x, y in ivs)
    n = {"profiler": 0, "gc": 0, "elsewhere": 0}
    worst = dict.fromkeys(n, 0.0)
    for a, b in late:
        key = "profiler" if crosses(a, b, calls) else \
            "gc" if crosses(a, b, gcs) else "elsewhere"
        n[key] += 1
        worst[key] = max(worst[key], (b - a) * 1e3)
    rel = [r.t_released - r.t_arrival for r in w.records
           if np.isfinite(r.t_released)]
    return {"late": len(late), **n, "latest_ms": worst,
            "p95_ms": float(np.percentile(rel, 95) * 1e3) if rel else None,
            "profiler_calls_ms": [[name, (b - a) * 1e3]
                                  for name, a, b in profiler.intervals]}


def write_fixture(out_dir: str, name: str, w, trace_dir: str, rows: dict,
                  pauses: dict, off: float, tables: dict) -> None:
    """The traced stretch's inputs to the readers, beside its trace."""
    import trace
    lo, hi = ((x - off) * 1e-9 for x in w.profile.window_ns)
    calls = [[c.index, c.rows, c.bucket, c.t0, c.t1] for c in w.calls
             if lo <= c.t0 <= hi]
    keep = (rows["done"] >= lo) & (rows["pickup"] <= hi)
    gc_keep = (pauses["end"] >= lo) & (pauses["start"] <= hi)
    shutil.copy(trace.find_trace(trace_dir),
                os.path.join(out_dir, name + ".xplane.pb"))
    out = {"origin": lo, "seconds": hi - lo, "calls": calls,
           "requests": {k: v[keep].tolist() for k, v in rows.items()},
           "pauses": {k: v[gc_keep].tolist() for k, v in pauses.items()},
           "scope_tables": {str(b): t for b, t in tables.items()}}
    with open(os.path.join(out_dir, name + ".json"), "w") as f:
        json.dump(out, f)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--out", default=None,
                    help="where the trace is kept (default: a new "
                         "temporary directory)")
    ap.add_argument("--fixture", default=None)
    args = ap.parse_args(argv)

    import jax

    import harness
    import program
    import traffic
    if jax.devices()[0].platform != "tpu":
        print("explain: no tpu found; nothing falls back", file=sys.stderr)
        return 1
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = harness.find_cell(bench, args.workload, REPO)
    peaks = harness.load_json(os.path.join(HERE, "peaks.json"))
    harness.use_compile_cache(REPO)
    harness.use_numerics(cell.cfg)
    if args.trace_seconds is not None:
        harness.TRACE_SECONDS = args.trace_seconds
    profiler = ProfilerCalls()
    rate = cell.mix["load_of_knee"] * cell.cfg["knee_qps"]
    sched = traffic.schedule(cell.mix, rate, args.seconds, args.seed)
    s = harness.set_up(cell, args.seed,
                       harness.buckets_of(sched.sizes, cell.cfg["serving"]))
    setup_s = time.monotonic() - T_START
    args.out = args.out or tempfile.mkdtemp(prefix="explain-")
    trace_dir = os.path.join(args.out, f"{args.workload}-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    w = harness.serve_window(s, sched, args.seconds, cell.mix["at_close"],
                             trace_dir=trace_dir)
    run = harness.Run(cell.cfg, cell.mix,
                      peaks[jax.devices()[0].device_kind], setup_s, w, s.pool,
                      cell.model)
    p = w.profile
    rows = program.requests(w)
    pauses = program.pauses(w)
    spans = program.program_spans(trace_dir)
    off, worst, _ = program.dispatch_fit(spans, rows)
    labelled = {name: np.asarray([(x.start_ns, x.end_ns) for x in spans
                                  if x.name == name]).reshape(-1, 2)
                for name in ORDER if name.startswith(("runtime", "feeder"))}
    labelled["runtime.device_wait"] = program.waits(rows, off)
    labelled["gc"] = np.stack([pauses["start"], pauses["end"]], 1) * 1e9 + off
    shift = program.device_shift([(e.start_ns, e.end_ns) for e in p.forward],
                                 labelled["runtime.device_wait"])
    gaps = [(a + shift, b + shift) for a, b, _ in p.gaps]
    per_call = {k: v / len(p.forward) * 1e-6
                for k, v in (program.scope_ns(run) or {}).items()}
    gc_gen = {str(g): {"count": int(np.sum(pauses["generation"] == g)),
                       "max_ms": float(np.max(
                           (pauses["end"] - pauses["start"])[
                               pauses["generation"] == g], initial=0) * 1e3)}
              for g in range(3)}
    out = {
        "workload": args.workload, "seed": args.seed,
        "window_s": p.window_s, "busy_s": p.busy_s,
        "forwards": len(p.forward),
        "clock": {"matched": int(len(worst)),
                  "within_50us": float(np.mean(worst <= 50e3)),
                  "residual_us": {q: float(np.percentile(worst, v) * 1e-3)
                                  for q, v in (("p50", 50), ("p99", 99),
                                               ("max", 100))},
                  "call_fit_minus_dispatch_fit_us":
                      (program.call_offset(w) - off) * 1e-3,
                  "device_shift_us": shift * 1e-3,
                  **{f"{k}_us": v * 1e-3 for k, v in
                     program.runtime_bounds(trace_dir).items()}},
        "idle": idle_split(gaps, labelled),
        "scopes_ms": per_call,
        "releases": late_releases(w, profiler, pauses),
        "pauses": gc_gen,
        "metrics": harness.read_metrics(cell.root, cell.e2e + cell.per_layer,
                                        run),
    }
    idle = sum(b - a for a, b in gaps) * 1e-9
    out["idle_none_share"] = out["idle"]["none"] / idle if idle else None
    if args.fixture:
        tables = program.forward_scope_tables(run, program.traced_buckets(p))
        write_fixture(args.out, args.fixture, w, trace_dir, rows, pauses,
                      off, tables)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
