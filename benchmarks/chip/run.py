"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>
    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --sweep <qps>,<qps>,...

A run serves the cell's configuration through the program's live path on
the chip for exactly ``--seconds``, under the cell's traffic mix at its
fixed rate, then checks every served answer against the plain reference.
Its last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones), ``device`` and, traced,
``breakdown``; the numbers compared with their limits come last, under
``checks``, and as the last lines of standard error.

``--sweep`` serves one window at each of the given rates after one
set-up, with no check, and prints one line per rate: how the knee rate
of a configuration is found.

It needs a TPU with as many chips as the cell asks for; anywhere else it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(REPO, "src")]

PLATFORM = "tpu"


def _fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr)
    return 1


def sweep(cell, rates: list[float], seconds: float, seed: int) -> None:
    """One window per offered rate after one set-up: the knee search."""
    import numpy as np

    import harness
    import traffic
    scheds = [traffic.schedule(cell.mix, r, seconds, seed) for r in rates]
    sizes = np.concatenate([s.sizes for s in scheds])
    s = harness.set_up(cell, seed, harness.buckets_of(sizes,
                                                      cell.cfg["serving"]))
    sla = cell.cfg["sla_ms"]
    for rate, sched in zip(rates, scheds):
        w = harness.serve_window(s, sched, seconds, "wait", drain_s=5.0)
        lat = w.latencies_ms()
        done = w.done()
        half = sched.times < seconds / 2
        row = {"qps": rate, "queries": sched.n, "failed": w.failed(),
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "p95_first_half_ms": float(np.percentile(lat[half], 95)),
               "p95_second_half_ms": float(np.percentile(lat[~half], 95)),
               "open_at_close": int(np.sum(~(done <= seconds))),
               "drain_s": float(np.nanmax(done) - seconds),
               "items_per_s": float(sched.sizes[done <= seconds].sum())
               / seconds,
               "compiles_in_window": w.compiles}
        row["meets_sla"] = bool(row["p95_ms"] <= sla and not row["failed"])
        print(json.dumps(row), flush=True)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated offered rates, queries/s")
    args = ap.parse_args(argv)

    import jax

    import harness
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    devices = jax.devices()
    if devices[0].platform != PLATFORM:
        return _fail(f"no {PLATFORM} found (default device is "
                     f"{devices[0].platform}); nothing falls back")
    cell = harness.find_cell(bench, args.workload, REPO)
    if len(devices) < cell.chips:
        return _fail(f"{len(devices)} chips, {args.workload} needs "
                     f"{cell.chips}")
    peaks = harness.load_json(os.path.join(HERE, "peaks.json"))
    if devices[0].device_kind not in peaks:
        return _fail(f"no peaks for device kind {devices[0].device_kind!r}")
    harness.use_compile_cache(REPO)
    harness.use_numerics(cell.cfg)
    if args.sweep is not None:
        sweep(cell, [float(r) for r in args.sweep.split(",")], args.seconds,
              args.seed)
        return 0
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              traced=bool(args.trace), t_start=T_START,
                              peak=peaks[devices[0].device_kind])
    bad = [k for k, m in result["metrics"].items()
           if not math.isfinite(m["value"])]
    if bad:
        print(f"benchmark: no finite value for {bad}: {result['failed']} of "
              f"{result['attempted']} queries failed", file=sys.stderr)
    print(json.dumps(result["notes"]), file=sys.stderr)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if bad:
        return 1
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
