"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig11,...] [--json out.json]

Prints ``name,us_per_call,derived`` CSV rows (the harness contract).
``--json`` additionally writes the same rows machine-readably, grouped per
suite with wall-clock and pass/fail status — consumed by the CI bench-smoke
artifact and future BENCH tracking.  Every completed suite also writes its
own report slice to ``$REPRO_ARTIFACTS/BENCH_<suite>.json`` (same shape as
one entry of the ``--json`` ``suites`` map), so CI steps that run a single
suite get a stable per-suite artifact without post-processing.
``--strict`` turns soft checks (rows whose derived column says ``FAIL``)
into a nonzero exit, so CI can gate on thresholds like the sched_speed
≥10× bar instead of only on exceptions.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

SUITES = [
    ("fig5_query_distributions", "benchmarks.query_distributions"),
    ("fig3_operator_breakdown", "benchmarks.operator_breakdown"),
    ("fig4_batch_speedup", "benchmarks.batch_speedup"),
    ("fig9_12_optimal_batch", "benchmarks.optimal_batch"),
    ("fig10_offload_threshold", "benchmarks.offload_threshold"),
    ("fig11_throughput_sla", "benchmarks.throughput_sla"),
    ("fig13_tail_latency", "benchmarks.tail_latency"),
    ("fig14_gpu_fraction", "benchmarks.gpu_fraction"),
    ("cluster_capacity", "benchmarks.cluster_capacity"),
    ("resilience", "benchmarks.resilience"),
    ("sched_speed", "benchmarks.sched_speed"),
    ("live_parity", "benchmarks.live_parity"),
    ("remote_scaling", "benchmarks.remote_scaling"),
    ("chaos", "benchmarks.chaos"),
    ("latency_attribution", "benchmarks.latency_attribution"),
    ("fleet_speed", "benchmarks.fleet_speed"),
    ("cache_offload", "benchmarks.cache_offload"),
    ("slo_diagnosis", "benchmarks.slo_diagnosis"),
    ("roofline_report", "benchmarks.roofline_report"),
    ("recorder_cost", "benchmarks.recorder_cost"),
]


def _write_suite_artifact(name: str, entry: dict) -> None:
    """Standard per-suite artifact: ``$REPRO_ARTIFACTS/BENCH_<name>.json``."""
    import os

    from benchmarks.common import ART
    os.makedirs(ART, exist_ok=True)
    with open(os.path.join(ART, f"BENCH_{name}.json"), "w") as f:
        json.dump({name: entry}, f, indent=1)


def _git_sha() -> str | None:
    import subprocess
    try:
        out = subprocess.run(["git", "rev-parse", "HEAD"],
                             capture_output=True, text=True, timeout=10)
        return out.stdout.strip() or None if out.returncode == 0 else None
    except OSError:
        return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated substring filter on suite names")
    ap.add_argument("--list", action="store_true",
                    help="print the available suite names and exit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write per-suite rows as JSON to PATH")
    ap.add_argument("--strict", action="store_true",
                    help="exit nonzero when any row's derived column "
                         "carries a FAIL soft-check verdict")
    args = ap.parse_args()
    if args.list:
        for name, module in SUITES:
            print(f"{name:28s} {module}")
        return

    import importlib

    from benchmarks.common import rows
    from repro.utils import use_compile_cache
    use_compile_cache()
    failures = []
    report: dict[str, dict] = {}
    selected = [(name, module) for name, module in SUITES
                if not args.only
                or any(tok in name for tok in args.only.split(","))]
    if args.only and not selected:
        # a typo'd --only silently running zero suites would exit 0 and
        # green-light a CI gate that measured nothing
        print(f"# no suite matches --only {args.only!r} "
              f"(see --list)", file=sys.stderr)
        sys.exit(2)
    for name, module in selected:
        print(f"# ==== {name} ====", flush=True)
        t0 = time.time()
        seen = len(rows())
        ok = True
        mod = None
        try:
            mod = importlib.import_module(module)
            mod.main()
            print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append(name)
            ok = False
        report[name] = {
            "ok": ok,
            "seconds": round(time.time() - t0, 3),
            # suites pin their rng seed in a module-level SEED so a JSON
            # artifact identifies the exact run it reports
            "seed": getattr(mod, "SEED", None),
            "rows": [{"name": r[0], "us_per_call": r[1], "derived": r[2]}
                     for r in rows()[seen:]],
        }
        _write_suite_artifact(name, report[name])
    if args.json:
        meta = {"git_sha": _git_sha(),
                "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
                "argv": sys.argv[1:]}
        with open(args.json, "w") as f:
            json.dump({"meta": meta, "suites": report,
                       "failures": failures}, f, indent=1)
        print(f"# wrote {args.json}")
    soft_fails = [r["name"] for s in report.values() for r in s["rows"]
                  if "FAIL" in r["derived"]] if args.strict else []
    if failures:
        print(f"# FAILED suites: {failures}")
    if soft_fails:
        print(f"# FAILED soft checks: {soft_fails}")
    if failures or soft_fails:
        sys.exit(1)


if __name__ == "__main__":
    main()
