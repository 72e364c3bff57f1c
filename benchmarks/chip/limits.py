"""Readings that a configuration's correctness limits are set from.

    python3 benchmarks/chip/limits.py --workload <cell> --seconds <s> \
        --seeds <n>,<n>,... --control-seeds <n>,<n>,...

In one process, for each of ``--seeds``: the program served through the
cell's own window (its rate, its mix, ``--seconds`` long) and checked as
a run checks it; then, for each of ``--control-seeds``, the same with the
control in the program's place: the reference computed one step below
the numerics the configuration states (``control_numerics``).  Each
prints one JSON line of the numbers the comparison reads.  The lower reading of a number is the
largest the program gives, the upper the smallest the control gives; a
limit lies between them (see PERF.md).  It needs the chip, as a run does.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(REPO, "src")]

def control_numerics(cfg: dict) -> dict:
    """The step below a float32 configuration's numerics that would tempt
    a later change.  At ``highest`` its contractions are float32: three
    bfloat16 passes (``high``).  At ``default`` a TPU's contractions take
    one bfloat16 pass, so it computes in bfloat16: float8 (e4m3)."""
    if cfg["matmul_precision"] == "highest":
        return {"store": cfg["dtype"], "precision": "high"}
    return {"store": "float8_e4m3fn", "precision": "default"}


def control_model(model, cfg: dict, seed: int):
    """The model's reference in the program's place, one step below the
    configuration's numerics."""
    import jax
    w = model.init_weights(seed, cfg)
    fwd = jax.jit(lambda w, batch: model.forward(w, batch,
                                                 **control_numerics(cfg)))
    return (lambda batch: fwd(w, batch)), w


def reading(cell, seed: int, seconds: float, build=None) -> dict:
    import harness
    import traffic
    rate = cell.mix["load_of_knee"] * cell.cfg["knee_qps"]
    sched = traffic.schedule(cell.mix, rate, seconds, seed)
    s = harness.set_up(cell, seed,
                       harness.buckets_of(sched.sizes, cell.cfg["serving"]),
                       build=build)
    w = harness.serve_window(s, sched, seconds, cell.mix["at_close"])
    return harness.verify(s, w)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)

    import jax

    import harness
    if jax.devices()[0].platform != "tpu":
        print("limits: no tpu found; nothing falls back", file=sys.stderr)
        return 1
    harness.use_compile_cache(REPO)
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    cell = harness.find_cell(bench, args.workload, REPO)
    harness.use_numerics(cell.cfg)
    for side, seeds, build in (("program", args.seeds, None),
                               ("control", args.control_seeds,
                                control_model)):
        for seed in (int(x) for x in seeds.split(",") if x):
            t0 = time.monotonic()
            row = reading(cell, seed, args.seconds, build)
            print(json.dumps({"cell": args.workload, "side": side,
                              "seed": seed, **row,
                              "s": time.monotonic() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
