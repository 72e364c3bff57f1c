"""Serve DLRM-RMC1 on one TPU chip through the live serving path.

    python chip_smoke.py

A smoke run, not a benchmark.  It builds DLRM-RMC1 at its published widths
with full 1M-row tables (10 x 1M x 32 x f32 = 1.28 GB on the device) from a
fixed seed, boots one live node with ``cluster.live.live_node`` (its
calibration of every bucket up to 256 is the warm-up), and serves a short
Poisson trace of production-size queries through
``cluster_sim.drive_fleet``.  It then checks what came out: every query
completed without error, nothing compiled inside the served window, every
served output is finite, and one request's output matches the same forward
run on the host CPU.

It needs one TPU and has no CPU fallback: on any other platform, or when a
check fails, it exits non-zero and prints no result line.  On success its
last line of output is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.cluster import drive_fleet, live_node, make_router  # noqa: E402
from repro.core.query_gen import PRODUCTION, sample_trace  # noqa: E402
from repro.serve.batching import bucket_for  # noqa: E402
from repro.serve.models import recsys_model, served_forward  # noqa: E402
from repro.utils import use_compile_cache  # noqa: E402

ARCH = "dlrm-rmc1"
SEED = 0
MAX_BUCKET = 256
# the batch-size knob: below the largest production query (1000 items), so
# large queries are split into several requests
BATCH_SIZE = 64
N_QUERIES = 300
# a modest offered rate: 50 queries/s of mean-130-item queries, about 6.5k
# items/s, so the ~6 s window shows the path working, not its capacity
QPS = 50.0
# TPU f32 matmuls run at the default precision, one bfloat16 pass: each
# operand keeps 8 significant bits (relative rounding 2^-9 ~ 2e-3).  The
# forward chains seven such contractions (three bottom-MLP layers, the dot
# interaction, three top-MLP layers), so errors of ~1e-2 of the output's
# scale are expected; 3e-2 leaves room for that and still fails a wrong
# gather or a wrong layer, which move the output by its own scale.
REL_TOL = 3e-2
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


class _CompileCounter:
    """Durations of backend compiles, from a ``jax.monitoring`` listener
    that lives between ``__enter__`` and ``__exit__``.  JAX records the
    event around its persistent-cache lookup too, so an executable loaded
    from the cache counts as a compile."""

    def __init__(self):
        self.seconds: list[float] = []

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds.append(duration)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)


def _requests(sizes: np.ndarray, batch_size: int) -> list[tuple[int, int]]:
    """(first row, rows) of every request, in the order one runtime worker
    serves them: queries in arrival order, each split by
    ``ServingRuntime.submit`` into ``batch_size``-row requests."""
    return [(lo, min(batch_size, int(s) - lo))
            for s in sizes for lo in range(0, int(s), batch_size)]


def run(cfg, *, platform: str = "tpu", n_queries: int = N_QUERIES,
        log=print) -> dict:
    """Serve ``cfg`` through one live node and check the results; raise
    ``SmokeFailure`` on any failed check.  ``platform`` is the one JAX
    platform the run accepts as its default device."""
    dev = jax.devices()[0]
    log(f"platform={dev.platform} device_kind={dev.device_kind} "
        f"device_count={len(jax.devices())}")
    if dev.platform != platform:
        raise SmokeFailure(f"default device is {dev.platform!r}, this run "
                           f"needs {platform!r}")
    log(f"compile_cache_dir={jax.config.jax_compilation_cache_dir}")

    with _CompileCounter() as setup_compiles:
        t0 = time.perf_counter()
        apply_fn, make_batch, params = recsys_model(
            cfg, seed=SEED, max_rows=PRODUCTION.max_size)
        jax.block_until_ready(params)
        t_build = time.perf_counter() - t0

        # every output served inside the window, in serving order
        outputs: list = []
        serving = threading.Event()

        def served(batch: dict):
            out = apply_fn(batch)
            if serving.is_set():
                outputs.append(out)
            return out

        t0 = time.perf_counter()
        node = live_node(served, make_batch, pool="chip",
                         batch_size=BATCH_SIZE, max_bucket=MAX_BUCKET)
        t_warm = time.perf_counter() - t0
    log(f"setup: build_s={t_build:.3f} warmup_s={t_warm:.3f} "
        f"compiles={len(setup_compiles.seconds)} "
        f"compile_s={sum(setup_compiles.seconds):.3f}")

    unit_times, sizes = sample_trace(np.random.default_rng(SEED), n_queries)
    times = unit_times / QPS
    if sizes.max() <= BATCH_SIZE:
        raise SmokeFailure(f"no query exceeds the batch size {BATCH_SIZE}: "
                           f"query splitting would go unexercised")
    try:
        serving.set()
        with _CompileCounter() as window_compiles:
            res = drive_fleet(times, sizes, [node],
                              make_router("round_robin"))
        serving.clear()
        records = node.completed_records()
    finally:
        node.close()
    log(f"served {res.n_queries}/{n_queries} queries at {QPS} qps offered, "
        f"batch_size={BATCH_SIZE} max_bucket={MAX_BUCKET}: "
        f"errors={res.errors} dropped={res.dropped} "
        f"feed_errors={len(node.feed_errors)} "
        f"compiles_in_window={len(window_compiles.seconds)}")
    log(f"smoke latency (not a benchmark): p50_ms={res.p50_ms:.3f} "
        f"p95_ms={res.p95_ms:.3f}")
    stats = dev.memory_stats()
    log(f"peak_bytes_in_use="
        f"{stats.get('peak_bytes_in_use') if stats else 'not reported'}")

    failed = [r for r in records if r.error is not None]
    if len(records) != n_queries or res.n_queries != n_queries or failed:
        raise SmokeFailure(f"{len(records)} of {n_queries} queries "
                           f"completed, {len(failed)} with errors: "
                           f"{failed[0].error if failed else ''}")
    if res.errors or node.feed_errors:
        raise SmokeFailure(f"run errors {res.errors}, feed errors "
                           f"{node.feed_errors[:3]}")
    if window_compiles.seconds:
        raise SmokeFailure(f"{len(window_compiles.seconds)} compiles inside "
                           f"the served window")

    requests = _requests(sizes, BATCH_SIZE)
    if len(outputs) != len(requests):
        raise SmokeFailure(f"{len(outputs)} requests served, "
                           f"{len(requests)} expected")
    for (lo, n), out in zip(requests, outputs):
        if out.shape[0] != bucket_for(n, MAX_BUCKET):
            raise SmokeFailure(f"a {n}-row request ran at bucket "
                               f"{out.shape[0]}")
        if not np.isfinite(np.asarray(out)).all():
            raise SmokeFailure(f"non-finite output for a {n}-row request")

    # one padded request (rows not a power of two), sliced to its true rows,
    # against the same forward on the host CPU at full f32 precision
    k = next(i for i, (_, n) in enumerate(requests) if n & (n - 1))
    lo, n = requests[k]
    got = np.asarray(outputs[k])[:n]
    cpu = jax.devices("cpu")[0]
    rows = {key: v[lo:lo + n] for key, v in make_batch(lo + n, -1).items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(served_forward("cpu")(
            jax.device_put(params, cpu), cfg, jax.device_put(rows, cpu)))
    if not np.isfinite(want).all():
        raise SmokeFailure("non-finite CPU reference output")
    scale = float(np.abs(want).max())
    err = float(np.abs(got - want).max()) / max(scale, 1e-30)
    log(f"reference check: request {k} ({n} rows at bucket "
        f"{bucket_for(n, MAX_BUCKET)}) vs host CPU at highest precision: "
        f"max_abs_err/scale={err:.3e} (scale {scale:.4g}, "
        f"tolerance {REL_TOL})")
    if err > REL_TOL:
        raise SmokeFailure(f"served output differs from the CPU reference "
                           f"by {err:.3e} of its scale (> {REL_TOL})")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def main() -> int:
    # the reference check runs the forward on the host CPU backend too
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    use_compile_cache()
    try:
        device = run(configs.get(ARCH).config)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
