"""One cell of the chip benchmark: set up, serve the window through the live
path, reduce, check.

The served path is the program's own: ``cluster_sim.drive_fleet`` drives
one ``cluster.live.live_node`` (a ``ServingRuntime`` with one worker),
whose feeder releases each query at its scheduled instant, and the
worker runs ``serve.models.served_forward`` on the chip.  The benchmark
adds only its payload factory (``Payload``) and a thin wrapper round the
served forward (``Served``), which tags each call with the items it
carried, times the host side of the call and never blocks.

Everything that belongs to one configuration, mix or metric is a file
that this module finds by the name ``BENCHMARK.json`` gives it, and
everything it knows of a model is in ``models/<model>.py``, found by the
configuration file's ``model`` key (see ``load_model``).
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import itertools
import json
import os
import shutil
import tempfile
import threading
import time

import numpy as np

import check
import reference
import trace
import traffic

ITEM_KEY = "item"                 # the payload's item tags; never served
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_SECONDS = 3.0


# ------------------------------------------------------------ the files


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A cell of ``BENCHMARK.json`` with its configuration and mix."""
    name: str
    cfg: dict
    mix: dict
    chips: int
    e2e: list[dict]           # metric entries of an untraced run
    per_layer: list[dict]     # metric entries of a traced run
    root: str                 # directory of the benchmark's files
    model: object             # the configuration's ``models/<model>.py``


def find_cell(bench: dict, name: str, repo: str) -> Cell:
    """The cell ``name`` of ``bench``; files are found under ``repo``
    (configurations by their ``file``) and under the benchmark's first
    path (mixes as ``traffic/<mix>.json``, metrics as
    ``metrics/<metric>.py``, models as ``models/<model>.py``)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    root = os.path.join(repo, bench["paths"][0])
    entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = load_json(os.path.join(repo, entry["file"]))
    mix = load_json(os.path.join(root, "traffic", w["traffic"] + ".json"))
    return Cell(name, cfg, mix, w["chips"], cell_metrics(bench, name, False),
                cell_metrics(bench, name, True), root,
                load_model(root, cfg["model"]))


def cell_metrics(bench: dict, cell: str, traced: bool) -> list[dict]:
    """The metric entries a run of ``cell`` reports: end-to-end ones
    untraced, per-layer ones traced.  A metric without a ``workloads``
    list is reported in every cell that reports what it moves."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in moved)]


def _load(root: str, kind: str, name: str):
    path = os.path.join(root, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"{kind}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(root: str, name: str):
    """``metrics/<name>.py``'s ``read(run) -> float | None``."""
    return _load(root, "metrics", name).read


def load_model(root: str, name: str):
    """``models/<name>.py``: all that the harness, the check, the control
    and the metric readers know of a model family.

    * ``rec_config(cfg)``: the program's ``RecConfig`` for a
      configuration file, every field read from the file;
    * ``draw_pool(cfg, rows, key) -> {name: array}``: the payload pool,
      one entry per input of the served forward, rows on the leading
      axis, drawn from the PRNG ``key``;
    * ``init_weights(seed, cfg)`` and ``forward(w, batch, *, store,
      precision) -> (B,)``: the plain float32 reference on a batch of the
      pool's entries (``reference.py`` says what ``store`` and
      ``precision`` mean), which imports nothing of the program;
    * ``flops_per_item(cfg)`` and ``call_bytes(cfg, bucket, inputs)``: what
      one forward call needs (``costs.py``), read only by the readers of
      roofline and peak shares."""
    return _load(root, "models", name)


def use_compile_cache(repo: str) -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program cached,
    however quick its compile."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(repo, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def use_numerics(cfg: dict) -> None:
    """Every contraction the process traces from here on, in any thread,
    at the configuration's matmul precision: the program computes as the
    configuration states."""
    import jax
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


def recsys_model(model, cfg: dict, seed: int):
    """The program's served ``apply_fn`` with its weights made on the
    device from ``seed``."""
    from repro.serve.models import recsys_model as build
    apply_fn, _, params = build(model.rec_config(cfg), seed=seed, max_rows=1)
    return apply_fn, params


# ------------------------------------------------------- the served path


class CompileCounter:
    """Backend compiles (persistent-cache loads included) while open."""

    def __init__(self):
        self.seconds: list[float] = []

    def _on(self, event: str, duration: float, **kwargs) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds.append(duration)

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)


class Payload:
    """``make_batch(size, model_id)``: the next query's rows of the pool.

    The one feeder of a node releases queries in arrival order, so the
    k-th call is query k.  Its rows start at the schedule's offset, and
    each row carries the tag ``k * ITEM_STRIDE + item`` under
    ``ITEM_KEY``, which the runtime slices and pads with the rest of the
    pool's entries."""

    def __init__(self, pool: dict, sched: traffic.Schedule):
        self.pool = pool
        self.sched = sched
        self.k = 0

    def __call__(self, size: int, model_id: int) -> dict:
        import jax
        with jax.profiler.TraceAnnotation("feeder_release"):
            q = self.k
            self.k += 1
            if size != self.sched.sizes[q]:
                raise ValueError(f"query {q} released with {size} items, "
                                 f"scheduled with {self.sched.sizes[q]}")
            o = int(self.sched.offsets[q])
            tags = np.arange(q * traffic.ITEM_STRIDE,
                             q * traffic.ITEM_STRIDE + size, dtype=np.int32)
            return {**{k: v[o:o + size] for k, v in self.pool.items()},
                    ITEM_KEY: tags}


@dataclasses.dataclass
class Call:
    index: int                # order of the call; the trace span's ``call``
    tags: np.ndarray          # item tags of the padded batch
    rows: int                 # real rows
    out: object               # the served output (device array)
    t0: float                 # host monotonic clock around the call
    t1: float

    @property
    def bucket(self) -> int:
        return len(self.tags)


class Served:
    """The served forward as the runtime calls it: strips the tags, calls
    the program, records what the call carried and returned.  It never
    waits for the device."""

    def __init__(self, apply_fn):
        self.apply_fn = apply_fn
        self.calls: list[Call] = []
        self._count = itertools.count()

    def __call__(self, batch: dict):
        import jax
        k = next(self._count)
        tags = batch[ITEM_KEY]
        inputs = {key: v for key, v in batch.items() if key != ITEM_KEY}
        rows = check.real_rows(tags)
        with jax.profiler.TraceAnnotation("apply_fn", call=k,
                                          bucket=len(tags), rows=rows):
            t0 = time.monotonic()
            out = self.apply_fn(inputs)
            t1 = time.monotonic()
        self.calls.append(Call(k, tags, rows, out, t0, t1))
        return out


class Tracer:
    """Profiles ``for_s`` seconds from ``at_s`` after ``start``."""

    def __init__(self, log_dir: str, at_s: float, for_s: float):
        self.log_dir, self.at_s, self.for_s = log_dir, at_s, for_s
        self.span: tuple[float, float] | None = None   # monotonic clock
        self.error: Exception | None = None
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run,
                                        args=(time.monotonic(),),
                                        daemon=True)
        self._thread.start()

    def _run(self, origin: float) -> None:
        import jax
        try:
            if self._stop.wait(max(origin + self.at_s - time.monotonic(),
                                   0.0)):
                return
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            t0 = time.monotonic()
            self._stop.wait(self.for_s)
            t1 = time.monotonic()
            jax.profiler.stop_trace()
            self.span = (t0, t1)
        except Exception as e:          # reported by join()
            self.error = e

    def join(self) -> None:
        self._stop.set()
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("profiler thread did not end")
        if self.error is not None:
            raise RuntimeError("tracing failed") from self.error
        if self.span is None:
            raise RuntimeError("the window closed before the trace began")


# ------------------------------------------------------------ one window


@dataclasses.dataclass
class Setup:
    model: object
    cfg: dict
    seed: int
    apply_fn: object
    params: object
    pool: dict
    curve: object


def request_rows(sizes: np.ndarray, batch_size: int) -> np.ndarray:
    """Rows of every request the runtime splits the queries into."""
    full = sizes // batch_size
    rest = sizes % batch_size
    return np.concatenate([np.full(int(full.sum()), batch_size),
                           rest[rest > 0]]).astype(np.int64)


def buckets_of(sizes: np.ndarray, serving: dict) -> list[int]:
    from repro.serve.batching import bucket_for
    bsz = min(serving["batch_size"], serving["max_bucket"])
    return sorted({bucket_for(int(n), serving["max_bucket"])
                   for n in np.unique(request_rows(sizes, bsz))})


def set_up(cell: Cell, seed: int, buckets: list[int], build=None) -> Setup:
    """Weights on the device, the payload pool, the buckets warmed.

    ``build(model, cfg, seed) -> (apply_fn, params)`` makes what is
    served: the program's model, or another put in its place."""
    import jax
    from repro.cluster import BucketedDeviceModel
    model, cfg = cell.model, cell.cfg
    apply_fn, params = (build or recsys_model)(model, cfg, seed)
    pool = model.draw_pool(cfg, cell.mix["pool_rows"], traffic.pool_key(seed))
    jax.block_until_ready(params)
    secs = []
    for b in buckets:
        batch = {k: v[:b] for k, v in pool.items()}
        jax.block_until_ready(apply_fn(batch))
        t0 = time.monotonic()
        jax.block_until_ready(apply_fn(batch))
        secs.append(time.monotonic() - t0)
    # the routing curve a live node is booted with; one node under round
    # robin never reads it
    curve = BucketedDeviceModel(np.asarray(buckets),
                                np.maximum.accumulate(np.asarray(secs)))
    return Setup(model, cfg, seed, apply_fn, params, pool, curve)


@dataclasses.dataclass
class Window:
    """What one served window left behind."""
    seconds: float
    sched: traffic.Schedule
    at_close: str
    records: list             # CompletedQuery, trace-time stamps
    calls: list[Call]
    origin: float             # monotonic instant of trace time 0
    compiles: int
    feed_errors: list[str]
    profile: trace.TraceSummary | None = None

    def done(self) -> np.ndarray:
        """Completion instant of each query (NaN: not completed, or
        failed), trace time."""
        t = np.full(self.sched.n, np.nan)
        for r in self.records:
            if r.error is None:
                t[r.index] = r.t_done
        if self.at_close == "abandon":
            t[t > self.seconds] = np.nan
        return t

    def failed(self) -> int:
        """Queries that errored, or, where in-flight queries are waited
        for, never completed."""
        errors = sum(r.error is not None for r in self.records)
        if self.at_close == "abandon":
            return errors
        return self.sched.n - len(self.records) + errors

    def latencies_ms(self) -> np.ndarray:
        """Latency of every scheduled query from its scheduled arrival;
        a failed query is infinite, an abandoned one is left out."""
        lat = (self.done() - self.sched.times) * 1e3
        if self.at_close == "abandon":
            keep = ~np.isnan(lat)
            keep[[r.index for r in self.records if r.error is not None]] = True
            lat = lat[keep]
        return np.where(np.isnan(lat), np.inf, lat)

    def calls_in_window(self) -> list[Call]:
        """The calls dispatched inside the window."""
        return [c for c in self.calls
                if 0.0 <= c.t0 - self.origin < self.seconds]


def serve_window(s: Setup, sched: traffic.Schedule, seconds: float,
                 at_close: str, *, trace_dir: str | None = None,
                 drain_s: float = 60.0) -> Window:
    """Serve ``sched`` through one live node and return what it left."""
    from repro.cluster import drive_fleet, live_node, make_router
    serving = s.cfg["serving"]
    served = Served(s.apply_fn)
    node = live_node(served, Payload(s.pool, sched), pool="chip",
                     n_workers=serving["n_workers"],
                     batch_size=serving["batch_size"],
                     max_bucket=serving["max_bucket"], device=s.curve)
    tracer = None
    if trace_dir is not None:
        span = min(TRACE_SECONDS, seconds / 3)
        tracer = Tracer(trace_dir, seconds / 2 - span / 2, span)
    # abandon: the drain ends when the window does, and what is still
    # queued then goes with the node
    drain = drain_s if at_close == "wait" else \
        max(seconds - float(sched.times[-1]), 1e-3)
    try:
        with CompileCounter() as compiles:
            if tracer is not None:
                tracer.start()
            drive_fleet(sched.times, sched.sizes, [node],
                        make_router("round_robin"), drain_timeout=drain)
            node.close()
            if tracer is not None:
                tracer.join()
    finally:
        node.close()
    w = Window(seconds, sched, at_close, node.completed_records(),
               served.calls, node.clock.origin, len(compiles.seconds),
               list(node.feed_errors))
    if tracer is not None:
        w.profile = trace.reduce(trace_dir)
    return w


# ------------------------------------------------------------- the check


def verify(s: Setup, w: Window) -> dict:
    """Every served answer against the reference; frees the program's
    state first.  Returns every number the comparison reads."""
    import jax
    outs = jax.device_get([c.out for c in w.calls])
    tags = [c.tags for c in w.calls]
    for c in w.calls:
        c.out = None
    s.apply_fn = s.params = None
    gc.collect()
    weights = s.model.init_weights(s.seed, s.cfg)
    ref = reference.logits(s.model.forward, weights, s.pool,
                           store=s.cfg["dtype"])
    del weights
    return check.compare(check.served_items(tags, outs), ref, w.sched,
                         w.done(), w.failed())


# --------------------------------------------------------- one whole run


@dataclasses.dataclass
class Run:
    """What a metric reader reads."""
    cfg: dict
    mix: dict
    peak: dict
    setup_s: float
    window: Window
    pool: dict
    model: object = None      # the configuration's ``models/<model>.py``


def read_metrics(root: str, entries: list[dict], run: Run) -> dict:
    """``{name: {"value", "unit"}}`` of every entry whose reader found
    something to read."""
    out = {}
    for m in entries:
        value = load_reader(root, m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def serve_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
               t_start: float, peak: dict) -> tuple[Run, Setup]:
    """Set up and serve one window of ``cell``."""
    rate = cell.mix["load_of_knee"] * cell.cfg["knee_qps"]
    sched = traffic.schedule(cell.mix, rate, seconds, seed)
    s = set_up(cell, seed, buckets_of(sched.sizes, cell.cfg["serving"]))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-") if traced else None
    try:
        setup_s = time.monotonic() - t_start
        w = serve_window(s, sched, seconds, cell.mix["at_close"],
                         trace_dir=trace_dir)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    return Run(cell.cfg, cell.mix, peak, setup_s, w, s.pool, cell.model), s


def run_cell(cell: Cell, *, seed: int, seconds: float, traced: bool,
             t_start: float, peak: dict) -> dict:
    """One run of ``cell`` on the default device, whose peaks are
    ``peak``: the result object the benchmark prints, with the numbers
    compared under ``checks``, its last key, and what an earlier line of
    the output reports under ``notes``.  The platform is the caller's to
    check."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    run, s = serve_cell(cell, seed=seed, seconds=seconds, traced=traced,
                        t_start=t_start, peak=peak)
    w = run.window
    stats = dev.memory_stats() or {}
    metrics = read_metrics(cell.root, cell.per_layer if traced else cell.e2e,
                           run)
    numbers = verify(s, w)
    checks = check.judged(numbers, cell.cfg["correctness"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": w.sched.n, "failed": w.failed(),
           "metrics": metrics, "device": device}
    if w.profile is not None:
        device["busy_s"] = w.profile.busy_s
        device["window_s"] = w.profile.window_s
        out["breakdown"] = {"device_ops": w.profile.top_ops(),
                            "idle_gaps": w.profile.idle_by_label()}
    completed = int((~np.isnan(w.done())).sum())
    out["notes"] = {"setup_s": run.setup_s, "compiles_in_window": w.compiles,
                    "feed_errors": len(w.feed_errors),
                    "completed": completed,
                    "abandoned": w.sched.n - completed - w.failed(),
                    "offered_qps": cell.mix["load_of_knee"]
                    * cell.cfg["knee_qps"],
                    **{k: v for k, v in numbers.items() if k not in checks}}
    out["checks"] = checks
    return out
