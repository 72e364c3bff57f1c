"""What the program records about itself, read for the benchmark.

* the serving runtime's request log and the process's garbage-collection
  pauses (``repro.serve.recorder``), on the host's monotonic clock;
* the program's own spans in a trace (``runtime.*``, ``feeder.release``);
* the named scopes of the served forward, from the ``op_name`` metadata of
  its compiled HLO: the profiler's device ops carry only each
  instruction's text, so an op is found by its name and result shape;
* one offset per run that puts monotonic stamps on the trace's clock,
  fitted from instants both clocks saw; and one shift that puts the
  device's plane on the host's: a TPU trace records the device about a
  millisecond ahead of the host (an execution "starts" before the call
  that enqueued it has returned), so the planes are aligned on what the
  program knows, that a request's execution runs inside its
  ``runtime.device_wait``.

Against a program that records none of this (an older commit), each
reader here gives ``None``, never an error.
"""
from __future__ import annotations

import os
import re

import numpy as np

import trace

PROGRAM_SPANS = ("runtime.dequeue", "runtime.pad", "runtime.dispatch",
                 "runtime.device_wait", "runtime.complete", "feeder.release")
SCOPES = ("embedding_gather", "bottom_mlp", "interaction", "top_mlp")
OTHER = "other"

_OP_NAME = re.compile(r'op_name="([^"]*)"')


# ------------------------------------------------- the program's own logs


def _recorder():
    try:
        from repro.serve import recorder
    except ImportError:
        return None
    return recorder


def requests(window) -> dict[str, np.ndarray] | None:
    """Rows whose dispatch ended inside the window, as columns
    (``RequestLog.FIELDS`` and ``STAMPS``), from the request log of the
    runtime that served it: of the process's recent logs, the one with the
    most such rows; None where there is none."""
    rec = _recorder()
    if rec is None:
        return None
    best = None
    for log in rec.recent_logs():
        rows, _ = log.rows()
        t = rows["dispatch"] - window.origin
        keep = (t >= 0.0) & (t < window.seconds)
        if best is None or keep.sum() > len(best["rid"]):
            best = {k: v[keep] for k, v in rows.items()}
    return best


def pauses(window) -> dict[str, np.ndarray] | None:
    """Garbage collections that started inside the window: ``generation``,
    ``start`` and ``end`` (monotonic seconds)."""
    rec = _recorder()
    if rec is None:
        return None
    rows, _ = rec.pauses().rows()
    t = rows["start"] - window.origin
    keep = (t >= 0.0) & (t < window.seconds)
    return {k: v[keep] for k, v in rows.items()}


# ------------------------------------------------------------ one clock


def clock_fit(trace_ns, mono_s) -> tuple[float, np.ndarray]:
    """The offset (ns) with ``trace_ns ≈ mono_s * 1e9 + offset`` over
    instants both clocks saw (the median of their differences), and each
    instant's residual (ns)."""
    d = np.asarray(trace_ns, float) - np.asarray(mono_s, float) * 1e9
    if not len(d):
        raise ValueError("no instants to fit the clock on")
    off = float(np.median(d))
    return off, d - off


def call_offset(window) -> float | None:
    """The trace-clock offset of a run's monotonic stamps, fitted on the
    served forward's calls: each traced ``apply_fn`` span against the host
    clock the call took inside it."""
    p = window.profile
    if p is None:
        return None
    calls = {c.index: c for c in window.calls}
    pairs = [(s, calls[int(s.stats["call"])]) for s in p.host
             if s.name == "apply_fn" and int(s.stats["call"]) in calls]
    if not pairs:
        return None
    off, _ = clock_fit([x for s, _ in pairs for x in (s.start_ns, s.end_ns)],
                       [x for _, c in pairs for x in (c.t0, c.t1)])
    return off


def dispatch_fit(spans: list[trace.Span], rows: dict[str, np.ndarray]
                 ) -> tuple[float, np.ndarray, np.ndarray]:
    """The offset fitted on ``runtime.dispatch`` spans matched to their log
    rows by ``rid`` (the span runs from the row's ``pad`` stamp to its
    ``dispatch`` stamp), each matched row's largest residual (ns), and the
    rids matched."""
    at = {int(r): i for i, r in enumerate(rows["rid"])}
    hits = [(s, at[int(s.stats["rid"])]) for s in spans
            if s.name == "runtime.dispatch" and int(s.stats["rid"]) in at]
    if not hits:
        raise ValueError("no runtime.dispatch span matches a log row")
    i = np.asarray([k for _, k in hits])
    start = np.asarray([s.start_ns for s, _ in hits])
    end = np.asarray([s.end_ns for s, _ in hits])
    off, res = clock_fit(np.concatenate([start, end]),
                         np.concatenate([rows["pad"][i], rows["dispatch"][i]]))
    worst = np.maximum(np.abs(res[:len(i)]), np.abs(res[len(i):]))
    return off, worst, rows["rid"][i]


def _below(iv: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Length of the merged, sorted intervals ``iv`` below each of ``x``."""
    lens = iv[:, 1] - iv[:, 0]
    cum = np.concatenate([[0.0], np.cumsum(lens)])
    i = np.searchsorted(iv[:, 0], x, side="right")   # intervals begun by x
    j = np.maximum(i - 1, 0)
    part = np.clip(x - iv[j, 0], 0.0, lens[j])
    return np.where(i > 0, cum[j] + part, 0.0)


def waits(rows, off: float) -> np.ndarray:
    """Each request's ``runtime.device_wait`` (dispatch end to ready) on
    the trace's clock, ns, as an (n, 2) array."""
    ok = np.isfinite(rows["dispatch"]) & np.isfinite(rows["ready"])
    return np.stack([rows["dispatch"][ok], rows["ready"][ok]], 1) * 1e9 + off


def device_shift(executions: np.ndarray, wait_iv: np.ndarray,
                 reach_ns: float = 10e6, step_ns: float = 2e3) -> float:
    """Nanoseconds to add to the device plane's times to put them on the
    host's clock: the least shift, within ``reach_ns`` and to
    ``step_ns``, at which the forward executions' device time lies inside
    the requests' ``runtime.device_wait`` spans as much as at any shift
    (to a thousandth).  An execution starts no earlier than its call
    returns, so the least such shift is low by at most the shortest
    launch delay."""
    iv = trace._union(np.asarray(wait_iv, float).reshape(-1, 2))
    ex = np.asarray(executions, float).reshape(-1, 2)
    if not len(iv) or not len(ex):
        raise ValueError("no executions or no waits to align")
    shifts = np.arange(-reach_ns, reach_ns + step_ns, step_ns)
    cover = np.array([np.sum(_below(iv, ex[:, 1] + d) - _below(iv, ex[:, 0] + d))
                      for d in shifts])
    return float(shifts[np.argmax(cover >= cover.max() * (1 - 1e-3))])


def runtime_bounds(path: str) -> dict[str, float]:
    """Bounds (ns) on the device plane's shift onto the host's clock from
    the TPU runtime's own host events, matched to executions by
    ``run_id`` in the trace at ``path``: no execution starts before its
    ``DoEnqueueProgram`` ends (``enqueue``, a lower bound), none ends after
    its ``CompleteCallbacks`` starts (``callback``, an upper bound)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = trace.find_trace(path)
    execs, enq, done = {}, {}, {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                run_id = dict(e.stats).get("run_id")
                if run_id is None:
                    continue
                if plane.name.startswith(trace.DEVICE_PREFIX) and \
                        line.name == trace.MODULES_LINE:
                    execs[run_id] = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name == "DoEnqueueProgram":
                    enq[run_id] = e.start_ns + e.duration_ns
                elif e.name == "CompleteCallbacks":
                    done[run_id] = e.start_ns
    lo = [enq[r] - s for r, (s, _) in execs.items() if r in enq]
    hi = [done[r] - e for r, (_, e) in execs.items() if r in done]
    return {"enqueue": max(lo) if lo else None,
            "callback": min(hi) if hi else None}


def aligned(run) -> tuple[dict, float, float] | None:
    """A traced run's request log rows, the trace-clock offset of their
    stamps and the shift of the device plane onto the host's clock; None
    without a trace or a request log."""
    w = run.window
    rows = requests(w)
    off = call_offset(w)
    if rows is None or off is None or not w.profile.forward:
        return None
    wait_iv = waits(rows, off)
    if not len(wait_iv):
        return None
    ex = [(e.start_ns, e.end_ns) for e in w.profile.forward]
    return rows, off, device_shift(ex, wait_iv)


def covered_ns(gaps, intervals) -> np.ndarray:
    """For each (start, end) gap, the nanoseconds of it that the union of
    ``intervals`` (an (n, 2) array) covers."""
    iv = trace._union(np.asarray(intervals, float).reshape(-1, 2))
    out = np.zeros(len(gaps))
    if not len(iv):
        return out
    for k, (a, b) in enumerate(gaps):
        lo = int(np.searchsorted(iv[:, 1], a))
        hi = int(np.searchsorted(iv[:, 0], b))
        seg = iv[lo:hi]
        out[k] = np.sum(np.clip(np.minimum(seg[:, 1], b)
                                - np.maximum(seg[:, 0], a), 0.0, None))
    return out


# ------------------------------------------------------ spans in a trace


def program_spans(path: str) -> list[trace.Span]:
    """The program's own spans in the trace at ``path`` (a file or a
    profiler log directory), by start."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = trace.find_trace(path)
    data = ProfileData.from_file(path)
    out = [trace.Span(e.name, e.start_ns, e.start_ns + e.duration_ns,
                      dict(e.stats))
           for plane in data.planes if plane.name == trace.HOST_PLANE
           for line in plane.lines for e in line.events
           if e.name in PROGRAM_SPANS]
    return sorted(out, key=lambda s: s.start_ns)


# ------------------------------------------------ the forward's scopes


def _op_key(text: str) -> str:
    """``%name = shape`` of an instruction's text: what a device op's name
    in the trace and a line of the compiled HLO share."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    return " ".join(text.split(" ", 3)[:3])


def scope_of(op_name: str) -> str:
    """The named scope an HLO ``op_name`` lies in."""
    return next((p for p in op_name.split("/") if p in SCOPES), OTHER)


def scope_table(hlo: str) -> dict[str, str]:
    """``%name = shape`` → scope, for every instruction of compiled HLO
    text whose metadata puts it in a named scope."""
    out = {}
    for line in hlo.splitlines():
        m = _OP_NAME.search(line)
        if m and line.lstrip().startswith(("%", "ROOT %")):
            scope = scope_of(m.group(1))
            if scope != OTHER:
                out[_op_key(line)] = scope
    return out


def op_scopes(op_names, tables) -> dict[str, str]:
    """Each device op's scope, by the scope tables of the programs that
    ran; an op no table names, or that two name differently, is
    ``other``."""
    merged: dict[str, set] = {}
    for t in tables:
        for k, v in t.items():
            merged.setdefault(k, set()).add(v)
    out = {}
    for name in op_names:
        found = merged.get(_op_key(name), ())
        out[name] = next(iter(found)) if len(found) == 1 else OTHER
    return out


def traced_buckets(profile) -> list[int]:
    """The buckets of the calls the trace saw."""
    return sorted({int(s.stats["bucket"]) for s in profile.host
                   if s.name == "apply_fn"})


def forward_lowered(run, buckets) -> dict:
    """The served forward lowered at each bucket for the default device, as
    the program serves it: on the params it serves
    (``serve.models.served_param_shapes``, tables packed) and the run's
    payload shapes."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from repro.serve.models import served_forward, served_param_shapes
    rc = run.model.rec_config(run.cfg)
    dev = jax.devices()[0]
    on = SingleDeviceSharding(dev)
    params = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on),
        served_param_shapes(rc))
    fwd = served_forward(dev.platform)
    out = {}
    for b in buckets:
        batch = {k: jax.ShapeDtypeStruct((b,) + v.shape[1:], v.dtype)
                 for k, v in run.pool.items()}
        out[b] = fwd.lower(params, rc, batch)
    return out


def forward_scope_tables(run, buckets) -> dict[int, dict[str, str]]:
    """The scope table of the served forward at each bucket, from a fresh
    compile.  JAX's persistent compile cache keys a program less its
    debug info, so a forward that differs from a cached one only in its
    named scopes (another commit's) would come back with the cached one's
    metadata, as would an executable the process already holds: the
    process's caches are dropped first, and this compile's key holds the
    metadata."""
    import jax
    jax.clear_caches()
    key = "jax_compilation_cache_include_metadata_in_key"
    was = getattr(jax.config, key)
    jax.config.update(key, True)
    try:
        return {b: scope_table(low.compile().as_text())
                for b, low in forward_lowered(run, buckets).items()}
    finally:
        jax.config.update(key, was)


def scope_ns(run) -> dict[str, float] | None:
    """Device nanoseconds of the traced ops by named scope of the served
    forward; None without a trace or where the program names no scope."""
    p = run.window.profile
    if p is None or not p.op_ns:
        return None
    tables = forward_scope_tables(run, traced_buckets(p))
    scopes = op_scopes(p.op_ns, tables.values())
    out: dict[str, float] = {}
    for name, ns in p.op_ns.items():
        out[scopes[name]] = out.get(scopes[name], 0.0) + ns
    return out if set(out) - {OTHER} else None
