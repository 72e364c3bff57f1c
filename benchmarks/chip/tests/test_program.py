"""The program's own records as the benchmark reads them, on the CPU: the
request log's rows against the spans of a profiled run, the forward's
named scopes, and the per-layer metrics that read them, pinned on a
recorded chip trace with the program's spans."""
import json
import os
import subprocess
import sys
import textwrap
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(REPO, "src")]

import harness  # noqa: E402
import program  # noqa: E402
import trace  # noqa: E402
import traffic  # noqa: E402

FIXTURES = os.path.join(BENCH, "tests", "fixtures")
SMOKE = os.path.join(FIXTURES, "dlrm-smoke.json")
RMC1 = os.path.join(BENCH, "configs", "dlrm-rmc1.json")
DLRM = harness.load_model(BENCH, "dlrm")


def read(name, run):
    return harness.load_reader(BENCH, name)(run)


# ------------------------------------------------- a profiled CPU run


N_QUERIES = 100


@pytest.fixture(scope="module")
def profiled(tmp_path_factory):
    """A feeder and a runtime serving 100 queries (239 requests) of a tiny
    jitted model under the profiler, every bucket compiled first: the
    trace, the log rows and the queries."""
    import jax
    import jax.numpy as jnp
    from repro.serve.runtime import PacedFeeder, ServingRuntime
    w = jnp.ones((4, 1))
    rt = ServingRuntime(jax.jit(lambda b: b["x"] @ w), n_workers=2,
                        batch_size=8)
    for q, n in enumerate((1, 2, 3, 5)):     # compile buckets 1, 2, 4, 8
        rt.submit(-1 - q, {"x": np.ones((n, 4), np.float32)}, n)
    rt.drain()
    _, cursor = rt.request_log()
    sizes = [1 + (7 * q) % 30 for q in range(1, N_QUERIES + 1)]
    feeder = PacedFeeder(
        lambda t: t, lambda q, n, m: rt.submit(
            q, {"x": np.ones((n, 4), np.float32)}, n))
    log_dir = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(log_dir)
    try:
        for q, n in enumerate(sizes, start=1):
            feeder.put(0.0, q, n, 0)
        while feeder.unfinished:           # poll without holding the GIL
            time.sleep(0.001)
        rt.drain()
    finally:
        jax.profiler.stop_trace()
        feeder.stop()
        rt.shutdown()
    rows, _ = rt.request_log(cursor)
    return program.program_spans(log_dir), rows, sizes


def test_a_profiled_run_holds_each_span_with_its_request(profiled):
    spans, rows, sizes = profiled
    assert len(rows["rid"]) == sum(-(-n // 8) for n in sizes)
    for name in ("runtime.pad", "runtime.dispatch", "runtime.device_wait",
                 "runtime.complete"):
        got = [s for s in spans if s.name == name]
        assert sorted(int(s.stats["rid"]) for s in got) == \
            sorted(rows["rid"].tolist()), name
        at = {int(r): i for i, r in enumerate(rows["rid"])}
        for s in got:
            i = at[int(s.stats["rid"])]
            assert int(s.stats["qid"]) == rows["qid"][i]
            assert int(s.stats["bucket"]) == rows["bucket"][i]
            assert int(s.stats["rows"]) == rows["rows"][i]
    # each worker's first wait began before the profiler did
    dequeues = sum(s.name == "runtime.dequeue" for s in spans)
    assert dequeues >= len(rows["rid"]) - 2
    released = sorted(int(s.stats["qid"]) for s in spans
                      if s.name == "feeder.release")
    assert released == list(range(1, N_QUERIES + 1))


def test_the_fitted_offset_puts_log_rows_on_their_spans(profiled):
    spans, rows, _ = profiled
    off, worst, rids = program.dispatch_fit(spans, rows)
    assert len(rids) == len(rows["rid"])
    assert np.mean(worst <= 50e3) >= 0.99
    # every row's ready stamp lies between the end of its device wait and
    # the start of its bookkeeping, the two spans it separates (a thread
    # descheduled between them widens the gap; the stamp stays inside)
    edge = {(s.name, int(s.stats["rid"])): s for s in spans
            if s.name in ("runtime.device_wait", "runtime.complete")}
    for rid, ready in zip(rows["rid"], rows["ready"] * 1e9 + off):
        wait = edge["runtime.device_wait", int(rid)]
        done = edge["runtime.complete", int(rid)]
        assert wait.end_ns - 50e3 < ready < done.start_ns + 50e3


def test_clock_fit_and_coverage_arithmetic():
    off, res = program.clock_fit([1000.0, 2010.0, 2990.0], [0.0, 1e-6, 2e-6])
    assert off == pytest.approx(1000.0) and res.tolist() == [0.0, 10.0, -10.0]
    gaps = [(0.0, 10.0), (20.0, 30.0), (40.0, 41.0)]
    iv = np.array([[5.0, 25.0], [8.0, 12.0], [29.0, 35.0]])
    assert program.covered_ns(gaps, iv).tolist() == [5.0, 6.0, 0.0]
    assert program.covered_ns(gaps, np.zeros((0, 2))).tolist() == [0, 0, 0]


# ------------------------------------------------- the forward's scopes


def smoke_run():
    """A run of the smoke configuration with a 64-row pool, for lowering."""
    cfg = harness.load_json(SMOKE)
    pool = DLRM.draw_pool(cfg, 64, traffic.pool_key(3))
    return harness.Run(cfg, {}, {}, 0.0, None, pool, DLRM)


def test_scope_tables_name_the_four_scopes_of_the_served_forward():
    import jax
    from repro.serve.models import init_params, served_forward
    run = smoke_run()
    cfg, pool = run.cfg, run.pool
    tables = program.forward_scope_tables(run, [4, 64])
    for t in tables.values():
        assert set(t.values()) == set(program.SCOPES)
    # the table of the program as served: compiled from the arrays a
    # call passes, tables packed, its instructions are the same
    rc = DLRM.rec_config(cfg)
    params = init_params(jax.random.PRNGKey(3), rc)
    batch = {k: np.asarray(v[:4]) for k, v in pool.items()}
    served = served_forward(jax.devices()[0].platform).lower(
        params, rc, batch).compile().as_text()
    assert program.scope_table(served) == tables[4]


def test_the_scope_table_maps_the_packed_tables_gather_to_its_scope():
    """The smoke configuration's tables (4 x 1000 rows of 8 lanes) are
    served packed 16 rows to a 128-lane row: the lowering the scope tables
    come from takes them so, and every op of its entry computation that
    reads them, and the gather inside, lies in ``embedding_gather``."""
    import re
    hlo = program.forward_lowered(smoke_run(), [64])[64].compile().as_text()
    table = program.scope_table(hlo)
    entry = hlo[hlo.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")].splitlines()[1:]
    param = next(line for line in entry
                 if "parameter(" in line and "f32[4,63,128]" in line)
    name = re.escape(param.split("=")[0].strip())
    users = [line for line in entry if line is not param
             and re.search(r"\(.*" + name + r"[,)]", line)]
    assert users
    for line in users:
        assert table[program._op_key(line)] == "embedding_gather", line
    gathers = [line for line in hlo.splitlines()
               if " gather(" in line and "slice_sizes={1,128}" in line]
    assert gathers
    for line in gathers:
        assert table[program._op_key(line)] == "embedding_gather", line


# Run in a process of its own: the persistent cache is set up once per
# process.  The unscoped forward (another commit's) is compiled into the
# cache first; a plain compile of the scoped forward then returns the cached
# program's metadata, and the scope tables must not.
_PAST_CACHE = """
import contextlib, sys
import jax
sys.path[:0] = [{bench!r}, {src!r}]
import harness, program, traffic
DLRM = harness.load_model({bench!r}, "dlrm")
jax.config.update("jax_compilation_cache_dir", {cache!r})
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
cfg = harness.load_json({smoke!r})
pool = DLRM.draw_pool(cfg, 64, traffic.pool_key(3))
run = harness.Run(cfg, {{}}, {{}}, 0.0, None, pool, DLRM)
lower = lambda: program.forward_lowered(run, [4])[4]
scoped = jax.named_scope
jax.named_scope = lambda name: contextlib.nullcontext()
assert program.scope_table(lower().compile().as_text()) == {{}}
jax.named_scope = scoped
jax.clear_caches()
print("plain", sorted(set(program.scope_table(
    lower().compile().as_text()).values())))
print("tables", sorted(set(program.forward_scope_tables(run, [4])[4].values())))
"""


def test_scope_tables_are_not_taken_from_a_cache_of_an_unscoped_forward(
        tmp_path):
    code = _PAST_CACHE.format(bench=BENCH, src=os.path.join(REPO, "src"),
                              cache=str(tmp_path / "cache"), smoke=SMOKE)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = dict(line.split(" ", 1) for line in out.stdout.splitlines())
    assert lines["plain"] == "[]"        # the cache served the unscoped one
    assert lines["tables"] == str(sorted(program.SCOPES))


def test_op_scopes_match_trace_ops_by_name_and_shape():
    hlo = "\n".join([
        '  %fusion.1 = f32[512,32]{0,1} fusion(%a, %b), kind=kLoop, '
        'metadata={op_name="jit(forward)/embedding_gather/vmap(jit(_take))'
        '/gather"}',
        '  ROOT %fusion.9 = f32[8]{0} fusion(%c), metadata={op_name='
        '"jit(forward)/top_mlp/add"}',
        '  %copy.3 = f32[8]{0} copy(%d)',
        '  %fusion.2 = f32[8,4]{1,0} fusion(%e), metadata={op_name='
        '"jit(forward)/vmap(jit(_take))/gather"}'])
    table = program.scope_table(hlo)
    assert table == {"%fusion.1 = f32[512,32]{0,1}": "embedding_gather",
                     "%fusion.9 = f32[8]{0}": "top_mlp"}
    other = {"%fusion.1 = f32[64,32]{0,1}": "interaction"}
    ops = ["%fusion.1 = f32[512,32]{0,1} fusion(f32[10,100,32]{2,1,0} %a, "
           "s32[512]{0} %b), kind=kLoop", "%fusion.9 = f32[8]{0} fusion()",
           "%copy.3 = f32[8]{0} copy(f32[8]{0} %d)"]
    assert program.op_scopes(ops, [table, other]) == {
        ops[0]: "embedding_gather", ops[1]: "top_mlp", ops[2]: "other"}
    # two programs naming one op differently: no scope is claimed
    clash = {"%fusion.9 = f32[8]{0}": "interaction"}
    assert program.op_scopes(ops[1:2], [table, clash]) == {ops[1]: "other"}


# ---------------------------------- readers on recorded chip traces


def _window(profile, origin=0.0, seconds=1e9, calls=()):
    sched = traffic.Schedule(np.zeros(1), np.ones(1, np.int64),
                             np.zeros(1, int))
    w = harness.Window(seconds, sched, "abandon", [], list(calls), origin,
                       0, [])
    w.profile = profile
    return w


@pytest.fixture(scope="module")
def steady():
    """The recorded steady-cell trace of the first chip benchmark."""
    return trace.reduce(os.path.join(FIXTURES, "rmc1-steady.xplane.pb"))


def test_existing_device_metrics_reduce_as_before_on_the_steady_trace(
        steady):
    cfg = harness.load_json(RMC1)
    peak = harness.load_json(os.path.join(BENCH, "peaks.json"))[
        "TPU v5 lite"]
    run = harness.Run(cfg, {}, peak, 0.0, _window(steady), {}, DLRM)
    assert read("device_idle_share", run) == pytest.approx(
        40.523449189393, rel=1e-9)
    assert read("forward_device_ms.mean", run) == pytest.approx(
        1.8834660365853657, rel=1e-9)
    assert read("step_mfu", run) == pytest.approx(0.002865017298012964,
                                                  rel=1e-9)
    assert steady.idle_by_label() == [
        ["apply_fn (83 gaps)", pytest.approx(0.101314788, rel=1e-9)],
        ["none (2408 gaps)", pytest.approx(0.003890714, rel=1e-9)],
        ["feeder_release (5 gaps)", pytest.approx(7e-9, rel=1e-6)]]


class _Log:
    def __init__(self, cols):
        self.cols = {k: np.asarray(v) for k, v in cols.items()}

    def rows(self, start=0):
        return dict(self.cols), len(next(iter(self.cols.values())))


def test_requests_come_from_the_log_of_the_runtime_that_served_the_window(
        monkeypatch):
    """Of the process's recent logs (a calibration runtime's, the serving
    runtime's), the rows dispatched inside the window are the serving
    runtime's."""
    def log(dispatch):
        n = len(dispatch)
        return _Log({"rid": np.arange(n), "dispatch": np.asarray(dispatch)})

    calibration = log([1.0, 2.0, 3.0])        # before the window
    serving = log([9.0, 10.5, 11.0, 12.5])    # two inside it
    fake = types.SimpleNamespace(recent_logs=lambda: [calibration, serving])
    monkeypatch.setattr(program, "_recorder", lambda: fake)
    rows = program.requests(_window(None, origin=10.0, seconds=2.0))
    assert rows["dispatch"].tolist() == [10.5, 11.0]
    fake.recent_logs = lambda: []
    assert program.requests(_window(None)) is None


@pytest.fixture(scope="module")
def overload():
    """A recorded overload-cell trace with the program's spans, and what
    the readers read beside it: the calls, the request log rows and the
    pauses of the traced stretch, and the forward's scope tables."""
    with open(os.path.join(FIXTURES, "rmc1-overload.json")) as f:
        rec = json.load(f)
    profile = trace.reduce(os.path.join(FIXTURES,
                                        "rmc1-overload.xplane.pb"))
    calls = [harness.Call(i, np.zeros(b, np.int32), rows, None, t0, t1)
             for i, rows, b, t0, t1 in rec["calls"]]
    w = _window(profile, rec["origin"], rec["seconds"], calls)
    cfg = harness.load_json(RMC1)
    peak = harness.load_json(os.path.join(BENCH, "peaks.json"))[
        "TPU v5 lite"]
    return harness.Run(cfg, {}, peak, 0.0, w, {}, DLRM), rec


@pytest.fixture
def recorded(monkeypatch, overload):
    run, rec = overload
    fake = types.SimpleNamespace(recent_logs=lambda: [_Log(rec["requests"])],
                                 pauses=lambda: _Log(rec["pauses"]))
    monkeypatch.setattr(program, "_recorder", lambda: fake)
    tables = {int(b): t for b, t in rec["scope_tables"].items()}
    monkeypatch.setattr(program, "forward_scope_tables",
                        lambda run, buckets: {b: tables[b] for b in buckets})
    return run


# as the chip run that recorded them read the metrics the trace alone
# gives (device_idle_share, gather_device_ms.mean, forward_device_ms.mean)
PINNED = {
    "pad_ms.mean": 0.073799568178081,
    "dispatch_ms.mean": 0.45506684090861943,
    "wake_ms.mean": 0.5411956428571428,
    "idle_host_share": 20.64387590484605,
    "gather_device_ms.mean": 1.5601695909090907,
    "gc_pause_ms.max": 0.14500000003181412,
    "device_idle_share": 44.923134934043915,
    "forward_device_ms.mean": 1.5671581590909092,
    "step_mfu": 0.0025913236049581482,
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_metrics_reduce_a_recorded_overload_trace(recorded, name):
    assert read(name, recorded) == pytest.approx(PINNED[name], rel=1e-6)


def test_the_recorded_trace_holds_the_programs_spans(overload):
    run, rec = overload
    spans = program.program_spans(os.path.join(FIXTURES,
                                               "rmc1-overload.xplane.pb"))
    assert {s.name for s in spans} >= set(program.PROGRAM_SPANS) - {
        "runtime.dequeue"}
    rows = {k: np.asarray(v) for k, v in rec["requests"].items()}
    off, worst, rids = program.dispatch_fit(spans, rows)
    assert len(rids) >= 10 and np.mean(worst <= 50e3) >= 0.99
    # the calls' fit and the dispatch spans' fit agree
    assert abs(program.call_offset(run.window) - off) < 20e3


def test_the_device_plane_is_shifted_onto_the_hosts_clock(recorded):
    """On the recorded trace the device's plane runs ahead of the host's:
    executions "start" before the TPU runtime has enqueued them.  The
    shift fitted on the program's waits lies at most a launch delay below
    the runtime's own enqueue bound, and below its callback bound."""
    _, _, shift = program.aligned(recorded)
    b = program.runtime_bounds(os.path.join(FIXTURES,
                                            "rmc1-overload.xplane.pb"))
    assert b["enqueue"] > 500e3                   # half a millisecond ahead
    assert b["enqueue"] - 150e3 <= shift <= b["enqueue"] < b["callback"]
    assert shift == 896e3


def test_new_metrics_read_nothing_from_a_program_without_them(
        monkeypatch, steady):
    monkeypatch.setattr(program, "_recorder", lambda: None)
    run = harness.Run(harness.load_json(RMC1), {}, {}, 0.0, _window(steady),
                      {})
    for name in ("pad_ms.mean", "dispatch_ms.mean", "wake_ms.mean",
                 "idle_host_share", "gc_pause_ms.max"):
        assert read(name, run) is None, name
    # a forward whose HLO names no scope
    monkeypatch.setattr(program, "forward_scope_tables",
                        lambda run, buckets: {b: {} for b in buckets})
    assert read("gather_device_ms.mean", run) is None
