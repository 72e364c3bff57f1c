"""Live serving runtime: split/execute/complete, bucketing, online control,
the request log and the pause counter."""
import gc
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve import recorder
from repro.serve.batching import bucket_for, pad_batch, slice_result
from repro.serve.runtime import (OffloadController, OnlineController,
                                 ServingRuntime)


def test_bucketing():
    assert bucket_for(1) == 1
    assert bucket_for(2) == 2
    assert bucket_for(3) == 4
    assert bucket_for(64) == 64
    assert bucket_for(65) == 128
    assert bucket_for(1024) == 1024
    assert bucket_for(1025, max_bucket=1024) == 1024    # clamped
    assert bucket_for(5000, max_bucket=1024) == 1024
    assert bucket_for(5, max_bucket=4) == 4


def test_pad_and_slice_roundtrip():
    b = {"x": jnp.arange(6.0).reshape(3, 2)}
    p = pad_batch(b, 8)
    assert p["x"].shape == (8, 2)
    out = slice_result(p, 3)
    np.testing.assert_array_equal(np.asarray(out["x"]), np.asarray(b["x"]))
    # exact fit: no copy needed, shapes preserved
    q = pad_batch(b, 3)
    assert q["x"].shape == (3, 2)
    # multi-leaf round-trip
    b2 = {"x": jnp.ones((5, 2)), "y": jnp.zeros((5,))}
    p2 = pad_batch(b2, 8)
    assert p2["x"].shape == (8, 2) and p2["y"].shape == (8,)
    out2 = slice_result(p2, 5)
    assert out2["x"].shape == (5, 2) and out2["y"].shape == (5,)


def test_pad_batch_rejects_oversize():
    """A request larger than its bucket means the caller forgot to split —
    pad_batch must refuse instead of silently dropping rows (it used to
    crash with a negative broadcast)."""
    b = {"x": jnp.ones((9, 2))}
    with pytest.raises(ValueError, match="split oversize"):
        pad_batch(b, 8)


def test_submit_rejects_zero_size():
    """size=0 would enqueue zero requests but leave a permanent
    _outstanding entry, deadlocking drain()."""
    rt = _runtime()
    try:
        with pytest.raises(ValueError, match="size"):
            rt.submit(0, {"x": jnp.ones((0, 4))}, 0)
    finally:
        rt.shutdown()


def test_runtime_splits_oversize_when_knob_exceeds_bucket():
    """The online controller can climb batch_size past max_bucket; submit
    must cap request size at max_bucket so no request outruns its bucket."""
    rt = _runtime(batch_size=64)
    rt.max_bucket = 16
    try:
        rt.submit(0, {"x": jnp.ones((50, 4))}, 50)      # → ⌈50/16⌉ requests
        rt.drain(timeout=60)
        recs = rt.completed()
        assert len(recs) == 1 and recs[0].latency_ms > 0
    finally:
        rt.shutdown()


def _runtime(batch_size=32, n_workers=2):
    w = jnp.ones((4, 1)) * 0.5

    @jax.jit
    def apply_fn(batch):
        return batch["x"] @ w

    return ServingRuntime(apply_fn, n_workers=n_workers, batch_size=batch_size)


def test_runtime_completes_queries():
    rt = _runtime()
    try:
        rng = np.random.default_rng(0)
        for qid in range(20):
            size = int(rng.integers(1, 200))
            rt.submit(qid, {"x": jnp.ones((size, 4))}, size)
        rt.drain(timeout=60)
        recs = rt.completed()
        assert len(recs) == 20
        assert all(r.latency_ms > 0 for r in recs)
    finally:
        rt.shutdown()


def test_runtime_splits_by_batch_size():
    rt = _runtime(batch_size=16)
    try:
        rt.submit(0, {"x": jnp.ones((100, 4))}, 100)   # → 7 requests
        rt.drain(timeout=60)
        assert len(rt.completed()) == 1
    finally:
        rt.shutdown()


def test_pad_batch_numpy_stays_numpy():
    """numpy leaves are padded host-side (no per-shape XLA compile churn);
    device leaves keep the jnp path."""
    p = pad_batch({"x": np.ones((3, 2), np.float32)}, 8)
    assert isinstance(p["x"], np.ndarray) and p["x"].shape == (8, 2)
    q = pad_batch({"x": jnp.ones((3, 2))}, 8)
    assert not isinstance(q["x"], np.ndarray) and q["x"].shape == (8, 2)


def test_worker_error_surfaces_and_drain_completes():
    """An apply_fn exception must not kill the worker or strand the
    query's _outstanding entry (which used to deadlock drain()); the error
    is carried on the QueryRecord."""
    calls = []

    def apply_fn(batch):
        calls.append(batch["x"].shape[0])
        if len(calls) == 1:
            raise RuntimeError("boom")
        return batch["x"].sum()

    rt = ServingRuntime(apply_fn, n_workers=1, batch_size=32)
    try:
        rt.submit(0, {"x": np.ones((8, 2), np.float32)}, 8)
        rt.drain(timeout=30)                     # must not deadlock
        rt.submit(1, {"x": np.ones((8, 2), np.float32)}, 8)
        rt.drain(timeout=30)                     # worker still alive
        bad, good = rt.record(0), rt.record(1)
        assert bad.t_done > 0 and "boom" in bad.error
        assert good.t_done > 0 and good.error is None
    finally:
        rt.shutdown()


def test_online_controller_steps_down_on_sla_violation():
    rt = _runtime(batch_size=64)
    ctl = OnlineController(rt, sla_ms=0.0001, window=5)   # impossible SLA
    try:
        for qid in range(10):
            rt.submit(qid, {"x": jnp.ones((64, 4))}, 64)
        rt.drain(timeout=60)
        ctl.step()
        assert rt.batch_size < 64                          # stepped down
    finally:
        rt.shutdown()


def test_online_controller_steps_up_when_headroom():
    rt = _runtime(batch_size=16)
    ctl = OnlineController(rt, sla_ms=1e6, window=5)       # infinite headroom
    try:
        for qid in range(10):
            rt.submit(qid, {"x": jnp.ones((16, 4))}, 16)
        rt.drain(timeout=60)
        ctl.step()
        assert rt.batch_size > 16
    finally:
        rt.shutdown()


def _fed_controller(batch_size, sla_ms, ladder=None):
    """A controller whose runtime has a full window of completed queries."""
    rt = _runtime(batch_size=batch_size)
    kwargs = {} if ladder is None else {"ladder": ladder}
    ctl = OnlineController(rt, sla_ms=sla_ms, window=5, **kwargs)
    for qid in range(6):
        rt.submit(qid, {"x": jnp.ones((8, 4))}, 8)
    rt.drain(timeout=60)
    return rt, ctl


def test_online_controller_snaps_off_ladder_knob():
    """A runtime constructed with a batch size not on the ladder used to
    raise ValueError in step(); it must snap to the nearest rung and keep
    climbing from there."""
    rt, ctl = _fed_controller(batch_size=48, sla_ms=1e6)   # 48 ∉ ladder
    try:
        ctl.step()                                          # must not raise
        assert rt.batch_size in ctl.ladder
        assert rt.batch_size == 64           # snapped to 32|64, headroom → up
    finally:
        rt.shutdown()


def test_online_controller_clamps_at_ladder_ends():
    rt, ctl = _fed_controller(batch_size=1, sla_ms=1e-6)   # breach at floor
    try:
        ctl.step()
        assert rt.batch_size == 1                           # clamped
    finally:
        rt.shutdown()
    rt, ctl = _fed_controller(batch_size=16, sla_ms=1e6, ladder=(4, 8, 16))
    try:
        ctl.step()
        assert rt.batch_size == 16             # top of the ladder: clamped
    finally:
        rt.shutdown()


def test_online_controller_holds_inside_hysteresis_band():
    """p95 between 0.7×SLA and SLA: neither step direction fires."""
    rt, ctl = _fed_controller(batch_size=16, sla_ms=1.0)
    try:
        done = rt.completed()
        p95 = float(np.percentile([r.latency_ms for r in done], 95))
        ctl.sla_ms = p95 / 0.85                # 0.7×SLA < p95 < SLA
        ctl.step()
        assert rt.batch_size == 16
        assert ctl.history and ctl.history[-1][0] == 16
    finally:
        rt.shutdown()


# --------------------------------------------- offload-threshold controller


def test_offload_controller_breach_steps_toward_unloaded_path():
    ctl = OffloadController(sla_ms=100.0, threshold=300)
    # CPU queueing dominates -> offload more (threshold down one rung)
    assert ctl.step(250.0, cpu_queue_p99_ms=80.0, acc_queue_p99_ms=5.0) == 200
    # accelerator queueing dominates -> keep work on CPU (up one rung)
    assert ctl.step(250.0, cpu_queue_p99_ms=5.0, acc_queue_p99_ms=80.0) == 300
    assert [h[0] for h in ctl.history] == [200, 300]


def test_offload_controller_headroom_drifts_to_prefer():
    ctl = OffloadController(sla_ms=100.0, threshold=300)
    ctl.threshold = 50                     # emergency moves left it low
    assert ctl.step(10.0, 0.0, 0.0) == 100   # one rung back toward 300
    assert ctl.step(10.0, 0.0, 0.0) == 150
    # from above, drift comes DOWN toward prefer too
    ctl.threshold = 700
    assert ctl.step(10.0, 0.0, 0.0) == 450


def test_offload_controller_holds_on_nan_and_mid_band():
    ctl = OffloadController(sla_ms=100.0, threshold=300)
    assert ctl.step(float("nan"), 1.0, 1.0) == 300      # empty window
    assert ctl.step(80.0, 50.0, 1.0) == 300             # inside the band
    # NaN queue components during a breach default to zero, not a crash
    assert ctl.step(250.0, float("nan"), float("nan")) == 200


def test_offload_controller_snaps_and_clamps():
    assert OffloadController(sla_ms=1.0, threshold=None).threshold == 1001
    assert OffloadController(sla_ms=1.0, threshold=333).threshold == 300
    ctl = OffloadController(sla_ms=100.0, threshold=1)
    assert ctl.step(500.0, 10.0, 0.0) == 1              # clamped at floor
    ctl2 = OffloadController(sla_ms=100.0, threshold=1001)
    assert ctl2.step(500.0, 0.0, 10.0) == 1001          # clamped at top


# ------------------------------------------------ request log and pauses


def _served(rt, sizes):
    """Submit one query per size, wait, and return the runtime's log."""
    for qid, size in enumerate(sizes):
        rt.submit(qid, {"x": np.ones((size, 4), np.float32)}, size)
    rt.drain(timeout=60)
    rows, _ = rt.request_log()
    return rows


def test_request_log_has_one_row_per_request():
    rt = _runtime(batch_size=16)
    try:
        rows = _served(rt, [40, 3, 16])       # 16+16+8, 3, 16
    finally:
        rt.shutdown()
    got = sorted(zip(rows["qid"], rows["part"], rows["rows"],
                     rows["bucket"]))
    assert got == [(0, 0, 16, 16), (0, 1, 16, 16), (0, 2, 8, 8),
                   (1, 0, 3, 4), (2, 0, 16, 16)]
    assert set(rows["worker"]) <= {0, 1}
    assert len(set(rows["rid"])) == 5


def test_request_log_stamps_are_ordered_and_start_the_query():
    rt = _runtime(batch_size=8)
    try:
        rows = _served(rt, [30, 5, 17, 1])
        recs = {r.qid: r for r in rt.completed()}
    finally:
        rt.shutdown()
    t = np.stack([rows[k] for k in recorder.RequestLog.STAMPS])
    assert np.all(np.isfinite(t))
    assert np.all(np.diff(t, axis=0) >= 0)    # enqueue ≤ pickup ≤ … ≤ done
    for qid, rec in recs.items():
        mine = rows["qid"] == qid
        assert rec.t_started == rows["pickup"][mine].min()
        assert rec.t_done == rows["done"][mine].max()
        assert np.all(rows["enqueue"][mine] == rec.t_arrival)


def test_request_log_cursor_reads_each_row_once():
    rt = _runtime(batch_size=4)
    try:
        first = _served(rt, [9])              # 3 requests
        _, cur = rt.request_log()
        _served(rt, [5])                      # 2 more
        fresh, nxt = rt.request_log(cur)
    finally:
        rt.shutdown()
    assert len(first["rid"]) == 3 and len(fresh["rid"]) == 2
    assert set(fresh["rid"]).isdisjoint(first["rid"])
    assert nxt > cur and rt.request_log(nxt)[0]["rid"].size == 0


def test_request_log_holds_a_failed_request():
    def apply_fn(batch):
        raise RuntimeError("boom")

    rt = ServingRuntime(apply_fn, n_workers=1, batch_size=8)
    try:
        rows = _served(rt, [3])
    finally:
        rt.shutdown()
    assert len(rows["rid"]) == 1
    assert np.isfinite(rows["pad"][0]) and np.isnan(rows["dispatch"][0])
    assert rows["done"][0] >= rows["pickup"][0]


def test_shutdown_closes_the_rows_of_abandoned_requests():
    """Requests still queued at shutdown are never picked up; their rows
    are closed as such, so they hold no reader's cursor."""
    import threading
    go = threading.Event()

    def apply_fn(batch):
        go.wait(timeout=10)
        return batch["x"]

    rt = ServingRuntime(apply_fn, n_workers=1, batch_size=1)
    rt.submit(0, {"x": np.ones((5, 4), np.float32)}, 5)
    time.sleep(0.05)                          # the worker holds request 0
    rt._stop.set()
    go.set()
    rt.shutdown()
    rows, _ = rt.request_log()
    assert len(rows["rid"]) == 5
    assert np.isfinite(rows["done"]).all()
    picked = np.isfinite(rows["pickup"])
    assert picked[0] and not picked[1:].any()
    assert (rows["bucket"][~picked] == -1).all()


def test_request_log_ring_keeps_the_newest_rows():
    log = recorder.RequestLog(capacity=4)
    rids = [log.open(q, 0, 1, float(q)) for q in range(6)]
    for r in rids:
        log.close(r, 1, 0, 1.0, 2.0, 3.0, 4.0, 5.0)
    rows, cur = log.rows(0)
    assert rows["rid"].tolist() == [2, 3, 4, 5] and cur == 6
    # a request still running holds the cursor, and the rows after it
    late = log.open(9, 0, 1, 9.0)
    after = log.open(10, 0, 1, 9.0)
    log.close(after, 1, 0, 1.0, 2.0, 3.0, 4.0, 5.0)
    rows, cur = log.rows(6)
    assert len(rows["rid"]) == 0 and cur == late
    log.close(late, 1, 0, 1.0, 2.0, 3.0, 4.0, 5.0)
    rows, cur = log.rows(6)
    assert rows["rid"].tolist() == [late, after] and cur == 8
    # a row the ring has wrapped past is not written over by its close
    log.close(rids[0], 9, 9, 0.0, 0.0, 0.0, 0.0, 0.0)
    assert log.rows(4)[0]["bucket"].tolist() == [1] * 4


def test_each_runtime_has_its_own_request_log():
    a, b = _runtime(batch_size=4), _runtime(batch_size=4)
    try:
        ra, rb = _served(a, [9]), _served(b, [5])   # 3 and 2 requests
    finally:
        a.shutdown()
        b.shutdown()
    assert ra["rid"].tolist() == [0, 1, 2] and rb["rid"].tolist() == [0, 1]
    logs = recorder.recent_logs()
    assert logs[-2:] == [a._log, b._log]


def test_request_log_adds_no_object_per_request():
    """Serving 2,000 requests leaves no more garbage-collected objects
    behind than serving 100: the log is numpy columns written in place."""
    def grown(sizes):
        rt = ServingRuntime(lambda b: b["x"], n_workers=1, batch_size=1)
        try:
            _served(rt, [1])                  # first-call work
            gc.collect()
            before = len(gc.get_objects())
            _served(rt, sizes)
            gc.collect()
            return len(gc.get_objects()) - before
        finally:
            rt.shutdown()
    small = grown([5] * 20)                   # 20 queries, 100 requests
    large = grown([100] * 20)                 # 20 queries, 2,000 requests
    assert large - small < 200            # one object per request: 1,900


def test_pause_counter_counts_a_forced_collection():
    _runtime().shutdown()                     # installs the hook
    log = recorder.pauses()
    assert gc.callbacks.count(log) == 1
    before = ServingRuntime.pauses()
    _, cur = log.rows()
    t0 = time.monotonic()
    gc.collect()
    t1 = time.monotonic()
    after = ServingRuntime.pauses()
    assert after["count"][2] == before["count"][2] + 1
    assert after["total_s"][2] > before["total_s"][2]
    assert after["max_s"][2] >= after["total_s"][2] - before["total_s"][2]
    rows, _ = log.rows(cur)
    full = rows["generation"] == 2
    assert full.sum() == 1
    assert t0 <= rows["start"][full][0] <= rows["end"][full][0] <= t1


# ------------------------------------------- two requests in flight per worker


class _Held:
    """A served output whose ``block_until_ready`` waits until the test
    releases it (or raises ``fail`` then)."""

    def __init__(self, fail=None):
        self.waited = threading.Event()
        self.released = threading.Event()
        self.fail = fail

    def block_until_ready(self):
        self.waited.set()
        if not self.released.wait(timeout=10):
            raise TimeoutError("the test never released this output")
        if self.fail is not None:
            raise self.fail
        return self


class _HeldModel:
    """``apply_fn`` returning a ``_Held`` output per call, in call order;
    ``fail_at`` maps a call index to the error its dispatch raises."""

    def __init__(self, fail_at=None):
        self.outs: list[_Held] = []
        self.fail_at = fail_at or {}
        self.called = threading.Condition()

    def __call__(self, batch):
        with self.called:
            k = len(self.outs)
            self.outs.append(_Held())
            self.called.notify_all()
        if k in self.fail_at:
            raise self.fail_at[k]
        return self.outs[k]

    def wait_calls(self, n, timeout=10.0):
        with self.called:
            assert self.called.wait_for(lambda: len(self.outs) >= n,
                                        timeout), f"{len(self.outs)} calls"


def _until(cond, timeout=10.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, "condition never held"
        time.sleep(0.001)


def _submit(rt, sizes):
    for qid, size in enumerate(sizes):
        rt.submit(qid, {"x": np.ones((size, 4), np.float32)}, size)


def test_the_runtime_dispatches_the_next_request_before_one_is_ready():
    model = _HeldModel()
    rt = ServingRuntime(model, n_workers=1, batch_size=1)
    try:
        _submit(rt, [1, 1])
        model.wait_calls(2)               # both out before either is ready
        assert rt.n_completed == 0
        for out in model.outs:
            out.released.set()
        rt.drain(timeout=10)
        rows, _ = rt.request_log()
    finally:
        rt.shutdown()
    assert rows["pad"][1] < rows["ready"][0]


def test_no_worker_has_more_than_two_requests_in_flight():
    model = _HeldModel()
    rt = ServingRuntime(model, n_workers=2, batch_size=1)
    try:
        _submit(rt, [1] * 12)
        for k in range(12):
            model.wait_calls(min(k + 4, 12))
            time.sleep(0.02)              # room for a fifth dispatch
            assert len(model.outs) == min(k + 4, 12)
            model.outs[k].released.set()
        rt.drain(timeout=10)
        rows, _ = rt.request_log()
    finally:
        rt.shutdown()
    for w in (0, 1):
        mine = rows["worker"] == w
        pick, ready = rows["pickup"][mine], rows["ready"][mine]
        order = np.argsort(rows["dispatch"][mine])
        pick, ready = pick[order], ready[order]
        # request k + 2 is picked up only once request k is ready
        assert np.all(pick[2:] > ready[:-2])


def test_completions_follow_dispatch_order_and_a_query_ends_with_its_last():
    model = _HeldModel()
    rt = ServingRuntime(model, n_workers=1, batch_size=2)
    try:
        _submit(rt, [4, 1])               # query 0: 2 requests; query 1: 1
        model.wait_calls(2)
        model.outs[1].released.set()      # the later one is ready first
        time.sleep(0.05)
        assert rt.n_completed == 0        # its completion waits on the first
        model.outs[0].released.set()
        _until(lambda: rt.record(0).t_done > 0)   # both its requests done
        model.wait_calls(3)
        assert rt.record(1).t_done == 0
        model.outs[2].released.set()
        rt.drain(timeout=10)
        rows, _ = rt.request_log()
        done = [r.qid for r in rt.take_completed()]
        recs = [rt.record(q) for q in (0, 1)]
    finally:
        rt.shutdown()
    assert done == [0, 1]
    order = np.argsort(rows["dispatch"])
    assert np.all(np.diff(rows["done"][order]) >= 0)
    assert np.all(np.diff(rows["ready"][order]) >= 0)
    for rec in recs:
        assert rec.t_done == rows["done"][rows["qid"] == rec.qid].max()
    t = np.stack([rows[k] for k in recorder.RequestLog.STAMPS])
    assert np.all(np.diff(t, axis=0) >= 0)


def test_a_failed_dispatch_completes_its_query_while_another_is_in_flight():
    model = _HeldModel(fail_at={1: RuntimeError("boom")})
    rt = ServingRuntime(model, n_workers=1, batch_size=1)
    try:
        _submit(rt, [1, 1, 1])
        model.wait_calls(2)
        model.outs[0].released.set()
        model.wait_calls(3)
        model.outs[2].released.set()
        rt.drain(timeout=10)
        rows, _ = rt.request_log()
        recs = [rt.record(q) for q in range(3)]
    finally:
        rt.shutdown()
    assert "boom" in recs[1].error and recs[1].t_done > 0
    assert recs[0].error is None and recs[2].error is None
    assert rt.n_pending == 0
    assert np.isfinite(rows["pad"][1]) and np.isnan(rows["dispatch"][1])
    assert np.isnan(rows["ready"][1]) and np.isfinite(rows["done"]).all()


def test_an_output_that_fails_its_wait_completes_its_query_with_the_error():
    model = _HeldModel()
    rt = ServingRuntime(model, n_workers=1, batch_size=1)
    try:
        _submit(rt, [1, 1])
        model.wait_calls(2)
        model.outs[0].fail = RuntimeError("device lost")
        for out in model.outs:
            out.released.set()
        rt.drain(timeout=10)
        rows, _ = rt.request_log()
        bad, good = rt.record(0), rt.record(1)
    finally:
        rt.shutdown()
    assert "device lost" in bad.error and bad.t_done > 0
    assert good.error is None and good.t_done > 0
    assert np.isfinite(rows["dispatch"][0]) and np.isnan(rows["ready"][0])
    assert np.isfinite(rows["ready"][1]) and np.isfinite(rows["done"]).all()


def test_shutdown_closes_the_row_of_a_request_still_in_flight():
    model = _HeldModel()
    rt = ServingRuntime(model, n_workers=1, batch_size=1)
    rt._JOIN_S = 0.3
    _submit(rt, [1])
    model.wait_calls(1)
    _until(lambda: model.outs[0].waited.is_set())   # dispatched, waited on
    t0 = time.monotonic()
    rt.shutdown()
    took = time.monotonic() - t0
    rows, _ = rt.request_log()
    assert took < rt._JOIN_S + 2.0
    assert len(rows["rid"]) == 1 and np.isfinite(rows["dispatch"][0])
    assert np.isnan(rows["ready"][0]) and np.isfinite(rows["done"][0])
    # the result arriving later changes neither the row nor the query
    model.outs[0].released.set()
    for t in rt._threads:
        t.join(timeout=5)
        assert not t.is_alive()
    again, _ = rt.request_log()
    assert np.isnan(again["ready"][0]) and again["done"][0] == rows["done"][0]
    assert rt.n_completed == 0 and rt.record(0).t_done == 0


def test_many_workers_complete_every_query_once_under_thread_switching():
    """More workers than cores, the interpreter switching threads often:
    each query completes once, when its last request does."""
    import sys
    n_workers = (os.cpu_count() or 4) + 1
    rng = np.random.default_rng(1)
    sizes = rng.integers(1, 40, 300).tolist()
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    rt = ServingRuntime(lambda b: b["x"] * 2.0, n_workers=n_workers,
                        batch_size=8)
    try:
        _submit(rt, sizes)
        rt.drain(timeout=60)
        rows, _ = rt.request_log()
        done = rt.completed_log(0)
    finally:
        sys.setswitchinterval(interval)
        rt.shutdown()
    assert sorted(r.qid for r in done) == list(range(len(sizes)))
    assert rt.n_completed == len(sizes) and rt.n_pending == 0
    assert len(rows["rid"]) == sum(-(-n // 8) for n in sizes)
    for r in done:
        assert r.t_done == rows["done"][rows["qid"] == r.qid].max()
