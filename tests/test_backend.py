"""The NodeBackend interface: sim/live equivalence, drive_fleet contract,
multi-tenant threading — small traces, tiny models (tier-1 budget)."""
import numpy as np
import pytest

from repro.cluster import (BucketedDeviceModel, Fleet, LiveNodeBackend,
                           NodeSpec, Pool, SimNodeBackend, WallClock,
                           drive_fleet, make_router, simulate_fleet)
from repro.cluster.fleet import NodeView
from repro.core.latency_model import TableDeviceModel
from repro.core.query_gen import sample_trace

pytestmark = pytest.mark.cluster

CPU = TableDeviceModel(np.array([1., 4, 16, 64, 256, 1024]),
                       np.array([.0008, .001, .0018, .0045, .015, .058]))


def _views(n=3):
    spec = NodeSpec(cpu=CPU, batch_size=8, n_executors=4)
    return [NodeView("pool", i, spec, 100.0) for i in range(n)]


def _trace(n=400, qps=600.0, seed=3):
    unit, sizes = sample_trace(np.random.default_rng(seed), n)
    return unit / qps, sizes


# ----------------------------------------------------------- sim backend


def test_drive_fleet_matches_simulate_fleet():
    """Explicit SimNodeBackends through drive_fleet ≡ the fleet wrapper
    (same engine, same windows)."""
    times, sizes = _trace()
    fleet = Fleet([Pool("pool", NodeSpec(cpu=CPU, batch_size=8,
                                         n_executors=4), count=3)])
    ref = simulate_fleet(times, sizes, fleet, make_router("round_robin"),
                         window_s=0.2)
    backends = [SimNodeBackend(v) for v in _views(3)]
    r = drive_fleet(times, sizes, backends, make_router("round_robin"),
                    window_s=0.2)
    np.testing.assert_allclose(r.p95_ms, ref.p95_ms, rtol=1e-12)
    np.testing.assert_allclose(r.p50_ms, ref.p50_ms, rtol=1e-12)
    assert r.n_queries == ref.n_queries


def test_sim_backend_completed_records_match_done_times():
    times, sizes = _trace(n=60)
    mids = (np.arange(60) % 2).astype(np.int64)
    backends = [SimNodeBackend(v) for v in _views(2)]
    r = drive_fleet(times, sizes, backends, make_router("round_robin"),
                    model_ids=mids)
    recs = [rec for b in backends for rec in b.completed_records()]
    assert len(recs) == 60
    assert sorted(rec.index for rec in recs) == list(range(60))
    for rec in recs:
        assert rec.t_arrival == times[rec.index]
        assert rec.model_id == mids[rec.index]
        assert rec.t_done >= rec.t_arrival
    # fleet-wide p95 reassembled from records matches the result
    lats = np.array([rec.t_done - rec.t_arrival for rec in recs])
    np.testing.assert_allclose(float(np.percentile(lats, 95) * 1e3),
                               r.p95_ms, rtol=1e-12)


def test_drive_fleet_argument_contract():
    times, sizes = _trace(n=20)
    backends = [SimNodeBackend(v) for v in _views(1)]
    fleet = Fleet([Pool("pool", NodeSpec(cpu=CPU), count=1)])
    with pytest.raises(ValueError, match="exactly one"):
        drive_fleet(times, sizes, backends, make_router("round_robin"),
                    fleet=fleet, factory=SimNodeBackend)
    with pytest.raises(ValueError, match="exactly one"):
        drive_fleet(times, sizes, None, make_router("round_robin"))
    from repro.cluster import Autoscaler
    with pytest.raises(ValueError, match="factory"):
        drive_fleet(times, sizes, backends, make_router("round_robin"),
                    window_s=0.1, autoscaler=Autoscaler(sla_ms=100.0))


def test_per_model_stats_from_labeled_traffic():
    times, sizes = _trace(n=200)
    mids = (np.arange(200) % 3).astype(np.int64)
    fleet = Fleet([Pool("pool", NodeSpec(cpu=CPU, batch_size=8), count=2)])
    r = simulate_fleet(times, sizes, fleet, make_router("round_robin"),
                      model_ids=mids)
    assert set(r.per_model) == {0, 1, 2}
    assert sum(m.n_queries for m in r.per_model.values()) == 200
    assert all(m.p95_ms > 0 for m in r.per_model.values())


def test_hetero_affinity_pins_tenant_to_pool():
    spec_a = NodeSpec(cpu=CPU, batch_size=8)
    spec_b = NodeSpec(cpu=CPU, batch_size=8)
    nodes = [NodeView("alpha", 0, spec_a, 100.0),
             NodeView("beta", 0, spec_b, 100.0)]
    times, sizes = _trace(n=100, qps=200.0)
    mids = (np.arange(100) % 2).astype(np.int64)
    router = make_router("hetero")
    router.affinity = {1: {"beta"}}
    assign = router.assign(times, sizes, nodes, model_ids=mids)
    assert np.all(assign[mids == 1] == 1)          # pinned tenant → beta
    assert (assign[mids == 0] == 0).any()          # others spread freely
    # affinity to a pool with no nodes present falls back to every node
    router = make_router("hetero")
    router.affinity = {1: {"gamma"}}
    assign = router.assign(times, sizes, nodes, model_ids=mids)
    assert assign.min() >= 0 and assign.max() <= 1


# ---------------------------------------------------------- live backend


def _tiny_apply():
    import jax
    import jax.numpy as jnp
    w = jnp.ones((4, 2)) * 0.5

    @jax.jit
    def apply_fn(batch):
        return batch["x"] @ w
    return apply_fn


def _make_batch(size, model_id):
    return {"x": np.ones((size, 4), np.float32)}


def _canned_device():
    # canned curve: no calibration in tier-1 tests
    return BucketedDeviceModel(np.array([1, 2, 4, 8, 16, 32, 64]),
                               np.full(7, 2e-4))


def _live_backend(clock, pool="live", index_in_pool=0):
    from repro.serve.runtime import ServingRuntime
    rt = ServingRuntime(_tiny_apply(), n_workers=1, batch_size=16,
                        max_bucket=64)
    spec = NodeSpec(cpu=_canned_device(), n_executors=1, batch_size=16,
                    request_overhead_s=0.0)
    return LiveNodeBackend(rt, _make_batch, spec=spec, pool=pool,
                           index_in_pool=index_in_pool, weight=100.0,
                           clock=clock, own_runtime=True)


def test_calibrate_device_fails_on_errored_requests():
    """The runtime turns an apply_fn exception into a record error that
    completes at once; calibration must raise, not time the errors."""
    from repro.cluster import calibrate_device
    ok = _tiny_apply()

    def apply_fn(batch):
        if batch["x"].shape[0] >= 4:
            raise RuntimeError("device halted")
        return ok(batch)

    with pytest.raises(RuntimeError, match="bucket 4 failed: .*halted"):
        calibrate_device(apply_fn, _make_batch, max_bucket=8, burst=2,
                         reps=1)


def test_live_backend_completes_trace_in_trace_time():
    times = np.linspace(0.0, 0.3, 30)
    sizes = np.full(30, 20, np.int64)              # 2 requests each
    mids = (np.arange(30) % 2).astype(np.int64)
    clock = WallClock()
    backends = [_live_backend(clock, index_in_pool=i) for i in range(2)]
    try:
        r = drive_fleet(times, sizes, backends, make_router("round_robin"),
                        model_ids=mids)
        assert r.n_queries == 30 and r.dropped == 0 and r.errors == 0
        assert r.p95_ms > 0
        assert set(r.per_model) == {0, 1}
        recs = [rec for b in backends for rec in b.completed_records()]
        assert sorted(rec.index for rec in recs) == list(range(30))
        for rec in recs:                   # trace-time coordinates
            assert rec.t_done >= rec.t_arrival >= 0.0
            assert rec.t_done < 30.0       # seconds of trace, not wall epoch
    finally:
        for b in backends:
            b.close()


def test_routers_make_identical_decisions_on_sim_and_live_backends():
    """The routing contract of the tentpole: a policy sees only the
    NodeHandle surface, so sim and live backends with the same
    spec/weight/identity get the same assignment on a fixed trace."""
    times, sizes = _trace(n=150, qps=300.0)
    spec = NodeSpec(cpu=_canned_device(), n_executors=1, batch_size=16,
                    request_overhead_s=0.0)
    sim_nodes = [SimNodeBackend(NodeView("live", i, spec, 100.0))
                 for i in range(2)]
    clock = WallClock()
    live_nodes = [_live_backend(clock, index_in_pool=i) for i in range(2)]
    try:
        for name in ("round_robin", "least_outstanding", "size_aware",
                     "hetero"):
            a_sim = make_router(name).assign(times, sizes, sim_nodes)
            a_live = make_router(name).assign(times, sizes, live_nodes)
            np.testing.assert_array_equal(a_sim, a_live)
    finally:
        for b in live_nodes:
            b.close()


def test_drive_fleet_rejects_duplicate_backend_identity():
    times, sizes = _trace(n=10)
    backends = [SimNodeBackend(NodeView("pool", 0, NodeSpec(cpu=CPU), 1.0)),
                SimNodeBackend(NodeView("pool", 0, NodeSpec(cpu=CPU), 1.0))]
    with pytest.raises(ValueError, match="duplicate backend identity"):
        drive_fleet(times, sizes, backends, make_router("round_robin"))


def test_errored_live_queries_count_as_dropped():
    """An apply_fn failure completes near-instantly; counting it as served
    would inflate measured capacity — it must surface as dropped+error."""
    import jax

    def apply_fn(batch):
        if batch["x"].shape[0] >= 16:          # bucket of the size-12 query
            raise RuntimeError("boom")
        return jax.numpy.asarray(batch["x"]).sum()

    from repro.serve.runtime import ServingRuntime
    rt = ServingRuntime(apply_fn, n_workers=1, batch_size=16, max_bucket=64)
    spec = NodeSpec(cpu=_canned_device(), n_executors=1, batch_size=16,
                    request_overhead_s=0.0)
    b = LiveNodeBackend(rt, _make_batch, spec=spec, clock=WallClock(),
                        own_runtime=True)
    try:
        times = np.linspace(0.0, 0.1, 6)
        sizes = np.array([4, 4, 12, 4, 4, 4], np.int64)   # one errors
        r = drive_fleet(times, sizes, [b], make_router("round_robin"))
        assert r.errors == 1
        assert r.dropped == 1                   # the errored query
        assert r.n_queries == 5
        assert not r.meets(1e9)                 # dropped → SLA check fails
    finally:
        b.close()


def test_live_backend_submit_before_start_anchors_clock():
    clock = WallClock()
    b = _live_backend(clock)
    try:
        b.submit(np.array([0]), np.array([0.0]), np.array([4]))
        b.drain(timeout=30)
        recs = b.completed_records()
        assert len(recs) == 1 and recs[0].error is None
    finally:
        b.close()