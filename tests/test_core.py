"""DeepRecInfra + DeepRecSched: simulator queueing sanity, scheduler
optimality.  (Hypothesis property tests live in test_properties.py so these
plain tests run even without the dev extras.)"""
import numpy as np
import pytest

from repro.core import query_gen as qg
from repro.core.latency_model import (AnalyticalDeviceModel, ContentionModel,
                                      GPU_1080TI, TableDeviceModel)
from repro.core.scheduler import static_baseline, tune
from repro.core.simulator import (FaultConfig, SchedulerConfig,
                                  max_qps_under_sla, simulate)

CPU = TableDeviceModel(np.array([1., 4, 16, 64, 256, 1024]),
                       np.array([.0008, .001, .0018, .0045, .015, .058]))


# ------------------------------------------------------------ query gen


def test_production_heavier_tail_than_lognormal():
    rng = np.random.default_rng(0)
    prod = qg.PRODUCTION.sample(rng, 100_000)
    ln = qg.LOGNORMAL.sample(rng, 100_000)
    assert np.percentile(prod, 99) > 1.5 * np.percentile(ln, 99)
    # paper Fig. 6 anchor: top-25% of queries ≈ half the work
    p75 = np.percentile(prod, 75)
    share = prod[prod > p75].sum() / prod.sum()
    assert 0.4 < share < 0.65


def test_query_stream_monotone():
    stream = qg.query_stream(0, 100.0)
    qs = [next(stream) for _ in range(3000)]
    times = [q.arrival for q in qs]
    assert all(t2 >= t1 for t1, t2 in zip(times, times[1:]))
    assert len({q.qid for q in qs}) == 3000


# ------------------------------------------------------------ simulator


def _queries(qps, n=2000, seed=0):
    return qg.generate_queries(np.random.default_rng(seed), qps, n)


def test_all_queries_complete():
    r = simulate(_queries(500), CPU, SchedulerConfig(batch_size=64))
    assert r.n_queries == 2000 and r.dropped == 0


def test_latency_increases_with_load():
    p95s = [simulate(_queries(q), CPU, SchedulerConfig(batch_size=64)).p95_ms
            for q in (200, 2000, 6000)]
    assert p95s[0] < p95s[1] < p95s[2]


def test_single_query_latency_equals_service_time():
    """At trivial load, query latency == service time + request overhead."""
    cfg = SchedulerConfig(batch_size=64, n_executors=4)
    qs = [qg.Query(0, 0.0, 64)]
    r = simulate(qs, CPU, cfg)
    want_ms = (CPU.latency(64) + cfg.request_overhead_s) * 1e3
    assert abs(r.mean_ms - want_ms) < 0.05


def test_splitting_reduces_latency_at_low_load():
    """A 1024-item query on 16 cores at B=64 beats B=1024 on one core."""
    qs = [qg.Query(0, 0.0, 1024)]
    one = simulate(qs, CPU, SchedulerConfig(batch_size=1024, n_executors=16))
    split = simulate(qs, CPU, SchedulerConfig(batch_size=64, n_executors=16))
    assert split.mean_ms < one.mean_ms


def test_offload_moves_large_queries():
    accel = AnalyticalDeviceModel(flops_per_sample=50e6,
                                  mem_bytes_per_sample=60e3,
                                  in_bytes_per_sample=12e3, **GPU_1080TI)
    r = simulate(_queries(800), CPU,
                 SchedulerConfig(batch_size=64, offload_threshold=200),
                 accel=accel)
    assert 0.0 < r.accel_frac_work < 1.0


def test_contention_slows_parallel_requests():
    cont = ContentionModel(factor_at_full=2.0)
    base = simulate(_queries(2000), CPU, SchedulerConfig(batch_size=32))
    slow = simulate(_queries(2000), CPU, SchedulerConfig(batch_size=32),
                    contention=cont)
    assert slow.p95_ms > base.p95_ms


def test_stragglers_hedging_failures():
    cfg = SchedulerConfig(batch_size=64)
    base = simulate(_queries(2000), CPU, cfg)
    st_ = simulate(_queries(2000), CPU, cfg,
                   faults=FaultConfig(straggler_frac=0.05, straggler_mult=6))
    hg = simulate(_queries(2000), CPU, cfg,
                  faults=FaultConfig(straggler_frac=0.05, straggler_mult=6,
                                     hedge_factor=2.0))
    assert st_.p95_ms > base.p95_ms
    assert hg.p95_ms < st_.p95_ms and hg.hedges > 0
    fl = simulate(_queries(2000), CPU, cfg,
                  faults=FaultConfig(fail_times=(0.1, 0.2, 0.3)))
    assert fl.n_queries == 2000          # at-least-once: nothing lost


# ------------------------------------------------------------ scheduler


def test_max_qps_respects_sla():
    cfg = SchedulerConfig(batch_size=64)
    q100 = max_qps_under_sla(CPU, cfg, 100.0, n_queries=800, iters=6)
    q10 = max_qps_under_sla(CPU, cfg, 10.0, n_queries=800, iters=6)
    assert q100 > q10 > 0


def test_tune_beats_static_baseline():
    sla = 100.0
    base_b = static_baseline(1000, 40)
    base_q = max_qps_under_sla(CPU, SchedulerConfig(batch_size=base_b), sla,
                               n_queries=800, iters=6)
    r = tune(CPU, sla, n_queries=800)
    assert r.qps >= base_q                      # paper Fig. 11: ≥ baseline
    assert r.batch_size in {1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024}


def test_tune_with_accel_improves_or_matches():
    accel = AnalyticalDeviceModel(flops_per_sample=50e6,
                                  mem_bytes_per_sample=60e3,
                                  in_bytes_per_sample=12e3, **GPU_1080TI)
    r_cpu = tune(CPU, 100.0, n_queries=600)
    r_gpu = tune(CPU, 100.0, accel=accel, n_queries=600)
    assert r_gpu.qps >= 0.95 * r_cpu.qps


def test_device_model_monotone_latency():
    for b1, b2 in [(1, 16), (16, 256), (256, 4096)]:
        assert CPU.latency(b2) > CPU.latency(b1)


def test_tune_pool_workers_import_no_jax():
    """``tune(workers=N)`` spawns fresh interpreters that import the
    scheduler's module chain to unpickle their work.  That chain must not
    import JAX: on an accelerator host a worker that loaded it would
    contend with the parent for the chip."""
    import os
    import subprocess
    import sys
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    code = ("import sys, repro.core, repro.core.scheduler\n"
            "bad = sorted(m for m in sys.modules if m.split('.')[0] == 'jax')\n"
            "assert not bad, bad\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120,
                         env=dict(os.environ, PYTHONPATH=src))
    assert res.returncode == 0, res.stderr


def test_accelerator_model_rejects_unknown_kind():
    from repro.core.latency_model import accelerator_model
    with pytest.raises(ValueError, match="unknown accelerator kind"):
        accelerator_model(None, "v6e")
