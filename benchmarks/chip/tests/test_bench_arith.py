"""The benchmark's arithmetic on the CPU: metric readers, costs against
XLA's count, the reference against the served model."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(BENCH, "..", "..", "src")]

import costs  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import traffic  # noqa: E402
from repro.cluster.backend import CompletedQuery  # noqa: E402

SMOKE = os.path.join(BENCH, "tests", "fixtures", "dlrm-smoke.json")
DLRM = harness.load_model(BENCH, "dlrm")


def window(times, sizes, done, at_close="wait", errors=(), calls=(),
           seconds=10.0):
    """A window whose query ``i`` completed at ``done[i]`` (NaN: never)."""
    n = len(times)
    sched = traffic.Schedule(np.asarray(times, float),
                             np.asarray(sizes, np.int64), np.zeros(n, int))
    recs = [CompletedQuery(i, times[i], done[i],
                           error="boom" if i in errors else None)
            for i in range(n) if not np.isnan(done[i])]
    return harness.Window(seconds, sched, at_close, recs, list(calls), 0.0,
                          0, [])


def read(name, w):
    run = harness.Run(cfg={}, mix={}, peak={}, setup_s=1.0, window=w, pool={})
    return harness.load_reader(BENCH, name)(run)


@pytest.mark.parametrize("failures, finite", [(0, True), (1, True),
                                              (49, True), (51, False)])
def test_p50_counts_failures_as_infinite(failures, finite):
    n = 100
    times = np.arange(n) * 0.1
    done = times + 0.010
    done[:failures // 2] = np.nan                  # never came
    errors = set(range(failures // 2, failures))   # came with an error
    w = window(times, np.ones(n, int), done, errors=errors)
    assert w.failed() == failures
    p50 = read("p50_ms", w)
    assert np.isfinite(p50) == finite
    if finite:
        assert p50 == pytest.approx(10.0, abs=1e-6)


def test_items_per_s_counts_only_completions_inside_the_window():
    times = [0.0, 1.0, 2.0, 3.0]
    sizes = [10, 20, 40, 80]
    done = [0.5, 9.0, 12.0, np.nan]      # inside, inside, after, never
    w = window(times, sizes, done, at_close="abandon", seconds=10.0)
    assert read("items_per_s", w) == pytest.approx(30 / 10.0)
    assert w.failed() == 0               # the backlog is abandoned
    w = window(times, sizes, done, at_close="abandon", errors={0})
    assert read("items_per_s", w) == pytest.approx(20 / 10.0)
    assert w.failed() == 1


def test_pad_share_is_padding_over_bucket_rows():
    def call(rows, bucket, t):
        tags = np.concatenate([np.arange(rows), np.zeros(bucket - rows)])
        return harness.Call(0, tags.astype(np.int32), rows, None, t, t + 1e-3)
    calls = [call(64, 64, 1.0), call(33, 64, 2.0), call(3, 4, 3.0)]
    w = window([0.0], [1], [1.0], calls=calls)
    assert read("pad_share", w) == pytest.approx(100 * (1 - 100 / 132))
    assert read("host_call_ms.mean", w) == pytest.approx(1.0)


def test_real_rows_reads_the_padding():
    from check import real_rows
    assert real_rows(np.array([7, 8, 9, 7])) == 3
    assert real_rows(np.array([7, 8, 9, 10])) == 4
    assert real_rows(np.array([7])) == 1
    assert real_rows(np.array([7, 7])) == 1


def test_metric_selection_follows_workloads_and_moves():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                            {"name": "b"}],
             "per_layer": [{"name": "la", "moves": "a", "workloads": ["x"]},
                           {"name": "lb", "moves": "b"},
                           {"name": "lc", "moves": "a"}]}
    names = lambda ms: [m["name"] for m in ms]  # noqa: E731
    assert names(harness.cell_metrics(bench, "x", False)) == ["a", "b"]
    assert names(harness.cell_metrics(bench, "y", False)) == ["b"]
    assert names(harness.cell_metrics(bench, "x", True)) == ["la", "lb", "lc"]
    assert names(harness.cell_metrics(bench, "y", True)) == ["lb"]


def test_schedule_gives_every_seed_the_same_work():
    mix = harness.load_json(os.path.join(BENCH, "traffic",
                                         "prod-steady.json"))
    a = traffic.schedule(mix, 50.0, 20.0, 1)
    b = traffic.schedule(mix, 50.0, 20.0, 2**31 + 5)
    assert a.n == b.n == 1000
    assert np.array_equal(a.sizes, b.sizes)
    assert np.array_equal(a.times, b.times)
    assert not np.array_equal(a.offsets, b.offsets)    # the data differs
    assert a.times[0] == 0.0 and a.times[-1] < 20.0
    assert np.all(np.diff(a.times) > 0)
    assert np.all(a.offsets + a.sizes <= mix["pool_rows"])
    c = traffic.schedule(mix, 50.0, 20.0, 1)
    assert np.array_equal(a.times, c.times)
    assert np.array_equal(a.offsets, c.offsets)


@pytest.mark.parametrize("bucket", [1, 8, 64])
def test_costs_flops_within_a_factor_of_xla(bucket):
    """XLA also counts the ReLUs, the padding of the dot interaction and
    the sums it fuses; the model's own operations are within 1.5x of its
    count at the smoke size."""
    import jax
    from repro.models import recsys
    cfg = harness.load_json(SMOKE)
    rc = DLRM.rec_config(cfg)
    params = jax.eval_shape(lambda k: recsys.init(k, rc),
                            jax.random.PRNGKey(0))
    batch = {"dense": jax.ShapeDtypeStruct((bucket, rc.n_dense), np.float32),
             "sparse": jax.ShapeDtypeStruct(
                 (bucket, rc.n_tables, rc.hotness), np.int32)}
    ca = jax.jit(recsys.forward, static_argnums=1).lower(
        params, rc, batch).compile().cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    ratio = ca["flops"] / (bucket * DLRM.flops_per_item(cfg))
    assert 1 / 1.5 <= ratio <= 1.5


def test_costs_match_the_published_widths():
    rmc1 = harness.load_json(os.path.join(BENCH, "configs", "dlrm-rmc1.json"))
    # no id named twice: 64 rows x 80 distinct ids in each of 10 tables
    every = {"sparse": np.arange(64 * 10 * 80).reshape(64, 10, 80)}
    assert DLRM.distinct_rows(every["sparse"]) == 64 * 10 * 80
    assert DLRM.call_bytes(rmc1, 64, every) == pytest.approx(7.39e6,
                                                              rel=0.01)
    assert DLRM.weight_bytes(rmc1) == 567428    # 141,857 float32 weights
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    # both are bound by bytes at bucket 64
    assert costs.least_seconds(DLRM, rmc1, 64, every, peak) == \
        pytest.approx(DLRM.call_bytes(rmc1, 64, every) / 819e9)
    # ids named twice are read once: the heavy head of the id law
    half = {"sparse": every["sparse"] // 2}
    assert DLRM.distinct_rows(half["sparse"]) == 64 * 10 * 80 // 2
    assert DLRM.call_bytes(rmc1, 64, half) < DLRM.call_bytes(
        rmc1, 64, every)


def test_distinct_rows_counts_each_table_apart():
    ids = np.array([[[1, 1, 2], [1, 3, 3]],
                    [[2, 2, 2], [1, 1, 1]]])      # (rows, tables, hotness)
    # table 0 names 1 and 2, table 1 names 1 and 3
    assert DLRM.distinct_rows(ids) == 4


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_reference_matches_the_served_model(seed):
    import jax
    from repro.models import recsys
    cfg = harness.load_json(SMOKE)
    rc = DLRM.rec_config(cfg)
    params = recsys.init(jax.random.PRNGKey(seed), rc)
    w = DLRM.init_weights(seed, cfg)
    # the same values from the seed, to the rounding of the last bit
    # (XLA may fold the init's scale into the normal draw differently)
    same = dict(rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.stack(w["tables"]), params["tables"],
                               **same)
    for (wt, b), p in zip(w["bottom"] + w["top"],
                          params["dense_mlp"] + params["predict"][0]):
        np.testing.assert_allclose(wt, p["w"], **same)
        np.testing.assert_array_equal(b, p["b"])
    pool = DLRM.draw_pool(cfg, 512, traffic.pool_key(seed))
    got = np.asarray(recsys.forward(params, rc, pool))
    want = reference.logits(DLRM.forward, w, pool, block=128)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    low = reference.logits(DLRM.forward, w, pool, block=128,
                           store="bfloat16", precision="default")
    assert np.max(np.abs(low - want)) > 100 * np.max(np.abs(got - want))


# recorded from the tree before DLRM's code moved into models/dlrm.py: the
# smoke configuration, 512 pool rows, seed 2**31 + 1234; sha256 of the
# arrays' bytes
PINNED_DLRM = {
    "dense":
        "f53880db0fb4f3447a272e7ff28528b9c9cda5223360924408ba786a54c29fd4",
    "sparse":
        "39c64536d71f1c0461693c83a8dbc3f881ac5dd99c31f00e9ed880bdd0298c45",
    "weights":
        "2a364fe85af5026af52a4d0bf35ef021a15d5de9368618364c53c74e91ac9e6e",
    "logits":
        "a88fa6038a6eba1e6eaa89a471ecf10da8a05d96146adecc19fc556b158b0536",
    "first": ["0x1.35b5260000000p-2", "0x1.c1932a0000000p-1",
              "0x1.d66eb80000000p-1", "0x1.8319520000000p-2"],
    "low":
        "86c9d521c1ffbcfad42854214824977d09ec99d266f32d2e799aa31206533eaa",
}


def test_the_dlrm_module_draws_and_computes_what_the_benchmark_did():
    """The pool, the reference weights and logits (at the reference's and
    at the control's numerics) and the costs, bit for bit as before the
    model's code moved behind ``models/<model>.py``."""
    import hashlib

    import jax

    def sha(a):
        return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()
    cfg = harness.load_json(SMOKE)
    seed = 2**31 + 1234
    pool = DLRM.draw_pool(cfg, 512, traffic.pool_key(seed))
    assert sorted(pool) == ["dense", "sparse"]
    assert pool["dense"].shape == (512, 16)
    assert pool["dense"].dtype == np.float32
    assert pool["sparse"].shape == (512, 4, 4)
    assert pool["sparse"].dtype == np.int32
    assert sha(pool["dense"]) == PINNED_DLRM["dense"]
    assert sha(pool["sparse"]) == PINNED_DLRM["sparse"]
    w = DLRM.init_weights(seed, cfg)
    assert sha(np.concatenate([np.ravel(np.asarray(x)) for x in
                               jax.tree_util.tree_leaves(w)])) == \
        PINNED_DLRM["weights"]
    got = reference.logits(DLRM.forward, w, pool, block=128)
    assert sha(got) == PINNED_DLRM["logits"]
    assert [float(x).hex() for x in got[:4]] == PINNED_DLRM["first"]
    low = reference.logits(DLRM.forward, w, pool, block=128,
                           store="bfloat16", precision="default")
    assert sha(low) == PINNED_DLRM["low"]
    assert DLRM.flops_per_item(cfg) == 2969
    rows = {k: v[:50] for k, v in pool.items()}
    assert DLRM.call_bytes(cfg, 64, rows) == 28708
    rows = {k: v[100:103] for k, v in pool.items()}
    assert DLRM.call_bytes(cfg, 4, rows) == 7060
    rmc1 = harness.load_json(os.path.join(BENCH, "configs", "dlrm-rmc1.json"))
    assert DLRM.flops_per_item(rmc1) == 316001


def test_trace_union_labels_and_pairs():
    import trace
    iv = trace._union(np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0],
                                [7.0, 8.0]]))
    assert iv.tolist() == [[0.0, 3.0], [5.0, 8.0]]
    host = [trace.Span("feeder_release", 3.0, 4.0, {}),
            trace.Span("apply_fn", 3.5, 5.5, {"call": 7}),
            trace.Span("apply_fn", 9.0, 9.5, {"call": 8})]
    label = trace._labeller(host)
    assert label(3.0, 5.0) == "apply_fn"          # 1.5 of apply_fn, 1 of feed
    assert label(3.0, 3.6) == "feeder_release"
    assert label(8.0, 8.9) == "none"
    summary = trace.TraceSummary(
        (0.0, 10.0), 6.0, 1, {"fusion": 4.0, "copy": 2.0},
        [trace.Span("jit_forward(1)", 2.0, 3.0, {}),     # dispatched earlier
         trace.Span("jit_forward(1)", 5.0, 8.0, {}),
         trace.Span("jit_forward(1)", 9.2, 9.8, {})],
        host, [(3.0, 5.0, "apply_fn"), (8.0, 9.0, "none"),
               (9.8, 10.0, "none")])
    pairs = summary.paired_forward()
    assert [(s.stats["call"], e.start_ns) for s, e in pairs] == [(7, 5.0),
                                                                (8, 9.2)]
    assert summary.idle_by_label() == [["apply_fn (1 gaps)", 2.0e-9],
                                       ["none (2 gaps)", pytest.approx(1.2e-9)]]
    assert summary.top_ops(1) == [["fusion", 4.0e-9]]


def test_trace_reduces_a_recorded_chip_trace():
    """A quarter second of ``rmc1`` served on one TPU v5e at 60 queries/s,
    traced by the harness: its reduction, pinned."""
    import trace
    s = trace.reduce(os.path.join(BENCH, "tests", "fixtures",
                                  "rmc1-steady.xplane.pb"))
    assert s.n_devices == 1
    assert s.busy_s == pytest.approx(0.154410864, rel=1e-9)
    assert s.window_s == pytest.approx(0.259616373, rel=1e-9)
    # busy and idle tile the window
    idle = sum(b - a for a, b, _ in s.gaps) * 1e-9
    assert idle + s.busy_s == pytest.approx(s.window_s, rel=1e-9)
    assert len(s.forward) == 82
    pairs = s.paired_forward()
    assert len(pairs) == 80
    assert all(e.start_ns >= c.start_ns for c, e in pairs)
    assert [c.stats["call"] for c, _ in pairs] == list(range(99, 179))
    assert {c.stats["bucket"] for c, _ in pairs} <= {1, 2, 4, 8, 16, 32, 64}
    # the embedding gather of a 64-item call leads the device's time
    top = s.top_ops(1)[0]
    assert "f32[51200,32]" in top[0] and "params__tables__" in top[0]
    assert top[1] == pytest.approx(0.148340533, rel=1e-6)
    labels = {name.split(" (")[0] for name, _ in s.idle_by_label()}
    assert labels == {"apply_fn", "none", "feeder_release"}
