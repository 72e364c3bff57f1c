"""DIEN in the benchmark, on the CPU: its plain reference against the
program (weights, logits, the mask), its costs by hand, a run of its smoke
configuration through ``run_cell`` with the real ``models/dien.py`` and
one planted fault, and the recurrence readers."""
import os
import sys
import time
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(REPO, "src")]

import harness  # noqa: E402
import program  # noqa: E402
import reference  # noqa: E402
import trace  # noqa: E402
import traffic  # noqa: E402

FIXTURES = os.path.join(BENCH, "tests", "fixtures")
DLRM = harness.load_model(BENCH, "dlrm")
DIEN = harness.load_model(BENCH, "dien")
DIEN_SMOKE = os.path.join(FIXTURES, "dien-smoke.json")
SECONDS = 1.5
SEED = 2**31 + 99


def read(name, run):
    return harness.load_reader(BENCH, name)(run)


# ------------------------------------------- the reference and the costs


def dien_pool(cfg, rows, seed):
    """A pool whose row 0 has one valid behaviour and row 1 all of them;
    every padded position keeps ids that name real rows."""
    pool = {k: np.array(v) for k, v in
            DIEN.draw_pool(cfg, rows, traffic.pool_key(seed)).items()}
    pool["hist_mask"][0] = np.arange(cfg["seq_len"]) < 1
    pool["hist_mask"][1] = True
    assert (pool["history"] >= 0).all() and \
        (pool["history"] < cfg["item_vocab"]).all()
    return pool


def test_the_dien_fixture_is_the_registrys_smoke_config():
    import dataclasses

    from repro import configs
    rc = DIEN.rec_config(harness.load_json(DIEN_SMOKE))
    assert rc == dataclasses.replace(configs.get("dien").smoke_config,
                                     name="dien-smoke")


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_dien_reference_matches_the_program(seed):
    """The program's forward (``recsys.forward`` at ``highest``, on the
    weights ``recsys.init`` draws) against the plain reference written from
    the paper, on weights it re-derives from the seed: within 1e-5 of the
    logits' scale, with histories of one behaviour and of all of them."""
    import jax
    from repro.models import recsys
    cfg = harness.load_json(DIEN_SMOKE)
    rc = DIEN.rec_config(cfg)
    params = recsys.init(jax.random.PRNGKey(seed), rc)
    w = DIEN.init_weights(seed, cfg)
    same = dict(rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(np.stack(w["tables"]), params["tables"],
                               **same)
    np.testing.assert_allclose(np.stack(w["items"]), params["item_tables"],
                               **same)
    for name in ("gru", "augru"):
        cell = w[name]
        np.testing.assert_allclose(
            np.concatenate([cell["W"][g] for g in "ruc"], 1),
            params[name]["wi"]["w"], **same)
        np.testing.assert_allclose(
            np.concatenate([cell["U"][g] for g in "ruc"], 1),
            params[name]["wh"]["w"], **same)
    np.testing.assert_allclose(w["att"], params["att"]["w"], **same)
    pool = dien_pool(cfg, 512, seed)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(recsys.forward(params, rc, pool))
    want = reference.logits(DIEN.forward, w, pool, block=128)
    scale = np.sqrt(np.mean(np.square(want)))
    assert np.max(np.abs(got - want)) < 1e-5 * scale
    # one step below the configuration's numerics is far outside
    high = reference.logits(DIEN.forward, w, pool, block=128,
                            precision="high")
    assert np.max(np.abs(high - want)) > 1e-5 * scale


def test_dien_masks_the_padded_behaviours():
    """Ids at padded positions name real rows and change nothing, in the
    program or the reference; the same ids counted as valid change the
    answer."""
    import jax
    from repro.models import recsys
    cfg = harness.load_json(DIEN_SMOKE)
    rc = DIEN.rec_config(cfg)
    params = recsys.init(jax.random.PRNGKey(5), rc)
    w = DIEN.init_weights(5, cfg)
    pool = dien_pool(cfg, 128, 5)
    moved = dict(pool, history=np.where(pool["hist_mask"][..., None],
                                        pool["history"],
                                        (pool["history"] + 17)
                                        % cfg["item_vocab"]))
    assert (moved["history"] != pool["history"]).any()
    unmasked = dict(pool, hist_mask=np.ones_like(pool["hist_mask"]))

    def program(batch):
        with jax.default_matmul_precision("highest"):
            return np.asarray(recsys.forward(params, rc, batch))

    def ref(batch):
        return reference.logits(DIEN.forward, w, batch, block=128)
    for fn in (program, ref):
        base = fn(pool)
        np.testing.assert_array_equal(fn(moved), base)
        assert abs(fn(unmasked)[0] - base[0]) > 1e-3 * np.abs(base).max()
        np.testing.assert_array_equal(fn(unmasked)[1], base[1])


def test_dien_costs_by_hand_at_the_smoke_size():
    """H = 8, behaviours 2 x 8 = 16 wide, T = 8, four 8-wide fields, head
    56 -> 16 -> 16 -> 1."""
    cfg = harness.load_json(DIEN_SMOKE)
    # per step: GRU 2*16*24 + 24 and 2*8*24; AUGRU 2*8*24 + 24 and 2*8*24
    assert DIEN.recurrence_flops_per_item(cfg) == 8 * (792 + 384 + 408 + 384)
    # W e_a 2*8*16, scores 2*8*8; head 2*56*16 + 16, 2*16*16 + 16, 2*16 + 1
    assert DIEN.flops_per_item(cfg) == 15744 + (256 + 128) + (1808 + 528 + 33)
    # GRU 16*24 + 24 + 8*24, AUGRU 8*24 + 24 + 8*24, W 8*16, head 1201
    assert DIEN.weight_bytes(cfg) == 4 * (600 + 408 + 128 + 1201)
    # 4 rows: behaviours in 8*16, states out and back 2*8*8, scores 8,
    # final state 8; both cells once
    assert DIEN.recurrence_bytes(cfg, 4) == 4 * (4 * (128 + 128 + 8 + 8)
                                                 + 1008)
    rows = {"sparse": np.zeros((2, 4, 1), np.int32),       # 4 rows, 1 each
            "history": np.stack([np.ones((2, 8)), np.full((2, 8), 2)],
                                -1).astype(np.int32),      # item 1, cat 2
            "target": np.array([[1, 3], [1, 3]], np.int32),  # item 1, cat 3
            "hist_mask": np.ones((2, 8), bool)}
    assert DIEN.distinct_rows(rows) == 4 + 1 + 2
    # per row: 4 field ids, 16 history ids, 2 target ids, 1 logit (int32 /
    # float32) and 8 mask bytes
    assert DIEN.call_bytes(cfg, 4, rows) == 4 * 8 * 7 + 4 * (4 * 23 + 8) \
        + DIEN.weight_bytes(cfg)
    full = harness.load_json(os.path.join(BENCH, "configs", "dien.json"))
    assert DIEN.recurrence_flops_per_item(full) == 3_132_000
    assert DIEN.flops_per_item(full) == 3_132_000 + 9_792 + 118_841


# ------------------------------------------------------ the live path


def run(name: str, bench: dict) -> dict:
    cell = harness.find_cell(bench, name, REPO)
    cell.mix = dict(cell.mix, pool_rows=4096)
    return harness.run_cell(cell, seed=SEED, seconds=SECONDS, traced=False,
                            t_start=time.monotonic(), peak={})


def dien_bench() -> dict:
    """``BENCHMARK.json`` with DIEN's smoke configuration and an overload
    cell of it, served by the benchmark's own ``models/dien.py``."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    bench["configs"].append({"name": "dien-smoke",
                             "file": os.path.relpath(DIEN_SMOKE, REPO)})
    bench["workloads"].append({"name": "dien-smoke-overload",
                               "traffic": "prod-overload",
                               "config": "dien-smoke", "chips": 1})
    for m in bench["end_to_end"]:
        if m["name"] == "items_per_s":
            m["workloads"].append("dien-smoke-overload")
    return bench


def unmasked(real):
    """The program's DIEN, built by ``real``, with its attention unmasked:
    every position of every history counted as a behaviour."""
    def build(model, cfg, seed):
        apply_fn, params = real(model, cfg, seed)

        def served(batch):
            return apply_fn(dict(batch,
                                 hist_mask=np.ones_like(batch["hist_mask"])))
        return served, params
    return build


@pytest.mark.parametrize("fault", [None, "unmasked"])
def test_dien_is_served_and_checked_through_the_live_path(monkeypatch,
                                                          fault):
    """DIEN's smoke configuration through ``run_cell`` with the real
    ``models/dien.py``: every answer matches the plain reference; with the
    attention's mask taken away (padded positions hold ids of real rows)
    the run is not correct."""
    if fault:
        monkeypatch.setattr(harness, "recsys_model",
                            unmasked(harness.recsys_model))
    r = run("dien-smoke-overload", dien_bench())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["notes"]["compiles_in_window"] == 0
    assert r["checks"]["unserved_items"]["value"] == 0
    assert set(r["metrics"]) == {"items_per_s", "setup_s"}
    c = r["checks"]["max_gap"]
    if fault:
        assert not r["correct"] and c["value"] > c["limit"]
    else:
        assert r["correct"], r["checks"]
        assert c["value"] < 1e-5


# ------------------------------------------------ the recurrence readers

RECURRENCE = harness._load(BENCH, "metrics", "recurrence_device_ms.mean")


def test_the_recurrence_table_names_both_scans_of_the_dien_forward():
    """Compiled from the served DIEN forward at the smoke size, the table
    holds ops and a while loop of the GRU's and of the AUGRU's scan; the
    DLRM forward has none."""
    cfg = harness.load_json(DIEN_SMOKE)
    pool = DIEN.draw_pool(cfg, 64, traffic.pool_key(3))
    run = harness.Run(cfg, {}, {}, 0.0, None, pool, DIEN)
    hlo = program.forward_lowered(run, [4])[4].compile().as_text()
    for scope in RECURRENCE.SCOPES:
        lines = [line for line in hlo.splitlines()
                 if f"/interaction/{scope}/" in line]
        keys = {RECURRENCE.table(line).get(program._op_key(line))
                for line in lines}
        assert {"loop", "op"} <= keys, scope
    table = RECURRENCE.tables(run, [4])[4]
    assert set(table.values()) == {"loop", "op"}
    dlrm = harness.load_json(os.path.join(FIXTURES, "dlrm-smoke.json"))
    run = harness.Run(dlrm, {}, {}, 0.0, None,
                      DLRM.draw_pool(dlrm, 64, traffic.pool_key(3)), DLRM)
    assert RECURRENCE.tables(run, [4])[4] == {}


_SCANS_HLO = "\n".join([
    '  %fusion.1 = f32[4,8]{1,0} fusion(%a), kind=kLoop, metadata={op_name='
    '"jit(forward)/interaction/interest_extractor/while/body/dot_general"}',
    '  %while.2 = (s32[], f32[4,8]{1,0}) while(%t), condition=%c, body=%b, '
    'metadata={op_name="jit(forward)/interaction/interest_evolution/while"}',
    '  %fusion.3 = f32[4]{0} fusion(%d), metadata={op_name='
    '"jit(forward)/top_mlp/add"}'])


def _window(profile):
    sched = traffic.Schedule(np.zeros(1), np.ones(1, np.int64),
                             np.zeros(1, int))
    w = harness.Window(1e9, sched, "abandon", [], [], 0.0, 0, [])
    w.profile = profile
    return w


def _scans_run(monkeypatch, op_ns, hlo=_SCANS_HLO, model=DIEN):
    """A traced run of two bucket-4 calls, each one execution, whose
    device ops are ``op_ns`` and whose compiled forward is ``hlo``."""
    compiled = types.SimpleNamespace(as_text=lambda: hlo)
    lowered = types.SimpleNamespace(compile=lambda: compiled)
    monkeypatch.setattr(program, "forward_lowered",
                        lambda run, buckets: {b: lowered for b in buckets})
    host = [trace.Span("apply_fn", 10.0 * k, 10.0 * k + 1,
                       {"call": k, "bucket": 4, "rows": 3}) for k in (0, 1)]
    forward = [trace.Span("jit_forward(1)", 10.0 * k + 2, 10.0 * k + 5, {})
               for k in (0, 1)]
    profile = trace.TraceSummary((0.0, 20.0), 6.0, 1, op_ns, forward, host,
                                 [])
    cfg = harness.load_json(os.path.join(BENCH, "configs", "dien.json"))
    peak = harness.load_json(os.path.join(BENCH, "peaks.json"))[
        "TPU v5 lite"]
    return harness.Run(cfg, {}, peak, 0.0, _window(profile), {}, model)


def test_recurrence_readers_count_the_scans_ops_and_not_their_loops(
        monkeypatch):
    """Where the trace shows a scan's body ops, the loop's own event,
    which spans them, is not counted again; the roofline is the scans'
    least time per call over their device time per execution."""
    ops = {"%fusion.1 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %a), kind=kLoop":
           3e6,
           "%while.2 = (s32[], f32[4,8]{1,0}) while((s32[]) %t)": 10e6,
           "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %d)": 5e6}
    run = _scans_run(monkeypatch, ops)
    assert read("recurrence_device_ms.mean", run) == pytest.approx(1.5)
    cfg = run.cfg
    least = max(4 * DIEN.recurrence_flops_per_item(cfg) / 197e12,
                DIEN.recurrence_bytes(cfg, 4) / 819e9)
    assert read("recurrence_roofline", run) == pytest.approx(
        100 * least / 1.5e-3)
    # a trace that shows only the loop: the loop is counted
    del ops["%fusion.1 = f32[4,8]{1,0} fusion(f32[4,8]{1,0} %a), kind=kLoop"]
    run = _scans_run(monkeypatch, ops)
    assert read("recurrence_device_ms.mean", run) == pytest.approx(5.0)


def test_recurrence_readers_read_nothing_without_a_recurrence(monkeypatch):
    ops = {"%fusion.3 = f32[4]{0} fusion(f32[4]{0} %d)": 5e6}
    run = _scans_run(monkeypatch, ops)
    assert read("recurrence_device_ms.mean", run) is None
    assert read("recurrence_roofline", run) is None
    run = _scans_run(monkeypatch, ops, model=DLRM)
    assert read("recurrence_roofline", run) is None
