"""The harness end to end on the CPU at a smoke configuration, through
``drive_fleet`` and the live node: ``harness.run_cell``, whose platform
check is ``run.py``'s alone; the control and planted faults; a
configuration, mix, cell and metric added as files alone; and a second
model family added as files alone."""
import copy
import importlib.util
import json
import os
import shutil
import sys
import time

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(REPO, "src")]

import harness  # noqa: E402
import limits  # noqa: E402

SMOKE = "benchmarks/chip/tests/fixtures/dlrm-smoke.json"
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
SECONDS = 1.5
SEED = 2**31 + 99


def smoke_bench() -> dict:
    """``BENCHMARK.json`` with a smoke configuration and its two cells."""
    bench = harness.load_json(os.path.join(REPO, "BENCHMARK.json"))
    bench["configs"].append({"name": "dlrm-smoke", "file": SMOKE})
    for mix in ("prod-steady", "prod-overload"):
        bench["workloads"].append({"name": f"smoke-{mix}", "traffic": mix,
                                   "config": "dlrm-smoke", "chips": 1})
    for m in bench["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("smoke-prod-steady")
        elif m["name"] == "items_per_s":
            m["workloads"].append("smoke-prod-overload")
    return bench


def run(name: str, bench: dict | None = None, repo: str = REPO) -> dict:
    cell = harness.find_cell(bench or smoke_bench(), name, repo)
    cell.mix = dict(cell.mix, pool_rows=4096)
    return harness.run_cell(cell, seed=SEED, seconds=SECONDS, traced=False,
                            t_start=time.monotonic(), peak={})


@pytest.mark.parametrize("name, metrics", [
    ("smoke-prod-steady", {"p50_ms", "setup_s"}),
    ("smoke-prod-overload", {"items_per_s", "setup_s"})])
def test_a_run_serves_and_checks_every_answer(name, metrics):
    r = run(name)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == metrics
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert list(r)[-1] == "checks"
    checks = r["checks"]
    assert checks["max_gap"]["value"] < 1e-5       # f32 on the CPU
    assert checks["unserved_items"]["value"] == 0
    assert r["notes"]["compiles_in_window"] == 0


def test_the_control_in_the_programs_place_is_not_correct(monkeypatch):
    """The reference with its contractions a step below the smoke
    configuration's ``highest`` (``limits.control_model``: ``high``, three
    bfloat16 passes), served through the whole path."""
    monkeypatch.setattr(harness, "recsys_model", limits.control_model)
    r = run("smoke-prod-steady")
    assert not r["correct"]
    assert r["checks"]["max_gap"]["value"] > r["checks"]["max_gap"]["limit"]
    assert r["checks"]["rms_gap"]["value"] > r["checks"]["rms_gap"]["limit"]


def faulty(fault: str):
    """The program's model, built as ``harness.recsys_model`` builds it,
    with ``fault`` planted in its 30th call of more than one row (past the
    warm-up's)."""
    real = harness.recsys_model

    def broken(model, cfg, seed):
        apply_fn, params = real(model, cfg, seed)
        calls = [0]

        def served(batch):       # the 30th call, past the warm-up's
            out = np.asarray(apply_fn(batch))
            if len(out) > 1:
                calls[0] += 1
            if calls[0] == 30 and len(out) > 1:
                calls[0] += 1
                if fault == "altered":
                    out = out.copy()
                    out[0] += 0.5
                elif fault == "reversed":
                    out = out[::-1]
                elif fault == "half":
                    half = len(out) // 2
                    out = np.concatenate([out[:half],
                                          out[:len(out) - half]])
                else:
                    raise RuntimeError("request lost")
            return out
        return served, params
    return broken


@pytest.mark.parametrize("fault, caught", [
    ("altered", "max_gap"), ("reversed", "max_gap"), ("half", "max_gap"),
    ("lost", "failed_queries")])
def test_a_faulty_served_path_is_not_correct(monkeypatch, fault, caught):
    """An answer altered where it is produced; a call's answers handed
    back in the wrong order; half of a call's rows left out, their
    answers copied from the rest; a request whose answer never comes."""
    monkeypatch.setattr(harness, "recsys_model", faulty(fault))
    r = run("smoke-prod-steady")
    assert not r["correct"]
    c = r["checks"][caught]
    assert c["value"] > c["limit"]


def test_new_files_alone_add_a_configuration_mix_cell_and_metric(tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a cell
    and a per-layer metric as new files and entries; the harness finds
    and runs them with no edit to a file that was there."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    cfg = harness.load_json(os.path.join(REPO, SMOKE))
    cfg.update(name="dlrm-tiny", hotness=2, knee_qps=30.0)
    (root / "configs" / "dlrm-tiny.json").write_text(json.dumps(cfg))
    mix = harness.load_json(os.path.join(BENCH, "traffic",
                                         "prod-steady.json"))
    mix.update(load_of_knee=1.0, pool_rows=2048)
    (root / "traffic" / "tiny-steady.json").write_text(json.dumps(mix))
    (root / "metrics" / "calls_per_query.py").write_text(
        "def read(run):\n"
        "    return len(run.window.calls) / run.window.sched.n\n")
    bench = copy.deepcopy(harness.load_json(os.path.join(REPO,
                                                         "BENCHMARK.json")))
    bench["configs"].append({"name": "dlrm-tiny",
                             "file": "benchmarks/chip/configs/dlrm-tiny.json"})
    bench["workloads"].append({"name": "tiny-steady", "config": "dlrm-tiny",
                               "traffic": "tiny-steady", "chips": 1})
    for m in bench["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("tiny-steady")
    bench["per_layer"].append({"name": "calls_per_query", "unit": "calls",
                               "moves": "p50_ms", "layer": "runtime queue",
                               "workloads": ["tiny-steady"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell(bench, "tiny-steady", str(tmp_path))
    assert cell.cfg["hotness"] == 2 and cell.mix["load_of_knee"] == 1.0
    assert [m["name"] for m in cell.per_layer] == ["calls_per_query"]
    r = harness.run_cell(cell, seed=SEED, seconds=SECONDS, traced=False,
                         t_start=time.monotonic(), peak={})
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"p50_ms", "setup_s"}
    assert r["attempted"] == round(30.0 * SECONDS)
    run_, _ = harness.serve_cell(cell, seed=SEED, seconds=SECONDS,
                                 traced=False, t_start=time.monotonic(),
                                 peak={})
    got = harness.read_metrics(cell.root, cell.per_layer, run_)
    assert got["calls_per_query"]["value"] >= 1.0
    assert {p: p.read_bytes() for p in before} == before


def test_new_files_alone_add_a_model_family(tmp_path, monkeypatch):
    """A copy of the benchmark gains a model module (``models/toy_seq.py``,
    the program's DIN smoke configuration, whose inputs are ``sparse``,
    ``history``, ``hist_mask`` and ``target``, no ``dense``), its
    configuration, a mix and a cell as new files and entries: the
    harness serves every pool entry to the program and checks every
    answer with no edit to a file that was there, and an answer altered
    where it is produced is not correct."""
    root = tmp_path / "benchmarks" / "chip"
    shutil.copytree(BENCH, root,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in root.rglob("*") if p.is_file()}
    shutil.copy(os.path.join(FIXTURES, "toy_seq.py"),
                root / "models" / "toy_seq.py")
    shutil.copy(os.path.join(FIXTURES, "din-smoke.json"),
                root / "configs" / "din-smoke.json")
    mix = harness.load_json(os.path.join(BENCH, "traffic",
                                         "prod-steady.json"))
    mix.update(load_of_knee=1.0, pool_rows=2048)
    (root / "traffic" / "toy-steady.json").write_text(json.dumps(mix))
    bench = copy.deepcopy(harness.load_json(os.path.join(REPO,
                                                         "BENCHMARK.json")))
    bench["configs"].append({"name": "din-smoke",
                             "file": "benchmarks/chip/configs/din-smoke.json"})
    bench["workloads"].append({"name": "din-steady", "config": "din-smoke",
                               "traffic": "toy-steady", "chips": 1})
    for m in bench["end_to_end"]:
        if m["name"] == "p50_ms":
            m["workloads"].append("din-steady")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.find_cell(bench, "din-steady", str(tmp_path))
    assert cell.model.rec_config(cell.cfg).interaction == "din"

    seen = []
    real = harness.recsys_model

    def spy(model, cfg, seed):
        apply_fn, params = real(model, cfg, seed)

        def served(batch):
            seen.append(sorted(batch))
            return apply_fn(batch)
        return served, params
    monkeypatch.setattr(harness, "recsys_model", spy)
    r = harness.run_cell(cell, seed=SEED, seconds=SECONDS, traced=False,
                         t_start=time.monotonic(), peak={})
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"p50_ms", "setup_s"}
    assert r["attempted"] == round(30.0 * SECONDS) and r["failed"] == 0
    assert r["checks"]["max_gap"]["value"] < 1e-5
    assert r["checks"]["unserved_items"]["value"] == 0
    assert r["notes"]["compiles_in_window"] == 0
    assert seen and all(keys == ["hist_mask", "history", "sparse", "target"]
                        for keys in seen)
    assert {p: p.read_bytes() for p in before} == before

    monkeypatch.setattr(harness, "recsys_model", faulty("altered"))
    r = harness.run_cell(cell, seed=SEED, seconds=SECONDS, traced=False,
                         t_start=time.monotonic(), peak={})
    assert not r["correct"]
    c = r["checks"]["max_gap"]
    assert c["value"] > c["limit"]


def test_the_entry_point_refuses_a_platform_without_a_tpu(capsys):
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(BENCH, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    rc = mod.main(["--workload", "rmc1-prod-steady", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no tpu" in err
