"""The ``overlap_share`` reader: each worker's requests in ``dispatch``
order, the share picked up before the previous one's ``ready``.  It reads
0 on the recorded serial overload run, about 100% on a log whose pick-ups
run ahead of the previous request's completion, and nothing where the
program keeps no request log."""
import json
import os
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(REPO, "src")]

import harness  # noqa: E402
import program  # noqa: E402
import traffic  # noqa: E402

FIXTURES = os.path.join(BENCH, "tests", "fixtures")
RMC1 = os.path.join(BENCH, "configs", "dlrm-rmc1.json")


def read(run):
    return harness.load_reader(BENCH, "overlap_share")(run)


class _Log:
    def __init__(self, cols):
        self.cols = {k: np.asarray(v) for k, v in cols.items()}

    def rows(self, start=0):
        return dict(self.cols), len(next(iter(self.cols.values())))


def _run(origin=0.0, seconds=1e9):
    sched = traffic.Schedule(np.zeros(1), np.ones(1, np.int64),
                             np.zeros(1, int))
    w = harness.Window(seconds, sched, "abandon", [], [], origin, 0, [])
    w.profile = None
    return harness.Run(harness.load_json(RMC1), {}, {}, 0.0, w, {})


def _serve(monkeypatch, cols):
    fake = types.SimpleNamespace(recent_logs=lambda: [_Log(cols)])
    monkeypatch.setattr(program, "_recorder", lambda: fake)


def test_the_recorded_serial_overload_run_reads_zero(monkeypatch):
    """One worker that waited for each result before it took the next."""
    with open(os.path.join(FIXTURES, "rmc1-overload.json")) as f:
        rec = json.load(f)
    _serve(monkeypatch, rec["requests"])
    assert read(_run(rec["origin"], rec["seconds"])) == 0.0


def test_pickups_before_the_previous_ready_count(monkeypatch):
    """Two workers, each picking up a request every 0.3 ms, each ready
    0.7 ms after its pick-up: every request after a worker's first was
    picked up while the previous one was in flight, but one that waited
    for it (worker 1's last)."""
    n = 10
    w = np.arange(2 * n) % 2
    k = np.arange(2 * n) // 2
    pickup = k * 0.3e-3 + w * 0.1e-3
    ready = pickup + 0.7e-3
    pickup[-1] = ready[-3] + 1e-6
    cols = {"rid": np.arange(2 * n), "worker": w, "pickup": pickup,
            "pad": pickup + 0.05e-3, "dispatch": pickup + 0.2e-3,
            "ready": ready, "done": ready + 0.01e-3}
    _serve(monkeypatch, cols)
    assert read(_run()) == pytest.approx(100.0 * 17 / 18)
    cols["pickup"] = cols["ready"] + 1.0          # each waits: serial
    cols["dispatch"] = cols["pickup"] + 0.2e-3
    assert read(_run()) == 0.0


def test_a_program_without_a_request_log_reads_nothing(monkeypatch):
    monkeypatch.setattr(program, "_recorder", lambda: None)
    assert read(_run()) is None
    _serve(monkeypatch, {"rid": [], "worker": [], "pickup": [],
                         "dispatch": [], "ready": []})
    assert read(_run()) is None
