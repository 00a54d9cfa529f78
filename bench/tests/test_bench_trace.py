"""The reduction from a profiler trace to device numbers: on a hand-built
trace whose answers are worked out below, and on one engine step recorded
on a TPU v5e."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"


def hand_trace():
    """Harness spans 0-100 ns; device: a loop 10-60 holding two ops 10-30
    and 40-50, a lone op 70-80, and an op 95-120 cut by the stretch.
    Busy: 10-60, 70-80, 95-100 = 65 of 100.  Idle gaps: 0-10 (in
    add_request), 60-70 and 80-95 (in engine.step)."""
    host = [("add_request", 0, 12), ("engine.step", 12, 80),
            ("wait_due", 92, 8)]
    ops = [("while.1 while", 10, 50), ("fusion.2 fusion", 10, 20),
           ("copy.3 copy", 40, 10), ("fusion.2 fusion", 70, 10),
           ("fusion.4 fusion", 95, 25)]
    return {"host": host, "devices": [ops]}


def test_hand_built_trace():
    r = TR.reduce(hand_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(65e-9)
    ops = dict(r["device_ops"])
    # self time: the loop keeps 50 - 20 - 10; fusion.2 20 + 10
    assert ops["while.1 while"] == pytest.approx(20e-9)
    assert ops["fusion.2 fusion"] == pytest.approx(30e-9)
    assert ops["copy.3 copy"] == pytest.approx(10e-9)
    assert ops["fusion.4 fusion"] == pytest.approx(5e-9)
    assert r["device_ops"][0][0] == "fusion.2 fusion"
    assert r["idle_gaps"] == [["engine.step", pytest.approx(15e-9)],
                              ["add_request", pytest.approx(10e-9)],
                              ["engine.step", pytest.approx(10e-9)]]


def test_no_device_ops_reads_nothing():
    t = hand_trace()
    assert TR.reduce({"host": t["host"], "devices": []}) is None
    assert TR.reduce({"host": [], "devices": t["devices"]}) is None


def test_short_names_of_hlo_text():
    hlo = ("%fusion.292 = bf16[16,1,4096]{2,0,1:T(8,128)(2,1)} "
           "fusion(bf16[16,1,4096] %p0), kind=kLoop")
    assert TR.short_name(hlo) == "fusion.292 fusion"
    assert TR.short_name("%copy-done.1 = bf16[4]{0} copy-done((bf16[4]) "
                         "%copy-start.1)") == "copy-done.1 copy-done"


def test_recorded_v5e_step():
    """One engine step recorded on the chip: the busy time is the union of
    the operation intervals (checked against a 1 us grid), self times
    never exceed it, and idle gaps fill the rest of the stretch."""
    ev = json.loads((DATA / "v5e_step_profile.json").read_text())
    r = TR.reduce(ev)
    lo = min(s for _, s, _ in ev["host"])
    hi = max(s + d for _, s, d in ev["host"])
    grid = np.zeros(int((hi - lo) // 1000) + 1, bool)
    for _, s, d in ev["devices"][0]:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            grid[int((a - lo) // 1000):int(np.ceil((b - lo) / 1000))] = True
    assert r["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=2e-3)
    assert 0.9 < r["busy_s"] / r["window_s"] <= 1.0
    assert sum(v for _, v in r["device_ops"]) <= r["busy_s"] * (1 + 1e-9)
    assert all(v > 0 for _, v in r["device_ops"])
    assert len(r["device_ops"]) == TR.TOP
    assert sum(v for _, v in r["idle_gaps"]) <= r["window_s"] - r["busy_s"] + 1e-12
    assert {name for name, _ in r["idle_gaps"]} <= set(TR.HOST_SPANS)
