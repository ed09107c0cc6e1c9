"""The reduction from a profiler trace to busy time, operation totals and
idle gaps: on a hand-made trace whose answers are known, and on a small
trace recorded on the chip (``data/trace_small.json``, the first events
of a traced ``pattern3.replay`` run)."""

import json
import os

import pytest

from bmlib import tracered

HERE = os.path.dirname(os.path.abspath(__file__))


def test_hand_made_trace():
    ev = {
        "devices": {
            "/device:TPU:0": {
                # a while that spans its body's operations, then a gap
                "ops": [("while.1", 0, 10), ("fusion.2", 5, 5),
                        ("all-reduce.3", 30, 10)],
                "modules": [("jit_step(1)", 0, 10), ("jit_step(1)", 30, 10),
                            ("jit_pack(2)", 41, 1)],
            },
            "/device:TPU:1": {
                "ops": [("while.1", 0, 20), ("fusion.2", 20, 20)],
                "modules": [],
            },
        },
        "host": [("bench.run_cycle", 12, 20), ("bench.sink", 14, 4),
                 ("bench.poll", 20, 2)],
    }
    out = tracered.reduce(ev)
    assert out["devices"] == 2
    assert out["window_s"] == pytest.approx(40e-9)
    # device 0 is busy 20 ns (the union, not the sum), device 1 40 ns
    assert out["busy_s"] == pytest.approx(30e-9)
    assert out["modules"]["jit_step(1)"] == [pytest.approx(20e-9), 2]
    assert out["collective_s"] == pytest.approx(10e-9)
    ops = dict(out["breakdown"]["device_ops"])
    assert ops["while.1"] == pytest.approx(10e-9)
    gaps = dict(out["breakdown"]["idle_gaps"])
    # the gap 10..30: run_cycle covers 12..30, of which sink 4 and poll 2
    assert gaps["bench.sink"] == pytest.approx(4e-9)
    assert gaps["bench.poll"] == pytest.approx(2e-9)
    assert gaps["bench.run_cycle"] == pytest.approx(12e-9)
    assert gaps["outside_bench.run_cycle"] == pytest.approx(2e-9)
    assert sum(gaps.values()) == pytest.approx(20e-9)


def test_no_device_plane_gives_nothing():
    assert tracered.reduce({"devices": {}, "host": []}) is None


def test_recorded_trace():
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path, encoding="utf-8") as f:
        ev = json.load(f)
    out = tracered.reduce(ev)
    assert out["devices"] >= 1
    assert 0 < out["busy_s"] <= out["window_s"]
    assert out["modules"], "the step program's executions are on the trace"
    assert len(out["breakdown"]["device_ops"]) <= 10
    assert all(v >= 0 for _, v in out["breakdown"]["idle_gaps"])
    idle = out["window_s"] - out["busy_s"]
    assert sum(v for _, v in out["breakdown"]["idle_gaps"]) == pytest.approx(
        idle, rel=1e-6, abs=1e-12)
