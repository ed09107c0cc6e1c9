"""The readers of PR 24's per-layer metrics, each on a hand-made
``Context``: what it reads, and that it gives nothing (and does not
raise) on a program that lacks the span, histogram or counter."""

import os

import pytest
from conftest import BENCH

from bmlib.cell import load_json, load_module
from bmlib.layers import Context

from flink_siddhi_tpu.telemetry import LatencyHistogram


def _ctx(snap0=None, snap1=None, trace=None):
    empty = {"counters": {}, "stages": {}, "histograms": {}, "t": 10.0}
    snap0 = {**empty, **(snap0 or {})}
    snap1 = {**empty, "t": 13.0, **(snap1 or {})}
    return Context(cell={}, cfg={}, job=None, snap0=snap0, snap1=snap1,
                   batches=0, batch=0, trace=trace, source=None, sink=None,
                   device={})


def _read(reader, ctx, **args):
    return load_module("metrics", reader, BENCH).read(ctx, **args)


def test_hist_window_mean_is_sum_over_samples_between_the_snapshots():
    h = LatencyHistogram()
    h.record_many([500_000] * 4, 10)  # before the window: 500 ms
    s0 = {"histograms": {"leg.device": h.snapshot()}}
    h.record_many([100_000, 300_000], [30, 10])  # in it: weighted 150 ms
    s1 = {"histograms": {"leg.device": h.snapshot()}}
    assert _read("hist_window_mean", _ctx(s0, s1), hist="leg.device") == 150.0
    assert _read("hist_window_mean", _ctx(s0, s0), hist="leg.device") is None
    assert _read("hist_window_mean", _ctx(), hist="leg.device") is None


def test_hist_window_percentile_sees_the_window_only():
    h = LatencyHistogram()
    h.record_many([900_000] * 100)
    s0 = {"histograms": {"drain.staleness": h.snapshot()}}
    h.record_many(range(1_000, 101_000, 1_000))  # 1..100 ms
    s1 = {"histograms": {"drain.staleness": h.snapshot()}}
    got = _read("hist_window_percentile", _ctx(s0, s1),
                hist="drain.staleness", q=95)
    assert got == pytest.approx(95.0, rel=0.01)
    # the parent's snapshots carry no buckets: nothing, and no error
    old = {"histograms": {"drain.staleness": {
        k: v for k, v in h.snapshot().items() if k != "buckets"}}}
    assert _read("hist_window_percentile", _ctx(old, old),
                 hist="drain.staleness", q=95) is None
    assert _read("hist_window_percentile", _ctx(),
                 hist="drain.staleness", q=95) is None
    assert _read("hist_window_percentile", _ctx(s1, s1),
                 hist="drain.staleness", q=95) is None


def test_span_share_and_its_complement():
    s0 = {"stages": {"drain": {"seconds": 1.0}, "dispatch": {"seconds": 2.0}}}
    s1 = {"stages": {"drain": {"seconds": 2.5}, "dispatch": {"seconds": 2.9},
                     "backpressure_wait": {"seconds": 0.3}}}
    ctx = _ctx(s0, s1)  # a window of 3 s
    assert _read("span_share", ctx, spans=["backpressure_wait"]) == (
        pytest.approx(10.0))
    spans = ["drain", "dispatch", "backpressure_wait", "route"]
    assert _read("span_share", ctx, spans=spans) == pytest.approx(90.0)
    assert _read("span_share", ctx, spans=spans, complement=True) == (
        pytest.approx(10.0))
    # a span that never opened is 0% of the time; no spans at all: nothing
    assert _read("span_share", ctx, spans=["route"]) == 0.0
    assert _read("span_share", _ctx(), spans=spans) is None


def test_module_share_is_device_time_of_the_named_programs():
    trace = {"window_s": 2.0, "modules": {
        "jit_seg_scan": [1.7, 20], "jit_pack": [0.2, 9],
        "jit_init_acc": [0.1, 9]}}
    ctx = _ctx(trace=trace)
    assert _read("module_share", ctx,
                 modules=["jit_pack", "jit_init_acc"]) == pytest.approx(15.0)
    assert _read("module_share", ctx, modules=["jit_flush"]) is None
    assert _read("module_share", _ctx(), modules=["jit_pack"]) is None


@pytest.mark.parametrize("name", sorted(
    f[:-5] for f in os.listdir(os.path.join(BENCH, "metrics"))
    if f.endswith(".json")))
def test_every_metric_file_reads_nothing_from_an_empty_program(name):
    """A program without the metric's span, histogram or counter (a
    parent commit) gives None or a number, never an error."""
    spec = load_json("metrics", name, BENCH)
    reader = load_module("metrics", spec["reader"], BENCH)
    if spec["reader"] in ("generator_timing", "gauge_skew", "memory_peak",
                          "hist_percentile"):
        pytest.skip("reads the live source, job or device, not snapshots")
    ctx = _ctx()
    ctx.sink = type("S", (), {"t": [], "rows": []})()
    value = reader.read(ctx, **spec.get("args", {}))
    assert value is None or isinstance(value, float)
