"""Every cell's functions, end to end, at a tiny size on the CPU.

``run_cell`` is all of a run but the look for a chip. The last test breaks
the timed path underneath it and sees ``correct`` come out false."""

import json
import os

import numpy as np
import pytest
from conftest import CELLS, REPO, TINY

from bmlib.cell import run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device",
        "compared"}


def _run(cell, trace=False, **kw):
    lines = []
    out = run_cell(cell, 2_147_483_659, 2.0, trace, overrides=dict(TINY),
                   say=lines.append, **kw)
    return out, lines


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_and_is_correct(cell):
    out, lines = _run(cell, control=True)
    assert set(out) == KEYS and list(out)[-1] == "compared"
    json.dumps(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    want = {
        m["name"] for m in bench["end_to_end"]
        if cell in m.get("workloads", [cell])
    }
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    # the earlier lines: numbers beside limits, the plain rate, the slices
    compared = json.loads(
        next(x for x in lines if x.startswith("[bench] compared"))[16:]
    )
    assert compared["ranges"] > 0 and compared["rows"] > 0
    assert all(v <= lim for v, lim in compared["numbers"].values())
    window = json.loads(
        next(x for x in lines if x.startswith("[bench] window"))[14:]
    )
    assert window["plain_rate"] > 0 and window["slices"] >= 1
    # a rate is all the window's events over all its time, not the median
    if "events_per_s" in out["metrics"]:
        assert out["metrics"]["events_per_s"]["value"] == window["plain_rate"]
    # the control, on the same samples, comes out as not correct
    control = json.loads(
        next(x for x in lines if x.startswith("[bench] control"))[21:]
    )
    assert control["correct"] is False


@pytest.mark.parametrize("cell", CELLS)
def test_traced_run_reports_program_metrics(cell):
    out, _ = _run(cell, trace=True)
    assert set(out) == KEYS  # no device planes on the CPU: no breakdown
    assert out["correct"] is True
    assert cell.endswith("live") or any(
        k.startswith("tape_build_ms_per_batch") for k in out["metrics"])
    if cell.endswith("live"):
        assert {"gen_late_ms.live", "batch_wait_ms.live",
                "drain_staleness_ms.live"} <= set(out["metrics"])
    if cell.startswith("keyed"):
        assert 1.0 <= out["metrics"]["shard_skew_x4"]["value"] < 1.5


def _alter_price(cb_or_rows):
    """A value altered where it is produced."""
    from flink_siddhi_tpu.compiler.output import ColumnBatch

    if isinstance(cb_or_rows, ColumnBatch):
        cols = dict(cb_or_rows.cols)
        k = "price" if "price" in cols else "total"
        cols[k] = cols[k] * (1 + 1e-3)
        return ColumnBatch(cb_or_rows.ts, cols)
    return [
        (t, tuple(v * (1 + 1e-3) if isinstance(v, float) else v for v in r))
        for t, r in cb_or_rows
    ]


def _lose_a_row(cb_or_rows):
    """The last row of every emission never reaches the sink."""
    from flink_siddhi_tpu.compiler.output import ColumnBatch

    if isinstance(cb_or_rows, ColumnBatch):
        return cb_or_rows.take(np.arange(len(cb_or_rows) - 1))
    return cb_or_rows[:-1]


@pytest.mark.parametrize("fault", [_alter_price, _lose_a_row])
@pytest.mark.parametrize("cell", CELLS)
def test_a_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    from flink_siddhi_tpu.runtime.executor import Job

    emit_columns, emit_rows = Job._emit_columns, Job._emit_rows
    monkeypatch.setattr(
        Job, "_emit_columns",
        lambda self, schema, cb, **kw: emit_columns(
            self, schema, fault(cb), **kw),
    )
    monkeypatch.setattr(
        Job, "_emit_rows",
        lambda self, schema, rows, **kw: emit_rows(
            self, schema, fault(rows), **kw),
    )
    out, _ = _run(cell)
    assert out["correct"] is False
