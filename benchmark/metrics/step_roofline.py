"""The step's share of its memory roofline, in percent.

Least bytes one batch makes the step move, from the configuration's
shapes: every event's referenced columns in (``step_bytes.per_event_in``)
and every row out (``step_bytes.per_row_out``, times the rows per event
the window delivered). The step is a scan, not a matrix product, so the
bound is HBM bandwidth; the peak comes from ``bmlib/peaks.json`` by
``device_kind`` and an unknown kind is an error."""

import json
import os


def step_bytes(cfg, events, rows):
    b = cfg["step_bytes"]
    return events * b["per_event_in"] + rows * b["per_row_out"]


def read(ctx):
    from bmlib.cell import load_module

    here = os.path.dirname(os.path.abspath(__file__))
    s = load_module(
        "metrics", "step_time", os.path.dirname(here)
    ).step_seconds_per_batch(ctx)
    if s is None:
        return None
    with open(os.path.join(here, "..", "bmlib", "peaks.json")) as f:
        peaks = json.load(f)
    kind = ctx.device["kind"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r}")
    events = ctx.batches * ctx.batch
    rows = ctx.rows()
    if not events:
        return None
    per_batch = step_bytes(ctx.cfg, ctx.batch, rows / events * ctx.batch)
    return 100.0 * per_batch / peaks[kind]["hbm_bytes_per_s"] / s
