"""A percentile of a job histogram over the window, in ms: the
histogram is rebuilt from the difference of the bucket counts in the
two snapshots. A program whose snapshots carry no buckets gives
nothing."""


def read(ctx, hist, q):
    before = ctx.snap0["histograms"].get(hist) or {}
    after = ctx.snap1["histograms"].get(hist) or {}
    if "buckets" not in after:
        return None
    from flink_siddhi_tpu.telemetry import LatencyHistogram

    window = LatencyHistogram.from_snapshots(after, before)
    if not window.count:
        return None
    return window.percentile(q) / 1e3
