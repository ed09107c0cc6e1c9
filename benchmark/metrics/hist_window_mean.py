"""Mean of a job histogram over the window, in ms: the sum recorded
between the two snapshots over the samples recorded between them (a
weighted histogram's samples are its weights: a latency leg is weighted
by events). Read from the snapshots' exact ``sum`` (microseconds): the
rounded ``mean_ms`` times a count of 1e8 would be off by the size of a
short leg. A program whose snapshots carry no sum gives nothing."""


def read(ctx, hist):
    before = ctx.snap0["histograms"].get(hist) or {}
    after = ctx.snap1["histograms"].get(hist) or {}
    n = after.get("count", 0) - before.get("count", 0)
    if "sum" not in after or not n:
        return None
    return (after["sum"] - before.get("sum", 0)) / n / 1e3
