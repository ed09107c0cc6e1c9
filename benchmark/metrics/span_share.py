"""Seconds of the named job spans over the window's seconds, in percent.
The spans are spans of the run loop that do not overlap one another, so
their sum is a share of that thread's time. ``complement``: 100 minus
it, the run loop's time that none of them covers. A span that never
opened reads 0; a job that keeps no spans at all gives nothing."""


def read(ctx, spans, complement=False):
    window = ctx.snap1["t"] - ctx.snap0["t"]
    if window <= 0 or not ctx.snap1["stages"]:
        return None
    share = 100.0 * sum(ctx.span_seconds(s) for s in spans) / window
    return 100.0 - share if complement else share
