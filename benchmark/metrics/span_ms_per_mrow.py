"""Milliseconds of a job span per million rows delivered in the window.
A job that never opened the span (an older program) gives nothing."""


def read(ctx, span):
    rows = ctx.rows()
    if not rows or span not in ctx.snap1["stages"]:
        return None
    return ctx.span_seconds(span) * 1e3 / (rows / 1e6)
