"""Milliseconds of a job span per batch handed to the job in the window."""


def read(ctx, span):
    n = ctx.counter("fusion.batches") or ctx.batches
    if not n or span not in ctx.snap1["stages"]:
        return None
    return ctx.span_seconds(span) * 1e3 / n
