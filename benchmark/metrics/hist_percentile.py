"""A percentile of a job histogram, in ms (since the job started: the
registry keeps no window)."""


def read(ctx, hist, q):
    h = ctx.job.telemetry.get_histogram(hist)
    if h is None or not h.count:
        return None
    return h.percentile_ms(q)
