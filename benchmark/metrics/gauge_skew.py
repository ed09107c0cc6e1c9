"""max / mean of a per-shard gauge (events routed to each shard)."""


def read(ctx, gauge_prefix):
    for name, now in ctx.snap1["gauges"].items():
        if name.startswith(gauge_prefix) and now:
            before = ctx.snap0["gauges"].get(name) or [0] * len(now)
            d = [a - b for a, b in zip(now, before)]
            if sum(d) <= 0:
                d = now
            return max(d) / (sum(d) / len(d))
    return None
