"""Device milliseconds of the step program per batch.

The step program is the module that takes most device time in the trace
(one execution is one fused segment, or one batch where nothing is
fused). Batches per execution come from the job's own counters."""


def step_seconds_per_batch(ctx):
    if not ctx.trace or not ctx.trace["modules"]:
        return None
    total, count = max(ctx.trace["modules"].values(), key=lambda m: m[0])
    disp = ctx.counter("fusion.dispatches")
    per_exec = ctx.counter("fusion.batches") / disp if disp else 1.0
    if not count or not per_exec:
        return None
    return total / (count * per_exec)


def read(ctx):
    s = step_seconds_per_batch(ctx)
    return None if s is None else s * 1e3
