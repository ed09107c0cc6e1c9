"""Device seconds of the named programs (``XLA Modules`` events of the
first device) over the traced window, in percent."""


def read(ctx, modules):
    if not ctx.trace or not ctx.trace.get("modules"):
        return None
    found = [ctx.trace["modules"][m][0] for m in modules
             if m in ctx.trace["modules"]]
    if not found or not ctx.trace["window_s"]:
        return None
    return 100.0 * sum(found) / ctx.trace["window_s"]
