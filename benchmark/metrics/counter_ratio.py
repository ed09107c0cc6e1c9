"""scale x (counter ``num`` / counter ``den``), both over the window."""


def read(ctx, num, den, scale=1.0):
    d = ctx.counter(den)
    if not d:
        return None
    return scale * ctx.counter(num) / d
