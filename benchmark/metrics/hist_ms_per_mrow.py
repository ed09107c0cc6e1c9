"""Summed milliseconds of a job histogram per million rows delivered."""


def read(ctx, hist):
    mass, n = ctx.hist_mass_ms(hist)
    rows = ctx.rows()
    if not n or not rows:
        return None
    return mass / (rows / 1e6)
