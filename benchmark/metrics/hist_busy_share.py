"""Summed time of job histograms over the window, in percent: how busy
the one thread is that does that work (the drain's fetch and decode run
on one fetch thread; near 100 it sets the pace)."""


def read(ctx, hists):
    window = ctx.snap1["t"] - ctx.snap0["t"]
    mass = sum(ctx.hist_mass_ms(h)[0] for h in hists)
    if window <= 0 or not any(ctx.hist_mass_ms(h)[1] for h in hists):
        return None
    return 100.0 * mass / 1e3 / window
