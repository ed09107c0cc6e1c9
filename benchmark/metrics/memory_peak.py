"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip."""


def read(ctx):
    return ctx.device["memory_peak_bytes"] or None
