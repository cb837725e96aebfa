"""Peak device memory of the fullest chip, in GB (``memory_stats``)."""


def read(ctx):
    peak = ctx.device["memory_peak_bytes"]
    return peak / 1e9 if peak else None
