"""Median duration, in ms, of the program spans called ``name`` that ended in the window."""
from benchlib import spans as sp, stats


def read(ctx, name: str):
    durs = [s["dur"] for s in sp.ended_in(ctx.spans, ctx.window.t_open, ctx.window.t_close)
            if s["name"] == name]
    return 1e3 * stats.quantile(durs, 0.5) if durs else None
