"""Summed self time of the program spans whose name starts with one of
``prefixes`` and that ended in the window, as a share of the window."""
from benchlib import spans as sp


def read(ctx, prefixes):
    mine = [s for s in sp.ended_in(ctx.spans, ctx.window.t_open, ctx.window.t_close)
            if any(s["name"].startswith(p) for p in prefixes)]
    if not mine:
        return None
    selfs = sp.self_times(ctx.spans)
    return 100.0 * sum(selfs[s["span"]] for s in mine) / ctx.window.seconds
