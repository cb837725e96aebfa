"""Summed duration of the spans called ``name`` that ended in the window, as
a share of the window."""
from benchlib import spans as sp


def read(ctx, name: str):
    mine = [s for s in sp.ended_in(ctx.spans, ctx.window.t_open, ctx.window.t_close)
            if s["name"] == name]
    return 100.0 * sum(s["dur"] for s in mine) / ctx.window.seconds if mine else None
