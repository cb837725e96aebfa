"""Summed duration of the spans called ``name`` over the summed attribute
``count`` (``host/decode`` carries ``n``, its images), in us an item."""
from benchlib import spans as sp


def read(ctx, name: str, count: str):
    mine = [s for s in sp.ended_in(ctx.spans, ctx.window.t_open, ctx.window.t_close)
            if s["name"] == name and count in s["attrs"]]
    items = sum(float(s["attrs"][count]) for s in mine)
    return 1e6 * sum(s["dur"] for s in mine) / items if items else None
