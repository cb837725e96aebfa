"""Mean of attribute ``attr`` over the program spans called ``name`` that
ended in the window (``gen/step`` carries ``slots``, the residents of that step)."""
from benchlib import spans as sp


def read(ctx, name: str, attr: str):
    values = [float(s["attrs"][attr])
              for s in sp.ended_in(ctx.spans, ctx.window.t_open, ctx.window.t_close)
              if s["name"] == name and attr in s["attrs"]]
    return sum(values) / len(values) if values else None
