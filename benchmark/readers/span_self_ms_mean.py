"""Mean self time, in ms, of the program spans called ``name`` that ended in
the window: the span's duration less what its child spans cover."""
from benchlib import spans as sp


def read(ctx, name: str):
    all_spans = sp.ended_in(ctx.spans, ctx.window.t_open, ctx.window.t_close)
    mine = [s for s in all_spans if s["name"] == name]
    if not mine:
        return None
    selfs = sp.self_times(ctx.spans)
    return 1e3 * sum(selfs[s["span"]] for s in mine) / len(mine)
