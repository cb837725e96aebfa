"""Share of the page pool that holds residents' tokens, averaged over the
decode steps of the window: for every token a step decoded, the pages its
resident's cached positions fill, summed and divided by the steps (spans
named ``name``) times the pool's pages. What a deployment's cache holds, as
against what it reserves."""
from benchlib import serving, spans as sp


def read(ctx, name: str):
    w = ctx.window
    steps = sum(1 for s in sp.ended_in(ctx.spans, w.t_open, w.t_close) if s["name"] == name)
    contexts = serving.decoded_contexts(ctx.records, w.t_open, w.t_close)
    if not steps or not contexts:
        return None
    page = int(ctx.config["cluster"]["gen_page_size"])
    pool = int(ctx.config["cluster"]["gen_num_pages"])
    return 100.0 * sum(-(-c // page) for c in contexts) / (steps * pool)
