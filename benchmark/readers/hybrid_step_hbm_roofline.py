"""How far the hybrid model's decode step is from what the chip's memory
allows: the bytes the steps of the traced window must move
(``benchlib/hybrid_counts.step_fixed_bytes`` and ``token_bytes``: per step the dense parameters once
and each held expert that got a row once; per decoded token its slot's
recurrent state read and written, its K and V read, one embedding row) over
the step program's device time there and the HBM bandwidth. The experts hit
are the mean of the ``experts_hit`` attribute of the ``gen/step`` spans of
the same window, never "all held"; a program whose steps carry no such
attribute gives nothing to read."""
from benchlib import hybrid_counts, serving, spans as sp


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    hit = [float(s["attrs"]["experts_hit"]) for s in sp.ended_in(ctx.spans, ctx.trace.t0, ctx.trace.t1)
           if s["name"] == "gen/step" and "experts_hit" in s["attrs"]]
    if not runs or not contexts or not hit:
        return None
    needed = (len(runs) * hybrid_counts.step_fixed_bytes(ctx.config, sum(hit) / len(hit))
              + sum(hybrid_counts.token_bytes(ctx.config, c) for c in contexts))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / sum(runs)
