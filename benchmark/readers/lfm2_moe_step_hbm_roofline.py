"""How far the LFM2-MoE decode step is from what the chip's memory allows:
the bytes the steps of the traced window must move
(``benchlib/lfm2_moe_counts.step_fixed_bytes`` and ``token_bytes``: per step
every operator, the dense feed-forwards, the routers and the head once and
each expert that got a row once; per decoded token its conv windows read and
written and its K and V read) over the step program's device time there and
the HBM bandwidth. The experts hit are the mean of the ``experts_hit``
attribute of the ``gen/step`` spans of the same window, never "all of them";
a program whose steps carry no such attribute gives nothing to read."""
from benchlib import lfm2_moe_counts, serving, spans as sp


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    hit = [float(s["attrs"]["experts_hit"]) for s in sp.ended_in(ctx.spans, ctx.trace.t0, ctx.trace.t1)
           if s["name"] == "gen/step" and "experts_hit" in s["attrs"]]
    if not runs or not contexts or not hit:
        return None
    needed = (len(runs) * lfm2_moe_counts.step_fixed_bytes(ctx.config, sum(hit) / len(hit))
              + sum(lfm2_moe_counts.token_bytes(ctx.config, c) for c in contexts))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / sum(runs)
