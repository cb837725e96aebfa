"""How far the DeepSeek-V3 decode step is from what the chip's memory allows:
the bytes the steps of the traced window must move
(``benchlib/deepseek_v3_counts.step_fixed_bytes`` per step: every layer's
attention matrices, the dense feed-forward, the routers, the shared experts
and the head once and each held expert that got a row once; per decoded token
the latent rows it attends, 1,152 B a position a layer) over the step
program's device time there and the HBM bandwidth. The experts hit are the
mean of the ``experts_hit`` attribute of the ``gen/step`` spans of the same
window; a program whose steps carry no such attribute gives nothing to read."""
from benchlib import deepseek_v3_counts, serving, spans as sp


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    hit = [float(s["attrs"]["experts_hit"]) for s in sp.ended_in(ctx.spans, ctx.trace.t0, ctx.trace.t1)
           if s["name"] == "gen/step" and "experts_hit" in s["attrs"]]
    if not runs or not contexts or not hit or "kv_lora_rank" not in ctx.config:
        return None
    needed = (len(runs) * deepseek_v3_counts.step_fixed_bytes(ctx.config, sum(hit) / len(hit))
              + deepseek_v3_counts.latent_bytes_per_token(ctx.config) * float(sum(contexts)))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / sum(runs)
