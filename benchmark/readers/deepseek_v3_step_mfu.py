"""The DeepSeek-V3 decode step against the chip's peak: the operations of
every token decoded in the traced window
(``benchlib/deepseek_v3_counts.decode_token_flops``: the latent projections,
the absorbed products, attention over the token's own context, the dense
feed-forward, routers, shared experts, the head) plus those of the
token-expert pairs whose expert is held here (``expert_pairs`` of the same
window's ``gen/step`` spans: the pairs the program counted, not the rows a
dense form multiplies), over the device time of the step program's runs there
and the bf16 peak."""
from benchlib import deepseek_v3_counts, serving, spans as sp


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    pairs = [float(s["attrs"]["expert_pairs"]) for s in sp.ended_in(ctx.spans, ctx.trace.t0, ctx.trace.t1)
             if s["name"] == "gen/step" and "expert_pairs" in s["attrs"]]
    if not runs or not contexts or not pairs or "kv_lora_rank" not in ctx.config:
        return None
    needed = (sum(deepseek_v3_counts.decode_token_flops(ctx.config, c) for c in contexts)
              + len(runs) * (sum(pairs) / len(pairs)) * deepseek_v3_counts.expert_pair_flops(ctx.config))
    return 100.0 * needed / sum(runs) / (ctx.peaks["flops_bf16"] * ctx.chips)
