"""The LFM2-MoE decode step against the chip's peak: the operations of every
token decoded in the traced window
(``benchlib/lfm2_moe_counts.decode_token_flops``: every operator's
projections, the dense feed-forwards, the router and the ``top_k`` experts a
token is sent to, attention over the token's own context, the head), over
the device time of the step program's runs there and the bf16 peak. The
dense form multiplies every row by every expert; only the chosen experts'
operations are counted, so the share cannot gain from that."""
from benchlib import lfm2_moe_counts, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts:
        return None
    needed = sum(lfm2_moe_counts.decode_token_flops(ctx.config, c) for c in contexts)
    return 100.0 * needed / sum(runs) / (ctx.peaks["flops_bf16"] * ctx.chips)
