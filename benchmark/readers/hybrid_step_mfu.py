"""The hybrid model's whole decode step against the chip's peak: the
operations of every token decoded in the traced window, this chip's share
(``benchlib/hybrid_counts.decode_token_flops``: M, *, router, latent
projections, shared expert and head whole, of the routed experts the
``top_k * held / experts`` a token sends here), each token at its own
context length, over the device time of the step program's runs there and
the bf16 peak."""
from benchlib import hybrid_counts, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts:
        return None
    needed = sum(hybrid_counts.decode_token_flops(ctx.config, c) for c in contexts)
    return 100.0 * needed / sum(runs) / (ctx.peaks["flops_bf16"] * ctx.chips)
