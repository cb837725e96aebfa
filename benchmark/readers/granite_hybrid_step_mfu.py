"""The Granite-4.0-H decode step against the chip's peak: the operations of
every token decoded in the traced window
(``benchlib/granite_hybrid_counts.decode_token_flops``: every layer's
projections and gated MLP, the SSM recurrence, attention over the token's
own context, the tied head), over the device time of the step program's runs
there and the bf16 peak. A configuration without mamba layers gives nothing
to read."""
from benchlib import granite_hybrid_counts, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts or "mamba_n_heads" not in ctx.config:
        return None
    needed = sum(granite_hybrid_counts.decode_token_flops(ctx.config, c) for c in contexts)
    return 100.0 * needed / sum(runs) / (ctx.peaks["flops_bf16"] * ctx.chips)
