"""The whole decode step's share of the chip's peak: the operations of every
token decoded in the traced window (each at its own context length) over the
device time of the step program's runs there, and the bf16 peak."""
from benchlib import flops, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts:
        return None
    cfg = ctx.config
    needed = sum(flops.lm_decode_token(cfg["vocab_size"], cfg["n_layer"], cfg["n_embd"],
                                       cfg["n_inner"], c) for c in contexts)
    return 100.0 * needed / sum(runs) / (ctx.peaks["flops_bf16"] * ctx.chips)
