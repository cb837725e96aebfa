"""How far a decode step is from what the chip's memory allows: the bytes a
step must read (the blocks, the final norm and the head once; for each
resident its K and V once and one row of the token and of the position
table) over the step program's device time in the trace and the HBM
bandwidth. Decode is bound by bandwidth, so this is the step's roofline
share. The embedding tables are NOT counted whole: a step that read them
would be doing work the algorithm does not need."""
from benchlib import flops, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts:
        return None
    cfg = ctx.config
    width = flops.DTYPE_BYTES[cfg["dtype"]]
    weights = width * flops.lm_step_params(cfg["vocab_size"], cfg["n_layer"], cfg["n_embd"],
                                           cfg["n_inner"])
    kv_token = 2 * cfg["n_layer"] * cfg["n_embd"] * width
    rows = width * flops.lm_step_rows(cfg["n_embd"])
    needed = weights * len(runs) + kv_token * sum(contexts) + rows * len(contexts)
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / sum(runs)
