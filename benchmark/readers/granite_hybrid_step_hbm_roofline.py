"""How far the Granite-4.0-H decode step is from what the chip's memory
allows: the bytes the steps of the traced window must move
(``benchlib/granite_hybrid_counts``: per step every parameter once, the tied
head reading the whole embedding, and the recurrent state of EVERY slot read
and written, ``step_state_bytes`` at the cell's ``gen_max_slots``; per
decoded token its K and V read) over the step program's device time there
and the HBM bandwidth. A configuration without mamba layers gives nothing to
read."""
from benchlib import granite_hybrid_counts as counts, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts or "mamba_n_heads" not in ctx.config:
        return None
    per_step = (counts.step_fixed_bytes(ctx.config)
                + counts.step_state_bytes(ctx.config, int(ctx.config["cluster"]["gen_max_slots"])))
    needed = len(runs) * per_step + counts.kv_bytes_per_token(ctx.config) * float(sum(contexts))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / sum(runs)
