"""How far the Olmo-Hybrid decode step is from what the chip's memory allows:
the bytes the steps of the traced window must move
(``benchlib/olmo_hybrid_counts.step_fixed_bytes`` and ``token_bytes``: per
step every layer and the head once; per decoded token its slot's recurrent
state read and written, its K and V read, one embedding row) over the step
program's device time there and the HBM bandwidth."""
from benchlib import olmo_hybrid_counts, serving


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not runs or not contexts:
        return None
    needed = (len(runs) * olmo_hybrid_counts.step_fixed_bytes(ctx.config)
              + sum(olmo_hybrid_counts.token_bytes(ctx.config, c) for c in contexts))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / sum(runs)
