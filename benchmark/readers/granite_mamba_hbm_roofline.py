"""The Mamba-2 mixers' share of what the chip's memory allows in a decode
step of Granite-4.0-H: the state of EVERY slot read and written once a step
(``benchlib/granite_hybrid_counts.step_state_bytes`` at the cell's
``gen_max_slots``: the select that keeps an idle row rewrites it too), over
the summed device time of the events ``kernels/<kernel>.py`` names and the
HBM bandwidth. The mixers' weights are left out: their products are among
the events but the weights are not read there (XLA brings ``in_proj`` and
``out_proj`` in by asynchronous slices that run under other events;
PERF.md). One token a slot against a float32 state of 2 MB a layer is
bound by bandwidth, so this is the mixers' roofline share, and what a fused
state update would move. The events are XLA's (no Pallas kernel computes the
update): a program without them gives nothing to read."""
from benchlib import granite_hybrid_counts as counts


def read(ctx, kernel: str, pattern: str):
    module = ctx.kernels.get(kernel)
    runs = ctx.trace.module_runs(pattern)
    if module is None or not runs or "mamba_n_heads" not in ctx.config:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    if not events:
        return None
    per_step = counts.step_state_bytes(ctx.config, int(ctx.config["cluster"]["gen_max_slots"]))
    return 100.0 * (len(runs) * per_step / ctx.peaks["hbm_bytes_per_s"]) / seconds
