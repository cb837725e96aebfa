"""The gated delta-rule mixers' share of what the chip's memory allows in a
decode step: per step the linear layers' mixer weights once, per decoded
token its slot's recurrent state read and written
(``benchlib/olmo_hybrid_counts.deltanet_weight_bytes``, ``state_bytes_per_slot``), over the summed device
time of the events ``kernels/<kernel>.py`` names and the HBM bandwidth. One
token a slot against a float32 state of 2.2 MB a layer is bound by bandwidth,
so this is the mixers' roofline share. The events are XLA's (no Pallas
kernel computes the update): a program without them gives nothing to read."""
from benchlib import olmo_hybrid_counts, serving


def read(ctx, kernel: str, pattern: str):
    module = ctx.kernels.get(kernel)
    runs = ctx.trace.module_runs(pattern)
    if module is None or not runs or "linear_num_key_heads" not in ctx.config:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    decoded = len(serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1))
    if not events or not decoded:
        return None
    needed = (len(runs) * olmo_hybrid_counts.deltanet_weight_bytes(ctx.config)
              + decoded * 2.0 * olmo_hybrid_counts.state_bytes_per_slot(ctx.config))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / seconds
