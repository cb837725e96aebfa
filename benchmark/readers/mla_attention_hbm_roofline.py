"""The absorbed latent decode-attention kernel's share of what the chip's
memory allows: the latent bytes of the positions the residents hold
(``kernels/<kernel>.py``'s ``bytes``: the algorithm's 576 values a position a
layer, every layer; positions HELD, not pages moved or lanes stored, and
neither the queries nor the result, so sound events cannot read over 100%)
over the kernel's summed device time and the HBM bandwidth."""
from benchlib import serving


def read(ctx, kernel: str):
    module = ctx.kernels.get(kernel)
    if module is None or "kv_lora_rank" not in ctx.config:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not events or not contexts:
        return None
    return 100.0 * (module.bytes(ctx.config, sum(contexts)) / ctx.peaks["hbm_bytes_per_s"]) / seconds
