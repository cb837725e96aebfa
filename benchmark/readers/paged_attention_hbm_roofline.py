"""The fused paged decode-attention kernel's share of what the chip's memory
allows: the K and V bytes of the positions the residents hold (one row of
``n_embd`` for K and one for V, per cached position a decoded token attended,
per layer), over the summed device time of the kernel's events and the HBM
bandwidth. Attention at one query per slot is bound by bandwidth, so this is
the kernel's roofline share. It counts tokens HELD, not pages moved (the tail
of a slot's last page is moved and not counted) and not the query or the
result, so sound events cannot read over 100%: a reading above it means the
pattern caught another kernel's events or missed some of this one's."""
from benchlib import flops, serving


def read(ctx, kernel: str):
    module = ctx.kernels.get(kernel)
    if module is None:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not events or not contexts:
        return None
    cfg = ctx.config
    needed = 2 * cfg["n_layer"] * cfg["n_embd"] * flops.DTYPE_BYTES[cfg["dtype"]] * sum(contexts)
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / seconds
