"""The fused paged decode-attention kernel's share of what the chip's memory
allows, for a model whose K/V layers are some of its layers: as
``readers/paged_attention_hbm_roofline.py``, with the K and V bytes of a
cached position from ``benchlib/olmo_hybrid_counts.kv_bytes_per_token`` (the
full-attention layers only). Tokens HELD, not pages moved, and neither the
query nor the result, so sound events cannot read over 100%."""
from benchlib import olmo_hybrid_counts, serving


def read(ctx, kernel: str):
    module = ctx.kernels.get(kernel)
    if module is None:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not events or not contexts:
        return None
    needed = olmo_hybrid_counts.kv_bytes_per_token(ctx.config) * sum(contexts)
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / seconds
