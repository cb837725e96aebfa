"""The fused paged decode-attention kernel's share of what the chip's memory
allows, for a model whose K/V layers are some of its layers and whose KV
heads are fewer than its query heads: as
``readers/paged_attention_hbm_roofline.py``, with the K and V bytes of a
cached position from ``benchlib/lfm2_moe_counts.kv_bytes_per_token`` (the
attention layers only, 8 KV heads of 64). Tokens HELD, not pages moved, and
neither the query nor the result, so sound events cannot read over 100%."""
from benchlib import lfm2_moe_counts, serving


def read(ctx, kernel: str):
    module = ctx.kernels.get(kernel)
    if module is None:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    contexts = serving.decoded_contexts(ctx.records, ctx.trace.t0, ctx.trace.t1)
    if not events or not contexts:
        return None
    needed = lfm2_moe_counts.kv_bytes_per_token(ctx.config) * sum(contexts)
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / seconds
