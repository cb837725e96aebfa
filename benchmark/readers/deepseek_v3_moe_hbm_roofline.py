"""The DeepSeek-V3 routed expert layers' share of what the chip's memory allows
in a decode step: per step each held expert that got a row (its three
matrices) and the router with its bias, once a layer
(``benchlib/deepseek_v3_counts.moe_step_bytes``; the experts hit are the mean
of the ``experts_hit`` attribute of the same window's ``gen/step`` spans), over
the summed device time of the events ``kernels/<kernel>.py`` names and the HBM
bandwidth. Three rows an expert against 4.7 M parameters is bound by
bandwidth, so this is the layers' roofline share. A program without the
events, or whose steps carry no ``experts_hit``, gives nothing to read."""
from benchlib import deepseek_v3_counts, spans as sp


def read(ctx, kernel: str, pattern: str):
    module = ctx.kernels.get(kernel)
    runs = ctx.trace.module_runs(pattern)
    if module is None or not runs or "kv_lora_rank" not in ctx.config:
        return None
    seconds, events = ctx.trace.op_seconds(module.EVENTS)
    hit = [float(s["attrs"]["experts_hit"]) for s in sp.ended_in(ctx.spans, ctx.trace.t0, ctx.trace.t1)
           if s["name"] == "gen/step" and "experts_hit" in s["attrs"]]
    if not events or not hit:
        return None
    needed = len(runs) * deepseek_v3_counts.moe_step_bytes(ctx.config, sum(hit) / len(hit))
    return 100.0 * (needed / ctx.peaks["hbm_bytes_per_s"]) / seconds
