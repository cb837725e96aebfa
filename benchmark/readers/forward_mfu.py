"""The whole forward's share of the chip's peak: the operations one device
batch needs (``benchlib/flops.py``, from the configuration's shapes) over the
device time of the forward program's runs in the trace, and the bf16 peak."""
from benchlib import flops


def read(ctx, pattern: str):
    runs = ctx.trace.module_runs(pattern)
    if not runs:
        return None
    cfg = ctx.config
    per_image = flops.resnet_forward(cfg["stage_sizes"], bool(cfg["bottleneck"]),
                                     int(cfg["num_classes"]), int(cfg["input_size"]))
    needed = per_image * int(cfg["cluster"]["batch_size"]) * len(runs)
    return 100.0 * needed / sum(runs) / (ctx.peaks["flops_bf16"] * ctx.chips)
