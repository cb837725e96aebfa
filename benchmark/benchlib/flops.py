"""Operation counts from shapes, copied from ``models/registry.py``
(`_resnet_flops`, `_lm_decode_flops`; pinned there against XLA's
``cost_analysis``). A multiply-accumulate is 2 FLOPs; elementwise, norm and
pool terms are left out as sub-percent."""

from __future__ import annotations


#: Bytes of one value of a configuration's ``dtype``.
DTYPE_BYTES = {"bfloat16": 2, "float32": 4}


def _conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    return (size + 2 * pad - kernel) // stride + 1


def resnet_forward(stage_sizes, bottleneck: bool, num_classes: int, image: int) -> float:
    """One image through stem 7x7/2, maxpool 3x3/2, the stages, pooled head."""
    size = _conv_out(image, 7, 2, 3)
    fl = 2.0 * size * size * 64 * 3 * 49
    size = _conv_out(size, 3, 2, 1)
    cin = 64
    for i, n in enumerate(stage_sizes):
        f = 64 * 2 ** i
        for b in range(n):
            s = 2 if (i > 0 and b == 0) else 1
            out = _conv_out(size, 3, s, 1)
            if bottleneck:
                fl += 2.0 * size * size * f * cin
                fl += 2.0 * out * out * f * f * 9
                fl += 2.0 * out * out * (4 * f) * f
                if s != 1 or cin != 4 * f:
                    fl += 2.0 * out * out * (4 * f) * cin
                cin = 4 * f
            else:
                fl += 2.0 * out * out * f * cin * 9
                fl += 2.0 * out * out * f * f * 9
                if s != 1 or cin != f:
                    fl += 2.0 * out * out * f * cin
                cin = f
            size = out
    return fl + 2.0 * cin * num_classes


def lm_decode_token(vocab: int, layers: int, hidden: int, mlp: int, context: int) -> float:
    """One generated token attending ``context`` cached positions."""
    per_layer = 8.0 * hidden * hidden + 4.0 * context * hidden + 4.0 * hidden * mlp
    return layers * per_layer + 2.0 * hidden * vocab


def lm_step_params(vocab: int, layers: int, hidden: int, mlp: int) -> int:
    """The parameters every decode step has to read whole: the blocks, the
    final norm and the untied head. The token and position tables are left
    out: a step gathers one row of each per resident (``lm_step_rows``)."""
    per_layer = 4 * (hidden * hidden + hidden) + 2 * hidden * mlp + mlp + hidden + 4 * hidden
    return layers * per_layer + 2 * hidden + hidden * vocab + vocab


def lm_step_rows(hidden: int) -> int:
    """Values gathered from the embedding tables for one decoded token."""
    return 2 * hidden
