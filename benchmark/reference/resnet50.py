"""Plain reference for ResNet (He et al. 2015, arXiv:1512.03385, Table 1;
bottleneck blocks with the stride on the 3x3 convolution, as torchvision's
``resnet50``) on the served path's own inputs: JPEG decode (Pillow), a
triangle-filter resize to the model's input size, ImageNet normalisation,
the forward in float32 at ``highest`` precision, softmax, top-1.

Straight ``jax.numpy``/``lax``; imports nothing of the program. The weights
are the benchmark's own seed-made arrays, read by their path names
(``conv_init``, ``bn_init``, ``stage<i>_block<j>/Conv_<k>``, ``BatchNorm_<k>``,
``downsample_conv``, ``downsample_bn``, ``head``).

``check`` decides the cell's ``correct`` on probabilities, because seed-made
weights send nearly every image to one class: the widest relative gap
between a served top-1 probability and the reference's, and the served
top-1 index wherever the reference's two best logits are not a near tie.
"""

from __future__ import annotations

import functools

IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)
BLOCK = 32  # images per reference block


def triangle_weights(in_size: int, out_size: int):
    """[out, in] row-stochastic triangle filter (Pillow's BILINEAR: the
    support widens by the downscale ratio)."""
    import numpy as np

    w = np.zeros((out_size, in_size), np.float32)
    scale = in_size / out_size
    support = max(1.0, scale)
    for i in range(out_size):
        center = (i + 0.5) * scale
        lo = max(0, int(np.floor(center - support)))
        hi = min(in_size, int(np.ceil(center + support)))
        js = np.arange(lo, hi)
        d = np.abs((js + 0.5 - center) / (scale if support > 1.0 else 1.0))
        ws = np.where(d < 1.0, 1.0 - d, 0.0)
        w[i, lo:hi] = ws / ws.sum()
    return w


def decode(path: str):
    """uint8 [H, W, 3] RGB at the file's own size."""
    import numpy as np
    from PIL import Image

    with Image.open(path) as im:
        return np.asarray(im.convert("RGB"), dtype=np.uint8)


from benchlib.lowprec import quantize as _quantize  # noqa: E402


@functools.lru_cache(maxsize=None)
def _forward(stage_sizes: tuple, raw: int, size: int, eps: float, mode):
    import jax
    import jax.numpy as jnp
    from jax import lax

    wy = jnp.asarray(triangle_weights(raw, size))
    mean, std = jnp.asarray(IMAGENET_MEAN), jnp.asarray(IMAGENET_STD)

    def conv(x, kernel, stride, pad):
        kernel = kernel.astype(jnp.float32)
        if mode is not None:
            x, kernel = _quantize(x, mode), _quantize(kernel, mode)
        return lax.conv_general_dilated(
            x, kernel, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"))

    def bn(x, p, s):
        f = lambda a: a.astype(jnp.float32)
        return (x - f(s["mean"])) * lax.rsqrt(f(s["var"]) + eps) * f(p["scale"]) + f(p["bias"])

    def run(params, stats, u8):
        x = u8.astype(jnp.float32)
        if raw != size:
            x = jnp.einsum("oh,nhwc->nowc", wy, x)
            x = jnp.einsum("pw,nowc->nopc", wy, x)
        x = (x / 255.0 - mean) / std
        x = jax.nn.relu(bn(conv(x, params["conv_init"]["kernel"], 2, 3),
                           params["bn_init"], stats["bn_init"]))
        x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                              [(0, 0), (1, 1), (1, 1), (0, 0)])
        for i, count in enumerate(stage_sizes):
            for j in range(count):
                name = f"stage{i + 1}_block{j + 1}"
                p, s = params[name], stats[name]
                stride = 2 if i > 0 and j == 0 else 1
                y = jax.nn.relu(bn(conv(x, p["Conv_0"]["kernel"], 1, 0),
                                   p["BatchNorm_0"], s["BatchNorm_0"]))
                y = jax.nn.relu(bn(conv(y, p["Conv_1"]["kernel"], stride, 1),
                                   p["BatchNorm_1"], s["BatchNorm_1"]))
                y = bn(conv(y, p["Conv_2"]["kernel"], 1, 0), p["BatchNorm_2"], s["BatchNorm_2"])
                if "downsample_conv" in p:
                    x = bn(conv(x, p["downsample_conv"]["kernel"], stride, 0),
                           p["downsample_bn"], s["downsample_bn"])
                x = jax.nn.relu(y + x)
        x = jnp.mean(x, axis=(1, 2))
        w = params["head"]["kernel"].astype(jnp.float32)
        if mode is not None:
            x, w = _quantize(x, mode), _quantize(w, mode)
        return x @ w + params["head"]["bias"].astype(jnp.float32)

    def highest(params, stats, u8):
        with jax.default_matmul_precision("highest"):
            return run(params, stats, u8)

    return jax.jit(highest)


def logits(cfg: dict, flat: dict, paths, mode=None):
    """float32 logits [N, classes] of the files at ``paths``."""
    import numpy as np

    from benchlib.weights import unflatten

    tree = unflatten(flat)
    images = [decode(p) for p in paths]
    raw = images[0].shape[0]
    fn = _forward(tuple(cfg["stage_sizes"]), raw, int(cfg["input_size"]),
                  float(cfg["bn_epsilon"]), mode)
    out = []
    for start in range(0, len(images), BLOCK):
        block = images[start:start + BLOCK]
        pad = BLOCK - len(block)
        u8 = np.stack(block + [block[-1]] * pad)
        out.append(np.asarray(fn(tree["params"], tree["batch_stats"], u8))[:len(block)])
    return np.concatenate(out).astype(np.float64)


def top1(logit_rows):
    """(index, probability, margin between the two best logits) per row."""
    import numpy as np

    z = np.exp(logit_rows - logit_rows.max(axis=1, keepdims=True))
    prob = z.max(axis=1) / z.sum(axis=1)
    top2 = np.sort(logit_rows, axis=1)[:, -2:]
    return logit_rows.argmax(axis=1), prob, top2[:, 1] - top2[:, 0]


def check(cfg: dict, flat: dict, sample_paths, served, limits: dict, control=None) -> dict:
    """``served``: {path: [(index, probability), ...]} as the timed path
    answered, once per time the file was served in the window. With
    ``control`` the answers judged are the lower precision's own."""
    import numpy as np

    ref_idx, ref_prob, margin = top1(logits(cfg, flat, sample_paths))
    if control is not None:
        low_idx, low_prob, _ = top1(logits(cfg, flat, sample_paths, control))
        served = {p: [(int(low_idx[i]), float(low_prob[i]))] for i, p in enumerate(sample_paths)}
    worst, mismatches, compared = 0.0, 0, 0
    for i, path in enumerate(sample_paths):
        for idx, prob in served.get(path, ()):
            compared += 1
            if not np.isfinite(prob):
                worst = float("inf")
                continue
            worst = max(worst, abs(prob - ref_prob[i]) / ref_prob[i])
            if margin[i] > float(limits["near_tie_logit_margin"]) and int(idx) != int(ref_idx[i]):
                mismatches += 1
    return {
        "top1_prob_rel_err_max": {"value": float(worst if compared else float("inf")),
                                  "limit": float(limits["top1_prob_rel_err_max"])},
        "top1_index_mismatches": {"value": mismatches, "limit": 0},
        "answers_compared": {"value": compared, "limit": 1, "sense": "min"},
    }
