"""Model registry.

The reference statically defines exactly two jobs, "resnet18" and "alexnet"
(src/services.rs:168-169), with models loaded eagerly at member startup
(src/services.rs:513-524). Here models are looked up by name from a registry
that also carries the input geometry, so the scheduler, CLI, and bench all
agree on model identity by string name — including the BASELINE.json extras
(resnet50, vit_b16, clip_vit_l14).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from dmlc_tpu.models.alexnet import alexnet
from dmlc_tpu.models.clip import clip_vit_b32, clip_vit_l14
from dmlc_tpu.models.lm import (
    LM_SMALL_MAX_LEN,
    LM_SMALL_VOCAB,
    LM_WIDE_MAX_LEN,
    LM_WIDE_NUM_HEADS,
    LM_WIDE_VOCAB,
    lm_small,
    lm_wide,
)
from dmlc_tpu.models.resnet import resnet18, resnet34, resnet50
from dmlc_tpu.models.seeded_tree import SeededTreeModule
from dmlc_tpu.models.vit import vit_b16, vit_l14
from dmlc_tpu.parallel.sharding import (
    REPLICATED_PARTITION_RULES,
    TRANSFORMER_PARTITION_RULES,
)


@dataclass(frozen=True)
class ModelSpec:
    name: str
    build: Callable[..., Any]          # (dtype=...) -> nn.Module
    input_size: int                    # square image side; max_len for kind="lm"
    num_outputs: int                   # classes / embedding dim; vocab for "lm"
    classifier: bool = True            # False => embedding model (no top-1/accuracy)
    kind: str = "image"                # "image" | "lm" (autoregressive decode)
    # Ordered (regex, PartitionSpec) table consumed by parallel/sharding.py:
    # declared ONCE here, compiled into sharded programs at any mesh shape.
    # None => fully replicated (the CNN families). num_heads bounds tp.
    partition_rules: tuple[tuple[str, Any], ...] | None = None
    num_heads: int | None = None
    # kind="lm": (dtype) -> the family object the generation engine serves
    # through (prefill + decode over explicit state). None => the GPT-2
    # family over ``build``'s SPTransformerLM.
    family: Callable[[Any], Any] | None = None

    def module(self, dtype=jnp.bfloat16):
        if self.kind == "lm":
            return self.build(dtype=dtype)
        if self.classifier:
            return self.build(num_classes=self.num_outputs, dtype=dtype)
        return self.build(dtype=dtype)

    def decode_family(self, dtype: Any) -> Any:
        """The model's math as the generation engine calls it."""
        if self.family is not None:
            return self.family(dtype)
        from dmlc_tpu.models.lm import TransformerFamily

        return TransformerFamily(self.module(dtype=dtype), dtype)

    def init_params(self, rng, dtype=jnp.bfloat16, batch_size: int = 1):
        model = self.module(dtype=dtype)
        if self.kind == "lm":
            # Any token length yields the full parameter tree (the embed
            # tables are sized by the module's vocab/max_len, not the
            # example), so init with a short dummy sequence.
            dummy = jnp.zeros((batch_size, 8), jnp.int32)
            return model, model.init(rng, dummy)
        dummy = jnp.zeros((batch_size, self.input_size, self.input_size, 3), jnp.float32)
        return model, model.init(rng, dummy, train=False)

    # ---- analytic model accounting (devicemon + placement headroom) -----

    def param_count(self) -> int:
        """Total parameter/statistic scalars across every variable
        collection (params + batch_stats), computed ABSTRACTLY via
        ``jax.eval_shape`` — no device allocation, no compile. Pinned
        against the real init pytree in tests/test_model_analytics.py."""
        return sum(math.prod(leaf.shape) for leaf in _abstract_leaves(self.name))

    def param_bytes(self, dtype: Any = None) -> int:
        """Resident bytes of the variables pytree: each leaf's element
        count times its init dtype's width (or ``dtype``'s, when the
        serving engine casts — e.g. bfloat16). This is the analytic
        weights-residency figure the placement headroom constraint and the
        ``resident_bytes_<model>`` gauges build on (docs/OBSERVABILITY.md
        §8)."""
        itemsize = None if dtype is None else jnp.dtype(dtype).itemsize
        total = 0
        for leaf in _abstract_leaves(self.name):
            width = itemsize if itemsize is not None else jnp.dtype(leaf.dtype).itemsize
            total += math.prod(leaf.shape) * width
        return total

    def flops_per_item(self) -> float | None:
        """Analytic forward FLOPs for ONE item — an image for ``kind=
        "image"`` models, one generated token (decode step at max_len
        context, the roofline-relevant upper bound) for ``kind="lm"``.
        Multiply-accumulates count 2 FLOPs, matching XLA's
        ``cost_analysis()['flops']`` convention (validated against it in
        tests/test_model_analytics.py); elementwise/norm/pool terms are
        omitted as sub-percent noise. None for models without a formula."""
        fn = _FLOPS_PER_ITEM.get(self.name)
        return float(fn()) if fn is not None else None


@functools.lru_cache(maxsize=None)
def _abstract_leaves(name: str) -> tuple[Any, ...]:
    """Abstract (shape/dtype-only) leaves of a model's full variables
    pytree: ``eval_shape`` runs the real flax init without touching the
    device, so counts/bytes match the served tree exactly."""
    spec = get_model(name)

    def init() -> Any:
        _, variables = spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
        return variables

    return tuple(jax.tree_util.tree_leaves(jax.eval_shape(init)))


def _conv_out(size: int, kernel: int, stride: int, pad: int) -> int:
    """Output side of a square conv/pool with explicit symmetric padding."""
    return (size + 2 * pad - kernel) // stride + 1


def _resnet_flops(blocks: tuple[int, ...], bottleneck: bool,
                  num_classes: int = 1000, image: int = 224) -> float:
    """Conv-walk of models/resnet.py: stem 7x7/2 -> maxpool 3x3/2 -> four
    stages (filters 64*2^i, first block of stages 2-4 strides 2), basic
    blocks (two 3x3) or bottlenecks (1x1 -> 3x3 stride s -> 1x1 expand x4),
    1x1 projection downsample exactly when the residual shape changes."""
    size = _conv_out(image, 7, 2, 3)
    fl = 2.0 * size * size * 64 * 3 * 49           # stem conv, bias-free
    size = _conv_out(size, 3, 2, 1)                # maxpool
    cin = 64
    for i, n in enumerate(blocks):
        f = 64 * 2 ** i
        for b in range(n):
            s = 2 if (i > 0 and b == 0) else 1
            out = _conv_out(size, 3, s, 1)
            if bottleneck:
                fl += 2.0 * size * size * f * cin           # 1x1 reduce
                fl += 2.0 * out * out * f * f * 9           # 3x3, stride s
                fl += 2.0 * out * out * (4 * f) * f         # 1x1 expand
                if s != 1 or cin != 4 * f:
                    fl += 2.0 * out * out * (4 * f) * cin   # projection shortcut
                cin = 4 * f
            else:
                fl += 2.0 * out * out * f * cin * 9
                fl += 2.0 * out * out * f * f * 9
                if s != 1 or cin != f:
                    fl += 2.0 * out * out * f * cin
                cin = f
            size = out
    return fl + 2.0 * cin * num_classes            # pooled head


def _alexnet_flops(num_classes: int = 1000, image: int = 224) -> float:
    """Conv/fc walk of models/alexnet.py (all convs/denses carry bias —
    bias adds are sub-percent and omitted like every elementwise term)."""
    s1 = _conv_out(image, 11, 4, 2)                # 55
    fl = 2.0 * s1 * s1 * 64 * 3 * 121
    s2 = _conv_out(s1, 3, 2, 0)                    # 27
    fl += 2.0 * s2 * s2 * 192 * 64 * 25
    s3 = _conv_out(s2, 3, 2, 0)                    # 13
    fl += 2.0 * s3 * s3 * 384 * 192 * 9
    fl += 2.0 * s3 * s3 * 256 * 384 * 9
    fl += 2.0 * s3 * s3 * 256 * 256 * 9
    s4 = _conv_out(s3, 3, 2, 0)                    # 6
    flat = 256 * s4 * s4
    return fl + 2.0 * (flat * 4096 + 4096 * 4096 + 4096 * num_classes)


def _vit_flops(patch: int, hidden: int, layers: int, mlp: int,
               out_dim: int, image: int = 224, cls_tokens: int = 1) -> float:
    """Transformer walk shared by models/vit.py and the CLIP vision trunk:
    patch-embed conv + per-block (q/k/v/out projections, score+mix
    attention, MLP) + head/projection read off the cls token."""
    grid = image // patch
    seq = grid * grid + cls_tokens
    fl = 2.0 * grid * grid * hidden * 3 * patch * patch
    per_block = (
        8.0 * seq * hidden * hidden        # q, k, v, out projections
        + 4.0 * seq * seq * hidden         # QK^T scores + attention-weighted V
        + 4.0 * seq * hidden * mlp         # MLP in + out
    )
    return fl + layers * per_block + 2.0 * hidden * out_dim


def _lm_decode_flops(vocab: int, layers: int, hidden: int, mlp: int,
                     context: int) -> float:
    """One decode step (one generated token) at ``context`` resident
    tokens: per-layer q/k/v/out projections + paged-KV attention + MLP,
    plus the vocab head. The embedding lookup is a gather (no MACs)."""
    per_layer = (
        8.0 * hidden * hidden              # q, k, v, out projections
        + 4.0 * context * hidden           # scores + mix against the KV pages
        + 4.0 * hidden * mlp               # MLP in + out
    )
    return layers * per_layer + 2.0 * hidden * vocab


_FLOPS_PER_ITEM: dict[str, Callable[[], float]] = {
    "resnet18": lambda: _resnet_flops((2, 2, 2, 2), False),
    "resnet34": lambda: _resnet_flops((3, 4, 6, 3), False),
    "resnet50": lambda: _resnet_flops((3, 4, 6, 3), True),
    "alexnet": lambda: _alexnet_flops(),
    "vit_b16": lambda: _vit_flops(16, 768, 12, 3072, 1000),
    "vit_l14": lambda: _vit_flops(14, 1024, 24, 4096, 1000),
    "clip_vit_l14": lambda: _vit_flops(14, 1024, 24, 4096, 768),
    "clip_vit_b32": lambda: _vit_flops(32, 768, 12, 3072, 512),
    "lm_small": lambda: _lm_decode_flops(
        LM_SMALL_VOCAB, 2, 128, 256, LM_SMALL_MAX_LEN
    ),
    "lm_wide": lambda: _lm_decode_flops(
        LM_WIDE_VOCAB, 2, 512, 1024, LM_WIDE_MAX_LEN
    ),
}


_REGISTRY: dict[str, ModelSpec] = {}


def register(spec: ModelSpec) -> None:
    _REGISTRY[spec.name] = spec


def get_model(name: str) -> ModelSpec:
    if name not in _REGISTRY:
        raise KeyError(f"unknown model {name!r}; known: {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def list_models() -> list[str]:
    return sorted(_REGISTRY)


def register_family(name: str, config: Any, shapes: dict,
                    leaf_mean_std: Callable[[str], tuple[float, float]],
                    family_cls: Callable[[Any, Any], Any]) -> ModelSpec:
    """Register a family's ``config`` as a servable ``kind="lm"`` model called
    ``name``: its parameter tree ``shapes`` drawn from a seed (each leaf's
    normal draw from ``leaf_mean_std(path)``), served through
    ``family_cls(config, dtype)``."""

    def build(dtype: Any) -> SeededTreeModule:
        return SeededTreeModule(shapes, leaf_mean_std, vocab=config.vocab_size,
                                max_len=config.max_len, dtype=dtype)

    spec = ModelSpec(
        name, build, config.max_len, config.vocab_size, classifier=False, kind="lm",
        num_heads=config.num_attention_heads, family=lambda dtype: family_cls(config, dtype))
    register(spec)
    return spec


for _spec in [
    ModelSpec("resnet18", resnet18, 224, 1000,
              partition_rules=REPLICATED_PARTITION_RULES),
    ModelSpec("resnet34", resnet34, 224, 1000,
              partition_rules=REPLICATED_PARTITION_RULES),
    ModelSpec("resnet50", resnet50, 224, 1000,
              partition_rules=REPLICATED_PARTITION_RULES),
    ModelSpec("alexnet", alexnet, 224, 1000,
              partition_rules=REPLICATED_PARTITION_RULES),
    ModelSpec("vit_b16", vit_b16, 224, 1000,
              partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=12),
    ModelSpec("vit_l14", vit_l14, 224, 1000,
              partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=16),
    ModelSpec("clip_vit_l14", clip_vit_l14, 224, 768, classifier=False,
              partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=16),
    ModelSpec("clip_vit_b32", clip_vit_b32, 224, 512, classifier=False,
              partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=12),
    # Servable causal LM for the generation engine (dmlc_tpu/generate/):
    # init from seed, weights hot-swapped via the SDFS models/<name> blob
    # path like every other entry. input_size carries max_len, num_outputs
    # the vocab.
    ModelSpec(
        "lm_small", lm_small, LM_SMALL_MAX_LEN, LM_SMALL_VOCAB,
        classifier=False, kind="lm",
        partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=2,
    ),
    # Gang-serving proof model (ISSUE 17): over the single-chip HBM budget
    # in the test harness, serves only as a >=2 chip gang (docs/SHARDING.md).
    ModelSpec(
        "lm_wide", lm_wide, LM_WIDE_MAX_LEN, LM_WIDE_VOCAB,
        classifier=False, kind="lm",
        partition_rules=TRANSFORMER_PARTITION_RULES, num_heads=LM_WIDE_NUM_HEADS,
    ),
]:
    register(_spec)

# The Nemotron-H family's CPU-test preset (Mamba-2 + GQA + LatentMoE layers
# in one stack); real sizes are registered by whoever serves them
# (``models/nemotron_h.register_nemotron_h``).
from dmlc_tpu.models.nemotron_h import NEMOTRON_H_TINY, register_nemotron_h  # noqa: E402

register_nemotron_h("nemotron_h_tiny", NEMOTRON_H_TINY)

# The Olmo-Hybrid family's CPU-test preset (gated delta-rule linear attention
# 3:1 with full attention, two residual branches a layer); real sizes are
# registered by whoever serves them (``models/olmo_hybrid.register_olmo_hybrid``).
from dmlc_tpu.models.olmo_hybrid import OLMO_HYBRID_TINY, register_olmo_hybrid  # noqa: E402

register_olmo_hybrid("olmo_hybrid_tiny", OLMO_HYBRID_TINY)

# The LFM2-MoE family's CPU-test preset (gated short convolutions 3:1 with
# rotary grouped-query attention, gated experts after two dense layers); real
# sizes are registered by whoever serves them (``models/lfm2_moe.register_lfm2_moe``).
from dmlc_tpu.models.lfm2_moe import LFM2_MOE_TINY, register_lfm2_moe  # noqa: E402

register_lfm2_moe("lfm2_moe_tiny", LFM2_MOE_TINY)

# The DeepSeek-V3 family's CPU-test preset (latent attention over one cached
# row a position, a dense layer, then gated experts beside shared ones, a
# quarter of them held); real sizes are registered by whoever serves them
# (``models/deepseek_v3.register_deepseek_v3``).
from dmlc_tpu.models.deepseek_v3 import DEEPSEEK_V3_TINY, register_deepseek_v3  # noqa: E402

register_deepseek_v3("deepseek_v3_tiny", DEEPSEEK_V3_TINY)

# The Granite-4.0-H family's CPU-test preset (Mamba-2 and grouped-query
# attention, two branches a layer under muP multipliers, a tied head); real
# sizes are registered by whoever serves them
# (``models/granite_hybrid.register_granite_hybrid``).
from dmlc_tpu.models.granite_hybrid import GRANITE_HYBRID_TINY, register_granite_hybrid  # noqa: E402

register_granite_hybrid("granite_hybrid_tiny", GRANITE_HYBRID_TINY)
