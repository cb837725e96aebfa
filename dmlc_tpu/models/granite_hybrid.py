"""The Granite-4.0-H family: Mamba-2 and grouped-query attention layers in
one stack, each layer two pre-norm branches under muP multipliers
(``model_type: granitemoehybrid`` without experts).

``layer_types`` names the mixer of each layer: ``mamba`` is a Mamba-2 mixer
(``models/mamba2``, shared with ``models/nemotron_h``), ``attention`` causal
grouped-query softmax attention with no bias and no position term
(``position_embedding_type: "nope"``). Every layer is::

    h  = x + residual_multiplier * mixer(RMSNorm(x))
    x' = h + residual_multiplier * W_out(SiLU(g) * u),  g | u = W_in RMSNorm(h)

with ``x_0 = embedding_multiplier * E[token]`` and logits ``RMSNorm(x_L) E^T
/ logits_scaling`` (the head tied to the embedding). The attention's scores
are ``q . k * attention_multiplier``, not ``/ sqrt(head_dim)``: the family
turns ``q`` by ``attention_multiplier * sqrt(head_dim)`` before the shared
paths (the paged kernel in a step, the dense causal form in a prefill), which
apply ``head_dim ** -0.5`` themselves. The factor must be a power of two, so
that the turn is exact in bfloat16 and the kernel runs as it runs for every
other family.

Like ``models/nemotron_h`` this file owns the math and nothing of serving: a
config read from the published keys, the parameter tree, and per layer kind
*prefill over a padded prompt* and *one decode step* over explicit state,
reached by the generation engine through ``GraniteHybridFamily.prefill`` /
``.decode``.

State a slot carries between steps (docs/GENERATE.md, "Two kinds of per-slot
state"): per attention layer its K/V in pages (the engine's pools); per mamba
layer the conv window and the SSM state, held as ONE array per kind with the
slot leading, ``conv [slots, mamba layers, K-1, conv_dim]`` and ``ssm [slots,
mamba layers, heads, head_dim, state]`` in float32: mamba layer ``m`` reads
``[:, m]`` and writes it back in place. Each program run replaces the arrays
it is handed, and the host lets each replaced one go at a cost of its own
(``gen/release``): two arrays, not two a layer.

Not built, by mechanism (a config that asks for one is refused): experts
(``num_local_experts > 0``), a position term, biases on the attention's or the
mixer's projections, an untied head, a conv without its bias.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from dmlc_tpu.models.blocks import attend_decode, attend_prefill, gated_mlp, layer_index, rms_norm
from dmlc_tpu.models.mamba2 import Mamba2Dims, mamba_decode, mamba_prefill

MAMBA, ATTENTION = "mamba", "attention"


@dataclass(frozen=True)
class GraniteHybridConfig:
    """The published keys this family reads, plus ``max_len`` (the serving
    length)."""

    vocab_size: int
    hidden_size: int
    shared_intermediate_size: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_n_groups: int
    mamba_d_state: int
    mamba_d_conv: int
    mamba_chunk_size: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float
    rms_norm_eps: float = 1e-5
    max_len: int = 2048

    def __post_init__(self) -> None:
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not self.layer_types or set(self.layer_types) - {MAMBA, ATTENTION}:
            raise ValueError(f"layer_types {self.layer_types!r}: each {MAMBA!r} or {ATTENTION!r}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into their KV heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must divide into mamba_n_groups")
        turn = math.log2(self.q_turn)
        if turn != round(turn):
            raise ValueError(
                f"attention_multiplier {self.attention_multiplier}: times sqrt(head_dim) "
                f"{self.head_dim ** 0.5:g} it must be a power of two (q is turned by it, exactly)")

    @classmethod
    def from_published(cls, cfg: dict, **overrides: Any) -> "GraniteHybridConfig":
        """From a ``config.json``-shaped dict: every field of this class the
        dict names is taken, ``overrides`` win. What the dict says of a
        mechanism this family does not build is refused, not ignored."""
        picked = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        picked.update(overrides)
        refused = [
            ("num_local_experts", cfg.get("num_local_experts", 0) > 0, "experts are not built"),
            ("position_embedding_type", cfg.get("position_embedding_type", "nope") != "nope",
             "only 'nope' (no position term) is built"),
            ("attention_bias", bool(cfg.get("attention_bias", False)),
             "a bias on the attention's projections is not built"),
            ("mamba_proj_bias", bool(cfg.get("mamba_proj_bias", False)),
             "a bias on the mixer's projections is not built"),
            ("mamba_conv_bias", not cfg.get("mamba_conv_bias", True),
             "the conv is built with its bias"),
            ("tie_word_embeddings", not cfg.get("tie_word_embeddings", True),
             "only the head tied to the embedding is built"),
            ("hidden_act", cfg.get("hidden_act", "silu") != "silu", "only 'silu' is built"),
            ("normalization_function", cfg.get("normalization_function", "rmsnorm") != "rmsnorm",
             "only 'rmsnorm' is built"),
        ]
        for key, bad, why in refused:
            if bad:
                raise ValueError(f"{key} {cfg[key]!r}: {why}")
        config = cls(**picked)
        depth = cfg.get("num_hidden_layers", len(config.layer_types))
        if depth != len(config.layer_types):
            raise ValueError(f"num_hidden_layers {depth} but {len(config.layer_types)} layer_types")
        expand = cfg.get("mamba_expand")
        if expand is not None and expand * config.hidden_size != config.mamba.inner:
            raise ValueError(f"mamba_expand {expand}: {expand} x hidden_size is not "
                             f"mamba_n_heads x mamba_d_head = {config.mamba.inner}")
        return config

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def q_turn(self) -> float:
        """What q is multiplied by so that ``head_dim ** -0.5`` after it is
        ``attention_multiplier``."""
        return self.attention_multiplier * self.head_dim ** 0.5

    @property
    def mamba(self) -> Mamba2Dims:
        """What ``models/mamba2`` reads of this config."""
        return Mamba2Dims(self.mamba_n_heads, self.mamba_d_head, self.mamba_n_groups,
                          self.mamba_d_state, self.mamba_d_conv, self.mamba_chunk_size,
                          self.rms_norm_eps)

    def layers_of(self, kind: str) -> list[int]:
        return list(layer_index(self.layer_types, kind))


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------


def param_shapes(cfg: GraniteHybridConfig) -> dict:
    """Nested {name: shape} of the family's parameters; no bias but the
    conv's, no head (tied to the embedding). The mixer's subtree is
    ``models/nemotron_h``'s; projections that read the same input share one
    kernel (``q | k | v``, the MLP's ``g | u``)."""
    d, dims = cfg.hidden_size, cfg.mamba
    q_dim = cfg.num_attention_heads * cfg.head_dim
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    tree: dict = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i, kind in enumerate(cfg.layer_types):
        if kind == MAMBA:
            mixer = {"mamba": {
                "in_proj": {"kernel": (d, dims.inner + dims.conv_dim + dims.num_heads)},
                "conv": {"kernel": (dims.conv_kernel, dims.conv_dim), "bias": (dims.conv_dim,)},
                "dt_bias": (dims.num_heads,), "A_log": (dims.num_heads,), "D": (dims.num_heads,),
                "norm": {"scale": (dims.inner,)},
                "out_proj": {"kernel": (dims.inner, d)},
            }}
        else:
            mixer = {"attn": {"qkv": {"kernel": (d, q_dim + 2 * kv_dim)},
                              "out": {"kernel": (q_dim, d)}}}
        tree[f"layer{i}"] = {
            "input_norm": {"scale": (d,)}, **mixer, "mlp_norm": {"scale": (d,)},
            "mlp": {"gate_up": {"kernel": (d, 2 * cfg.shared_intermediate_size)},
                    "down": {"kernel": (cfg.shared_intermediate_size, d)}},
        }
    tree["norm_f"] = {"scale": (d,)}
    return tree


def _leaf_mean_std(path: str, width: int) -> tuple[float, float]:
    """Seed init: the table the benchmark's configuration file states
    (``benchmark/configs/granite-4-h-micro.json``, ``init``), whose reasons
    are given there. The FINAL norm's scale is drawn about 0, not 1: the
    head is the embedding's own matrix, and under a scale near 1 every
    token's largest logit is its own, so a greedy stream of seed-drawn
    weights repeats its last token whatever the layers compute
    (``models/lfm2_moe``); ``logits_scaling / sqrt(width)`` keeps the logits'
    spread about 1 after the divisor."""
    if path.endswith("norm_f/scale"):
        return 0.0, 8.0 * width ** -0.5
    if path.endswith("scale") or path.endswith("/D"):
        return 1.0, 0.05
    if path.endswith("A_log"):
        return 1.0, 0.5
    if path.endswith("dt_bias"):
        return -3.0, 0.5
    if path.endswith("conv/bias"):
        return 0.0, 0.02
    if path.endswith("conv/kernel"):
        return 0.0, 0.5
    if path.endswith("embed/embedding"):
        return 0.0, 1.0
    if path.endswith("qkv/kernel"):
        return 0.0, 0.06
    if path.endswith(("out_proj/kernel", "out/kernel", "down/kernel")):
        return 0.0, 0.05
    return 0.0, 0.02


# ---------------------------------------------------------------------------
# the two functions the engine calls
# ---------------------------------------------------------------------------


class GraniteHybridFamily:
    """The engine's view of one registered Granite-4.0-H model (the seam of
    ``generate/engine.py``: ``prefill`` and ``decode`` over explicit state)."""

    def __init__(self, config: GraniteHybridConfig, dtype: Any) -> None:
        self.config = config
        self.dtype = dtype
        self.vocab = config.vocab_size
        self.max_len = config.max_len
        self._kv_index = layer_index(config.layer_types, ATTENTION)
        self._m_index = layer_index(config.layer_types, MAMBA)
        self.kv_layers = len(self._kv_index)
        self.kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim

    def state_shapes(self, max_slots: int) -> dict:
        """The recurrent state beside the pages: ONE array per kind, the
        slot leading and the mamba layer second, so a step updates
        ``[:, m]`` of each in place and a run replaces two arrays."""
        dims, n = self.config.mamba, len(self._m_index)
        return {
            "conv": ((max_slots, n, dims.conv_kernel - 1, dims.conv_dim), self.dtype),
            "ssm": ((max_slots, n, dims.num_heads, dims.head_dim, dims.state_size), jnp.float32),
        }

    def _embed(self, params: Any, tokens: Any) -> Any:
        rows = params["embed"]["embedding"][tokens].astype(jnp.float32)
        return (rows * self.config.embedding_multiplier).astype(self.dtype)

    def _qkv(self, p: Any, u: Any) -> tuple[Any, Any, Any]:
        """``q | k | v`` from one kernel, q turned so that the shared paths'
        ``head_dim ** -0.5`` gives ``attention_multiplier``."""
        cfg = self.config
        lead, dh = u.shape[:-1], cfg.head_dim
        q_dim, kv_dim = cfg.num_attention_heads * dh, cfg.num_key_value_heads * dh
        qkv = u @ p["qkv"]["kernel"]
        q = qkv[..., :q_dim].reshape(*lead, cfg.num_attention_heads, dh) * cfg.q_turn
        k = qkv[..., q_dim:q_dim + kv_dim].reshape(*lead, cfg.num_key_value_heads, dh)
        v = qkv[..., q_dim + kv_dim:].reshape(*lead, cfg.num_key_value_heads, dh)
        return q, k, v

    def _mlp_branch(self, p: Any, h: Any) -> Any:
        """``h + residual_multiplier * MLP(RMSNorm(h))``."""
        cfg = self.config
        u = rms_norm(h, p["mlp_norm"]["scale"], cfg.rms_norm_eps)
        with jax.named_scope("mlp"):
            return h + gated_mlp(p["mlp"], u) * cfg.residual_multiplier

    def _logits(self, params: Any, x: Any) -> Any:
        """Final norm, the embedding's own matrix, then the divisor; float32."""
        cfg = self.config
        x = rms_norm(x, params["norm_f"]["scale"], cfg.rms_norm_eps)
        logits = jnp.einsum("...d,vd->...v", x, params["embed"]["embedding"],
                            preferred_element_type=jnp.float32)
        return logits / cfg.logits_scaling

    def prefill(self, params: Any, tokens: Any, length: Any, slot: Any, kv: Any,
                state: Any) -> tuple[Any, Any, Any]:
        """tokens [1, S] padded at the end -> (logits at ``length - 1`` [V]
        float32, state with slot ``slot`` overwritten whole, aux)."""
        cfg = self.config
        x = self._embed(params, tokens[0])
        conv, ssm = state["conv"], state["ssm"]
        for i, kind in enumerate(cfg.layer_types):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
            if kind == MAMBA:
                m = self._m_index[i]
                with jax.named_scope("mamba"):
                    out, window, h = mamba_prefill(p["mamba"], cfg.mamba, u, length)
                    conv = conv.at[slot, m].set(window.astype(conv.dtype))
                    ssm = ssm.at[slot, m].set(h)
            else:
                with jax.named_scope("attn"):
                    q, k, v = self._qkv(p["attn"], u)
                    out = attend_prefill(kv, self._kv_index[i], q, k, v, p["attn"]["out"]["kernel"])
            x = self._mlp_branch(p, x + out * cfg.residual_multiplier)
        logits = self._logits(params, jnp.take(x, length - 1, axis=0))
        return logits, {"conv": conv, "ssm": ssm}, {}

    def decode(self, params: Any, tokens: Any, lengths: Any, active: Any, kv: Any,
               state: Any) -> tuple[Any, Any, Any]:
        """tokens [B] -> (logits [B, V] float32, state, aux)."""
        del lengths  # no position term of any kind; the cache ops hold the lengths
        cfg = self.config
        x = self._embed(params, tokens)
        conv, ssm = state["conv"], state["ssm"]
        for i, kind in enumerate(cfg.layer_types):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["input_norm"]["scale"], cfg.rms_norm_eps)
            if kind == MAMBA:
                m = self._m_index[i]
                with jax.named_scope("mamba"):
                    out, window, h = mamba_decode(p["mamba"], cfg.mamba, u, conv[:, m], ssm[:, m],
                                                  active)
                    conv = conv.at[:, m].set(window)
                    ssm = ssm.at[:, m].set(h)
            else:
                with jax.named_scope("attn"):
                    q, k, v = self._qkv(p["attn"], u)
                    out = attend_decode(kv, self._kv_index[i], q, k, v, p["attn"]["out"]["kernel"])
            x = self._mlp_branch(p, x + out * cfg.residual_multiplier)
        return self._logits(params, x), {"conv": conv, "ssm": ssm}, {}

    def work_attrs(self, aux: dict, rows: int) -> dict:
        """No expert layer: a run's work is what ``gen/step`` /
        ``gen/prefill`` already say (the engine adds ``state_bytes``)."""
        return {}


def register_granite_hybrid(name: str, config: GraniteHybridConfig) -> Any:
    """Register ``config`` as a servable ``kind="lm"`` model called ``name``."""
    from dmlc_tpu.models import registry

    width = config.hidden_size
    return registry.register_family(name, config, param_shapes(config),
                                    lambda path: _leaf_mean_std(path, width), GraniteHybridFamily)


#: The CPU tests' preset: both kinds of layer (4 mamba, 2 attention), ONE
#: Mamba group as published, two KV heads under four query heads of 16, the
#: published multipliers (the score's ``1 / head_dim`` is 1/16 here).
GRANITE_HYBRID_TINY = GraniteHybridConfig(
    vocab_size=256, hidden_size=64, shared_intermediate_size=160,
    layer_types=(MAMBA, ATTENTION, MAMBA, MAMBA, ATTENTION, MAMBA),
    num_attention_heads=4, num_key_value_heads=2,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_d_conv=4, mamba_chunk_size=8, embedding_multiplier=12.0,
    residual_multiplier=0.22, attention_multiplier=0.0625, logits_scaling=8.0,
    max_len=256)
