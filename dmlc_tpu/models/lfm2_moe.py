"""The LFM2-MoE family: gated short convolutions 3:1 with rotary
grouped-query attention, a gated MLP in the leading layers and gated experts
behind a sigmoid router in the rest (``model_type: lfm2_moe``).

``layer_types`` names the operator of each layer: ``conv`` is a gated short
convolution, ``full_attention`` causal softmax attention with RMSNorm over
each head's lanes of the query and the key and rotary positions. A layer is
``h = x + op(RMSNorm(x))``, ``x' = h + ffn(RMSNorm(h))``; the first
``num_dense_layers`` layers' ``ffn`` is ``W_2 (SiLU(W_1 x) * W_3 x)``, the
others' a sum over the ``num_experts_per_tok`` chosen experts of that same
form at the expert width; final RMSNorm, the head tied to the embedding. Like
``models/olmo_hybrid`` this file owns the math and nothing of serving: a
config read from the published keys, the parameter tree, and per layer kind
*prefill over a padded prompt* and *one decode step* over explicit state,
reached by the generation engine through ``Lfm2MoeFamily.prefill`` /
``.decode``.

The short convolution, per token ``x_t``: ``B | C | u = W_in x_t``, ``z_t =
B * u``, ``c_t = sum_{j < L} k_j * z_{t-L+1+j}`` (depthwise, causal, ``L =
conv_L_cache`` taps, no bias, no activation), ``y_t = W_out (C * c_t)``.

Rotary positions (the family's own: the engine hands ``decode`` each slot's
``lengths`` and knows nothing of positions): a head's ``d`` lanes are paired
``(i, i + d/2)`` (the ``rotate_half`` pairing) and pair ``i`` is turned by
``pos * theta^(-2i/d)``; tables in float32. A prefill turns a padded prompt
at positions ``0..S-1``, a step each slot's query and new key at that slot's
own ``lengths[b]``. K goes into the pages after its norm and rotation, so the
attention over the cache is position-free.

The router: ``s = sigmoid(W_g x)`` in float32 over every expert; the
``num_experts_per_tok`` of largest ``s + b`` are chosen (the expert bias
picks, it does not weigh); ``g = s[chosen] / (sum + 1e-6)`` under
``norm_topk_prob``, times ``routed_scaling_factor``. Every expert of a layer
is held here (``held = (0, num_experts)``): ``parallel/moe.held_experts_ffn``
in its dense form for a decode batch and its grouped one for a prefill.

State a slot carries between steps (docs/GENERATE.md, "Two kinds of per-slot
state"): per attention layer its K/V in pages (the engine's pools); per conv
layer the last ``L - 1`` rows of ``z``, ``[L - 1, hidden]``. Padded prefill is
exact: padding sits at the END, the conv is causal, and the window kept is
rows ``length-L+1 .. length-1`` of ``z`` (zeros before position 0).

Not built, by mechanism (a config that asks for one is refused): a conv bias,
scaled rotary tables (``rope_scaling``), an activation other than SiLU.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from dmlc_tpu.models.blocks import (
    apply_rotary, attend_decode, attend_prefill, expert_attrs, expert_aux, gated_expert, gated_mlp,
    layer_index, rms_norm, rotary_tables)
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk

CONV, FULL = "conv", "full_attention"

#: What the family's router adds to the sum of the chosen scores.
GATE_EPS = 1e-6


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys this family reads, plus ``max_len`` (the serving
    length)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    layer_types: tuple[str, ...]
    num_dense_layers: int
    num_experts: int
    num_experts_per_tok: int
    num_attention_heads: int
    num_key_value_heads: int
    conv_L_cache: int = 3
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True
    max_len: int = 2048

    def __post_init__(self) -> None:
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not self.layer_types or set(self.layer_types) - {CONV, FULL}:
            raise ValueError(f"layer_types {self.layer_types!r}: each {CONV!r} or {FULL!r}")
        if self.hidden_size % self.num_attention_heads or self.head_dim % 2:
            raise ValueError("hidden_size must divide into num_attention_heads of an even width")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into their KV heads")
        if not 0 <= self.num_dense_layers <= len(self.layer_types):
            raise ValueError(f"num_dense_layers {self.num_dense_layers} outside the stack")
        if not 1 <= self.num_experts_per_tok <= self.num_experts:
            raise ValueError("num_experts_per_tok must lie in 1..num_experts")
        if self.conv_L_cache < 2:
            raise ValueError("conv_L_cache must be at least 2 taps")

    @classmethod
    def from_published(cls, cfg: dict, **overrides: Any) -> "Lfm2MoeConfig":
        """From a ``config.json``-shaped dict: every field of this class the
        dict names is taken, ``overrides`` win. What the dict says of a
        mechanism this family does not build is refused, not ignored."""
        picked = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        picked.update(overrides)
        config = cls(**picked)
        depth = cfg.get("num_hidden_layers", len(config.layer_types))
        if depth != len(config.layer_types):
            raise ValueError(f"num_hidden_layers {depth} but {len(config.layer_types)} layer_types")
        if cfg.get("conv_bias", False):
            raise ValueError("conv_bias: a bias on the conv operator's projections is not built")
        if cfg.get("rope_scaling") is not None:
            raise ValueError("rope_scaling: scaled rotary tables are not built")
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {cfg['hidden_act']!r}: only 'silu' is built")
        return config

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layers_of(self, kind: str) -> list[int]:
        return list(layer_index(self.layer_types, kind))


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------


def param_shapes(cfg: Lfm2MoeConfig) -> dict:
    """Nested {name: shape} of the family's parameters; no bias but the
    router's expert bias, no head (tied to the embedding). Projections that
    read the same input share one kernel (``B | C | u``, ``q | k | v``,
    ``W_1 | W_3`` of the MLP and of each expert), every boundary on a
    multiple of 128 lanes at the published widths."""
    d = cfg.hidden_size
    q_dim = cfg.num_attention_heads * cfg.head_dim
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    tree: dict = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i, kind in enumerate(cfg.layer_types):
        if kind == CONV:
            op = {"shortconv": {
                "in_proj": {"kernel": (d, 3 * d)},
                "conv": {"kernel": (cfg.conv_L_cache, d)},
                "out_proj": {"kernel": (d, d)},
            }}
        else:
            op = {"attn": {
                "qkv": {"kernel": (d, q_dim + 2 * kv_dim)},
                "q_norm": {"scale": (cfg.head_dim,)}, "k_norm": {"scale": (cfg.head_dim,)},
                "out": {"kernel": (q_dim, d)},
            }}
        if i < cfg.num_dense_layers:
            ffn = {"mlp": {"gate_up": {"kernel": (d, 2 * cfg.intermediate_size)},
                           "down": {"kernel": (cfg.intermediate_size, d)}}}
        else:
            router = {"kernel": (d, cfg.num_experts)}
            if cfg.use_expert_bias:
                router["bias"] = (cfg.num_experts,)
            ffn = {"moe": {
                "router": router,
                "experts": {"w13": (cfg.num_experts, d, 2 * cfg.moe_intermediate_size),
                            "w2": (cfg.num_experts, cfg.moe_intermediate_size, d)},
            }}
        tree[f"layer{i}"] = {**op, **ffn, "operator_norm": {"scale": (d,)},
                             "ffn_norm": {"scale": (d,)}}
    tree["norm_f"] = {"scale": (d,)}
    return tree


def _leaf_mean_std(path: str, depth: int, width: int) -> tuple[float, float]:
    """Seed init (a served configuration brings its own table: the
    benchmark's is in its configuration file). The expert bias is drawn away
    from zero, so that choosing by ``s + b`` and weighing by ``s`` differ; the
    embedding wide enough that greedy streams of seed-drawn weights stay
    distinct (the current token stays the larger part of the stream). The
    FINAL norm's scale is drawn about 0, not 1: the head is the embedding's
    own matrix, and under a scale near 1 every token's largest logit is its
    own (``e . e`` against ``e . e'``), so a greedy stream of seed-drawn
    weights repeats its last prompt token whatever the layers compute. Its
    spread ``1 / sqrt(width)`` keeps the logits' spread about 1."""
    if path.endswith("norm_f/scale"):
        return 0.0, width ** -0.5
    if path.endswith("scale"):
        return 1.0, 0.05
    if path.endswith("router/bias"):
        return 0.0, 0.05
    if path.endswith("conv/kernel"):
        return 0.0, 0.5
    if path.endswith("embed/embedding"):
        return 0.0, 1.0
    if path.endswith(("out_proj/kernel", "out/kernel", "down/kernel", "experts/w2")):
        return 0.0, 0.02 / (2.0 * depth) ** 0.5
    return 0.0, 0.02


# ---------------------------------------------------------------------------
# conv: the gated short convolution
# ---------------------------------------------------------------------------


def _gated_conv_out(p: Any, rows: Any, c_gate: Any, out_rows: int) -> Any:
    """``rows`` [.., out_rows + L - 1, D] (the L - 1 rows of ``z`` before the
    first output row in front) -> ``W_out (C * conv(z))``, [.., out_rows, D]."""
    w = p["conv"]["kernel"].astype(jnp.float32)
    conv = sum(rows[..., j:j + out_rows, :].astype(jnp.float32) * w[j] for j in range(w.shape[0]))
    return (c_gate * conv.astype(c_gate.dtype)) @ p["out_proj"]["kernel"]


def shortconv_prefill(p: Any, cfg: Lfm2MoeConfig, u: Any, length: Any) -> tuple[Any, Any]:
    """One prompt, padded at the end. u: [S, D] (normed). Returns (out [S, D],
    the window [L-1, D] = rows ``length-L+1 .. length-1`` of ``z = B * u``)."""
    s, d, taps = u.shape[0], cfg.hidden_size, cfg.conv_L_cache
    bcu = u @ p["in_proj"]["kernel"]
    z = bcu[:, :d] * bcu[:, 2 * d:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, d), z.dtype), z], axis=0)
    window = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
    return _gated_conv_out(p, padded, bcu[:, d:2 * d], s), window


def shortconv_decode(p: Any, cfg: Lfm2MoeConfig, u: Any, window: Any,
                     active: Any) -> tuple[Any, Any]:
    """One token per slot. u: [B, D] (normed); window [B, L-1, D]; rows with
    ``active`` false keep their window."""
    d = cfg.hidden_size
    bcu = u @ p["in_proj"]["kernel"]
    z = bcu[:, :d] * bcu[:, 2 * d:]
    full = jnp.concatenate([window, z[:, None].astype(window.dtype)], axis=1)     # [B, L, D]
    out = _gated_conv_out(p, full, bcu[:, None, d:2 * d], 1)[:, 0]
    return out, jnp.where(active[:, None, None], full[:, 1:], window)


# ---------------------------------------------------------------------------
# full_attention (per-head query/key norm, rotary) and the gated experts
# ---------------------------------------------------------------------------


def _qkv(p: Any, cfg: Lfm2MoeConfig, u: Any, cos: Any, sin: Any) -> tuple[Any, Any, Any]:
    """``q | k | v`` from one kernel, RMSNorm over each head's lanes of q and
    of k, then both turned by the tables' rows (one a row of ``u``)."""
    lead, dh = u.shape[:-1], cfg.head_dim
    q_dim, kv_dim = cfg.num_attention_heads * dh, cfg.num_key_value_heads * dh
    qkv = u @ p["qkv"]["kernel"]
    q = qkv[..., :q_dim].reshape(*lead, cfg.num_attention_heads, dh)
    k = qkv[..., q_dim:q_dim + kv_dim].reshape(*lead, cfg.num_key_value_heads, dh)
    v = qkv[..., q_dim + kv_dim:].reshape(*lead, cfg.num_key_value_heads, dh)
    q = apply_rotary(rms_norm(q, p["q_norm"]["scale"], cfg.norm_eps), cos, sin)
    k = apply_rotary(rms_norm(k, p["k_norm"]["scale"], cfg.norm_eps), cos, sin)
    return q, k, v


def gated_moe(p: Any, cfg: Lfm2MoeConfig, u: Any, rows: Any) -> tuple[Any, Any]:
    """u: [T, D] (normed); ``rows`` [T] bool, the rows that hold a token.
    Returns (out [T, D], token-expert pairs per expert [num_experts])."""
    bias = p["router"].get("bias")
    if bias is None:
        bias = jnp.zeros((cfg.num_experts,), jnp.float32)
    idx, gates = route_sigmoid_topk(
        u, p["router"]["kernel"], bias, cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob, eps=GATE_EPS)
    routed, counts = held_experts_ffn(
        u, p["experts"]["w13"], p["experts"]["w2"], idx, gates, (0, cfg.num_experts), rows,
        n_experts=cfg.num_experts, activation=gated_expert)
    return routed.astype(u.dtype), counts


# ---------------------------------------------------------------------------
# the two functions the engine calls
# ---------------------------------------------------------------------------


class Lfm2MoeFamily:
    """The engine's view of one registered LFM2-MoE model (the seam of
    ``generate/engine.py``: ``prefill`` and ``decode`` over explicit state)."""

    def __init__(self, config: Lfm2MoeConfig, dtype: Any) -> None:
        self.config = config
        self.dtype = dtype
        self.vocab = config.vocab_size
        self.max_len = config.max_len
        self._kv_index = layer_index(config.layer_types, FULL)
        self._conv_index = layer_index(config.layer_types, CONV)
        self.kv_layers = len(self._kv_index)
        self.kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.expert_layers = len(config.layer_types) - config.num_dense_layers
        self.state_bytes_per_slot = sum(
            jnp.dtype(dtype).itemsize * math.prod(shape[1:])
            for leaves in self.state_shapes(1).values() for shape, dtype in leaves)

    def state_shapes(self, max_slots: int) -> dict:
        """The recurrent state beside the pages: per conv layer the window of
        ``z``, slot-indexed, so a step updates each in place."""
        cfg = self.config
        shape = (max_slots, cfg.conv_L_cache - 1, cfg.hidden_size)
        return {"conv": [(shape, self.dtype)] * len(self._conv_index)}

    def _ffn(self, p: Any, i: int, x: Any, rows: Any, counts: list) -> Any:
        """``x + ffn(RMSNorm(x))``: the gated MLP in the leading dense layers,
        the gated experts in the others (whose pair counts join ``counts``)."""
        u = rms_norm(x, p["ffn_norm"]["scale"], self.config.norm_eps)
        if i < self.config.num_dense_layers:
            with jax.named_scope("mlp"):
                return x + gated_mlp(p["mlp"], u)
        with jax.named_scope("moe"):
            out, c = gated_moe(p["moe"], self.config, u, rows)
        counts.append(c)
        return x + out

    def _logits(self, params: Any, x: Any) -> Any:
        """Final norm, then the head: the embedding's own matrix."""
        x = rms_norm(x, params["norm_f"]["scale"], self.config.norm_eps)
        return jnp.einsum("...d,vd->...v", x, params["embed"]["embedding"]).astype(jnp.float32)

    def prefill(self, params: Any, tokens: Any, length: Any, slot: Any, kv: Any,
                state: Any) -> tuple[Any, Any, Any]:
        """tokens [1, S] padded at the end -> (logits at ``length - 1`` [V]
        float32, state with slot ``slot`` overwritten whole, aux)."""
        cfg = self.config
        x = params["embed"]["embedding"][tokens[0]].astype(self.dtype)
        positions = jnp.arange(x.shape[0])
        rows = positions < length
        cos, sin = rotary_tables(positions, cfg.head_dim, cfg.rope_theta)
        conv, counts = list(state["conv"]), []
        for i, kind in enumerate(cfg.layer_types):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["operator_norm"]["scale"], cfg.norm_eps)
            if kind == CONV:
                with jax.named_scope("shortconv"):
                    out, window = shortconv_prefill(p["shortconv"], cfg, u, length)
                m = self._conv_index[i]
                conv[m] = conv[m].at[slot].set(window.astype(conv[m].dtype))
            else:
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, u, cos, sin)
                    out = attend_prefill(kv, self._kv_index[i], q, k, v, p["attn"]["out"]["kernel"])
            x = self._ffn(p, i, x + out, rows, counts)
        logits = self._logits(params, jnp.take(x, length - 1, axis=0))
        return logits, {"conv": conv}, expert_aux(counts)

    def decode(self, params: Any, tokens: Any, lengths: Any, active: Any, kv: Any,
               state: Any) -> tuple[Any, Any, Any]:
        """tokens [B] -> (logits [B, V] float32, state, aux). Slot ``b``'s
        token sits at position ``lengths[b]``: its query and key turn there."""
        cfg = self.config
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        cos, sin = rotary_tables(lengths, cfg.head_dim, cfg.rope_theta)
        conv, counts = list(state["conv"]), []
        for i, kind in enumerate(cfg.layer_types):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["operator_norm"]["scale"], cfg.norm_eps)
            if kind == CONV:
                m = self._conv_index[i]
                with jax.named_scope("shortconv"):
                    out, conv[m] = shortconv_decode(p["shortconv"], cfg, u, conv[m], active)
            else:
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, u, cos, sin)
                    out = attend_decode(kv, self._kv_index[i], q, k, v, p["attn"]["out"]["kernel"])
            x = self._ffn(p, i, x + out, active, counts)
        aux = expert_aux(counts)
        aux["kv_tokens_read"] = jnp.sum(jnp.where(active, lengths + 1, 0))
        return self._logits(params, x), {"conv": conv}, aux

    def work_attrs(self, aux: dict, rows: int) -> dict:
        """The attributes of ``gen/step`` (``aux`` of ``decode``; ``rows``
        active slots, each reading and writing its windows) and
        ``gen/prefill`` (the runs' counts summed; ``rows`` prompt tokens): the
        expert layers' work (``expert_attrs``; NumPy counts ``[expert layers,
        num_experts]``, every expert held here, so none absent), the state's
        and the cache's as ``models/olmo_hybrid`` does."""
        out = {"conv_layers": len(self._conv_index)}
        counts = aux.get("expert_counts")
        if counts is not None:
            out.update(expert_attrs(counts, rows, self.config.num_experts_per_tok,
                                    self.expert_layers))
        if "kv_tokens_read" in aux:
            out.update(state_bytes_touched=2 * rows * self.state_bytes_per_slot,
                       kv_tokens_read=int(aux["kv_tokens_read"]))
        else:
            out.update(state_bytes_touched=self.state_bytes_per_slot, prompt_tokens=rows)
        return out


def register_lfm2_moe(name: str, config: Lfm2MoeConfig) -> Any:
    """Register ``config`` as a servable ``kind="lm"`` model called ``name``."""
    from dmlc_tpu.models import registry

    depth, width = len(config.layer_types), config.hidden_size
    return registry.register_family(name, config, param_shapes(config),
                                    lambda path: _leaf_mean_std(path, depth, width), Lfm2MoeFamily)


#: The CPU tests' preset: two dense layers, then ``attn conv conv conv``
#: twice; four query heads on two KV heads of 16, 8 experts top 2.
LFM2_MOE_TINY = Lfm2MoeConfig(
    vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=48,
    layer_types=(CONV, CONV) + (FULL, CONV, CONV, CONV) * 2, num_dense_layers=2,
    num_experts=8, num_experts_per_tok=2, num_attention_heads=4, num_key_value_heads=2,
    max_len=256)
