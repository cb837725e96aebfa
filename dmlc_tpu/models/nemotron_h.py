"""The Nemotron-H family: Mamba-2, grouped-query attention and LatentMoE
layers in one stack (``model_type: nemotron_h``).

A layer is ONE mixer under one pre-norm and one residual,
``x <- x + mixer(RMSNorm(x))``; ``hybrid_override_pattern`` names the mixer
of each layer: ``M`` Mamba-2, ``*`` attention (no position term of any
kind), ``E`` LatentMoE. This file owns the family's math and nothing of
serving: a config read from the published keys, the parameter tree, and per
layer kind two pure functions over an explicit state, *prefill over a
padded prompt* and *one decode step*. The generation engine
(``generate/engine.py``) owns batching, pages, state slots and sampling and
reaches the family through ``NemotronHFamily.prefill`` / ``.decode``.

State a slot carries between steps (docs/GENERATE.md, "Two kinds of
per-slot state"): per ``*`` layer its K/V in pages (the engine's pools);
per ``M`` layer the last ``conv_kernel - 1`` pre-activation ``xBC`` rows and
the SSM state ``h`` ``[heads, head_dim, state]`` in float32, one array each.
The Mamba-2 mixer itself, prefill (chunked scan) and decode (one-step
recurrence), is ``models/mamba2``'s, which ``models/granite_hybrid`` runs
too; this config hands it ``mamba``.

The multi-token-prediction module of the published model is not built: it
adds nothing to next-token logits and the slot scheduler yields one token a
step.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from dmlc_tpu.models.blocks import (
    attend_decode, attend_prefill, expert_attrs, expert_aux, layer_index, rms_norm)
from dmlc_tpu.models.mamba2 import Mamba2Dims, mamba_decode, mamba_prefill
from dmlc_tpu.models.mamba2 import ssd_chunked  # noqa: F401  (the family's scan, as tests reach it)
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk


@dataclass(frozen=True)
class NemotronHConfig:
    """The published keys this family reads, plus what the deployment adds:
    ``experts_held = (first, count)`` (the routed experts that live on this
    chip; the router still scores all ``n_routed_experts``) and ``max_len``
    (the serving length)."""

    vocab_size: int
    hidden_size: int
    hybrid_override_pattern: str
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    mamba_num_heads: int
    mamba_head_dim: int
    n_groups: int
    ssm_state_size: int
    conv_kernel: int
    chunk_size: int
    n_routed_experts: int
    num_experts_per_tok: int
    moe_intermediate_size: int
    moe_latent_size: int
    moe_shared_expert_intermediate_size: int
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    norm_eps: float = 1e-5
    max_len: int = 1024
    experts_held: tuple[int, int] | None = None
    layer_kinds: tuple[str, ...] = field(init=False)

    def __post_init__(self) -> None:
        kinds = tuple(self.hybrid_override_pattern)
        if not kinds or set(kinds) - set("ME*"):
            raise ValueError(f"hybrid_override_pattern {self.hybrid_override_pattern!r}: "
                             "one of 'M', 'E', '*' per layer")
        object.__setattr__(self, "layer_kinds", kinds)
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside 0..{self.n_routed_experts}")
        if self.mamba_num_heads % self.n_groups or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must divide into their groups")

    @classmethod
    def from_published(cls, cfg: dict, **overrides: Any) -> "NemotronHConfig":
        """From a ``config.json``-shaped dict: every field of this class the
        dict names is taken, ``overrides`` win."""
        names = {f for f in cls.__dataclass_fields__ if f != "layer_kinds"}
        picked = {k: cfg[k] for k in names if k in cfg}
        picked.update(overrides)
        if picked.get("experts_held") is not None:
            picked["experts_held"] = tuple(int(v) for v in picked["experts_held"])
        return cls(**picked)

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held if self.experts_held is not None else (0, self.n_routed_experts)

    @property
    def mamba(self) -> Mamba2Dims:
        """What ``models/mamba2`` reads of this config."""
        return Mamba2Dims(self.mamba_num_heads, self.mamba_head_dim, self.n_groups,
                          self.ssm_state_size, self.conv_kernel, self.chunk_size, self.norm_eps)

    def layers_of(self, kind: str) -> list[int]:
        return list(layer_index(self.layer_kinds, kind))


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------


def param_shapes(cfg: NemotronHConfig) -> dict:
    """Nested {name: shape} of the family's parameters. No bias anywhere
    but the conv's."""
    d, lat = cfg.hidden_size, cfg.moe_latent_size
    dims = cfg.mamba
    inner, heads = dims.inner, dims.num_heads
    q_dim = cfg.num_attention_heads * cfg.head_dim
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    held = cfg.held[1]
    tree: dict = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i, kind in enumerate(cfg.layer_kinds):
        layer: dict = {"norm": {"scale": (d,)}}
        if kind == "M":
            layer["mamba"] = {
                "in_proj": {"kernel": (d, inner + dims.conv_dim + heads)},   # [z | xBC | dt]
                "conv": {"kernel": (cfg.conv_kernel, dims.conv_dim), "bias": (dims.conv_dim,)},
                "dt_bias": (heads,), "A_log": (heads,), "D": (heads,),
                "norm": {"scale": (inner,)},
                "out_proj": {"kernel": (inner, d)},
            }
        elif kind == "*":
            layer["attn"] = {
                "query": {"kernel": (d, q_dim)}, "key": {"kernel": (d, kv_dim)},
                "value": {"kernel": (d, kv_dim)}, "out": {"kernel": (q_dim, d)},
            }
        else:
            layer["moe"] = {
                "router": {"kernel": (d, cfg.n_routed_experts), "bias": (cfg.n_routed_experts,)},
                "down": {"kernel": (d, lat)}, "up": {"kernel": (lat, d)},
                "experts": {"w1": (held, lat, cfg.moe_intermediate_size),
                            "w2": (held, cfg.moe_intermediate_size, lat)},
                "shared": {"w1": {"kernel": (d, cfg.moe_shared_expert_intermediate_size)},
                           "w2": {"kernel": (cfg.moe_shared_expert_intermediate_size, d)}},
            }
        tree[f"layer{i}"] = layer
    tree["norm_f"] = {"scale": (d,)}
    tree["head"] = {"kernel": (d, cfg.vocab_size)}
    return tree


def _leaf_mean_std(path: str, depth: int) -> tuple[float, float]:
    """Seed init, sized so activations stay O(1) through the stack (a
    served configuration brings its own table: the benchmark's is in its
    configuration file)."""
    residual = 0.02 / (2.0 * depth) ** 0.5
    if path.endswith("scale") or path.endswith("/D"):
        return 1.0, 0.05
    if path.endswith("A_log"):
        return 1.39, 0.5
    if path.endswith("dt_bias"):
        return -4.6, 0.5
    if path.endswith("router/bias"):
        return 0.0, 0.01
    if path.endswith("conv/bias"):
        return 0.0, 0.02
    if path.endswith("conv/kernel"):
        return 0.0, 0.3
    if any(path.endswith(s) for s in ("out_proj/kernel", "out/kernel", "up/kernel",
                                      "experts/w2", "shared/w2/kernel")):
        return 0.0, residual
    return 0.0, 0.02


# ---------------------------------------------------------------------------
# *: grouped-query attention, no positions
# ---------------------------------------------------------------------------


def _qkv(p: Any, cfg: NemotronHConfig, u: Any) -> tuple[Any, Any, Any]:
    lead = u.shape[:-1]
    q = (u @ p["query"]["kernel"]).reshape(*lead, cfg.num_attention_heads, cfg.head_dim)
    k = (u @ p["key"]["kernel"]).reshape(*lead, cfg.num_key_value_heads, cfg.head_dim)
    v = (u @ p["value"]["kernel"]).reshape(*lead, cfg.num_key_value_heads, cfg.head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# E: LatentMoE
# ---------------------------------------------------------------------------


def _relu2(x: Any) -> Any:
    return jnp.square(jax.nn.relu(x))


def latent_moe(p: Any, cfg: NemotronHConfig, u: Any, rows: Any) -> tuple[Any, Any]:
    """u: [T, D] (normed); ``rows`` [T] bool, the rows that hold a token.
    Returns (out [T, D], held token-expert pairs per held expert [count])."""
    idx, gates = route_sigmoid_topk(
        u, p["router"]["kernel"], p["router"]["bias"], cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob)
    lat = u @ p["down"]["kernel"]
    routed, counts = held_experts_ffn(lat, p["experts"]["w1"], p["experts"]["w2"],
                                      idx, gates, cfg.held, rows,
                                      n_experts=cfg.n_routed_experts, activation=_relu2)
    shared = _relu2(u @ p["shared"]["w1"]["kernel"]) @ p["shared"]["w2"]["kernel"]
    return routed.astype(u.dtype) @ p["up"]["kernel"] + shared, counts


# ---------------------------------------------------------------------------
# the two functions the engine calls
# ---------------------------------------------------------------------------


class NemotronHFamily:
    """The engine's view of one registered Nemotron-H model (the seam of
    ``generate/engine.py``: ``prefill`` and ``decode`` over explicit state)."""

    def __init__(self, config: NemotronHConfig, dtype: Any) -> None:
        self.config = config
        self.dtype = dtype
        self.vocab = config.vocab_size
        self.max_len = config.max_len
        self._kv_index = layer_index(config.layer_kinds, "*")
        self._m_index = layer_index(config.layer_kinds, "M")
        self.kv_layers = len(self._kv_index)
        self.kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.expert_layers = len(config.layers_of("E"))
        self.experts_per_token = config.num_experts_per_tok

    def state_shapes(self, max_slots: int) -> dict:
        """The recurrent state beside the pages: per ``M`` layer one array
        each, slot-indexed, so a step updates each in place."""
        cfg = self.config
        n = len(self._m_index)
        return {
            "conv": [((max_slots, cfg.conv_kernel - 1, cfg.mamba.conv_dim), self.dtype)] * n,
            "ssm": [((max_slots, cfg.mamba_num_heads, cfg.mamba_head_dim,
                      cfg.ssm_state_size), jnp.float32)] * n,
        }

    def prefill(self, params: Any, tokens: Any, length: Any, slot: Any, kv: Any,
                state: Any) -> tuple[Any, Any, Any]:
        """tokens [1, S] padded at the end -> (logits at ``length - 1`` [V]
        float32, state with slot ``slot`` overwritten whole, aux)."""
        cfg = self.config
        x = params["embed"]["embedding"][tokens[0]].astype(self.dtype)
        rows = jnp.arange(x.shape[0]) < length
        conv, ssm, counts = list(state["conv"]), list(state["ssm"]), []
        for i, kind in enumerate(cfg.layer_kinds):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
            if kind == "M":
                with jax.named_scope("mamba"):
                    out, window, h = mamba_prefill(p["mamba"], cfg.mamba, u, length)
                m = self._m_index[i]
                conv[m] = conv[m].at[slot].set(window.astype(conv[m].dtype))
                ssm[m] = ssm[m].at[slot].set(h)
            elif kind == "*":
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, u)
                    out = attend_prefill(kv, self._kv_index[i], q, k, v, p["attn"]["out"]["kernel"])
            else:
                with jax.named_scope("moe"):
                    out, c = latent_moe(p["moe"], cfg, u, rows)
                counts.append(c)
            x = x + out
        last = jnp.take(x, length - 1, axis=0)
        logits = rms_norm(last, params["norm_f"]["scale"], cfg.norm_eps) @ params["head"]["kernel"]
        return logits.astype(jnp.float32), {"conv": conv, "ssm": ssm}, expert_aux(counts)

    def decode(self, params: Any, tokens: Any, lengths: Any, active: Any, kv: Any,
               state: Any) -> tuple[Any, Any, Any]:
        """tokens [B] -> (logits [B, V] float32, state, aux)."""
        del lengths  # no position term of any kind; the cache ops hold the lengths
        cfg = self.config
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        conv, ssm, counts = list(state["conv"]), list(state["ssm"]), []
        for i, kind in enumerate(cfg.layer_kinds):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["norm"]["scale"], cfg.norm_eps)
            if kind == "M":
                m = self._m_index[i]
                with jax.named_scope("mamba"):
                    out, conv[m], ssm[m] = mamba_decode(p["mamba"], cfg.mamba, u, conv[m], ssm[m],
                                                       active)
            elif kind == "*":
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, u)
                    out = attend_decode(kv, self._kv_index[i], q, k, v, p["attn"]["out"]["kernel"])
            else:
                with jax.named_scope("moe"):
                    out, c = latent_moe(p["moe"], cfg, u, active)
                counts.append(c)
            x = x + out
        logits = rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps) @ params["head"]["kernel"]
        return logits.astype(jnp.float32), {"conv": conv, "ssm": ssm}, expert_aux(counts)

    def work_attrs(self, aux: dict, rows: int) -> dict:
        """The attributes of ``gen/step`` / ``gen/prefill``: the expert
        layers' work (``[E layers, held]`` counts)."""
        return expert_attrs(aux["expert_counts"], rows, self.experts_per_token, self.expert_layers)


def register_nemotron_h(name: str, config: NemotronHConfig) -> Any:
    """Register ``config`` as a servable ``kind="lm"`` model called ``name``."""
    from dmlc_tpu.models import registry

    depth = len(config.layer_kinds)
    return registry.register_family(name, config, param_shapes(config),
                                    lambda path: _leaf_mean_std(path, depth), NemotronHFamily)


#: The CPU tests' preset (as ``lm_small`` is for the GPT-2 family): every
#: kind of layer, two KV heads under four query heads, 2 of 8 experts held.
NEMOTRON_H_TINY = NemotronHConfig(
    vocab_size=256, hidden_size=64, hybrid_override_pattern="ME*E",
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    conv_kernel=4, chunk_size=8, n_routed_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=48, moe_latent_size=32,
    moe_shared_expert_intermediate_size=96, routed_scaling_factor=2.5,
    max_len=128, experts_held=(0, 2))
