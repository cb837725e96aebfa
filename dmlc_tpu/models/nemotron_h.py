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
the SSM state ``h`` ``[heads, head_dim, state]`` in float32.

Padded prefill is exact for the recurrence: padding sits at the END, and
``Delta`` is forced to 0 at padded positions, where ``h_t = exp(0) h_{t-1} +
0`` carries ``h`` unchanged, so the state after the padded scan IS the state
after position ``length - 1``; the conv window is rows ``length-3 ..
length-1``. Prefill runs the chunked form of the scan (``chunk_size``),
decode the one-step recurrence; tests pin both to the sequential definition.

The multi-token-prediction module of the published model is not built: it
adds nothing to next-token logits and the slot scheduler yields one token a
step.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp

from dmlc_tpu.models.seeded_tree import SeededTreeModule
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk

HIGHEST = jax.lax.Precision.HIGHEST


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
    def mamba_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    def layers_of(self, kind: str) -> list[int]:
        return [i for i, k in enumerate(self.layer_kinds) if k == kind]


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------


def param_shapes(cfg: NemotronHConfig) -> dict:
    """Nested {name: shape} of the family's parameters. No bias anywhere
    but the conv's."""
    d, lat = cfg.hidden_size, cfg.moe_latent_size
    inner, heads = cfg.mamba_inner, cfg.mamba_num_heads
    q_dim = cfg.num_attention_heads * cfg.head_dim
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    held = cfg.held[1]
    tree: dict = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i, kind in enumerate(cfg.layer_kinds):
        layer: dict = {"norm": {"scale": (d,)}}
        if kind == "M":
            layer["mamba"] = {
                "in_proj": {"kernel": (d, inner + cfg.conv_dim + heads)},   # [z | xBC | dt]
                "conv": {"kernel": (cfg.conv_kernel, cfg.conv_dim), "bias": (cfg.conv_dim,)},
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


class NemotronHModule(SeededTreeModule):
    """The family's parameter tree as the registry's ``init_params`` draws it."""

    def __init__(self, config: NemotronHConfig, dtype: Any = jnp.float32) -> None:
        depth = len(config.layer_kinds)
        super().__init__(param_shapes(config), lambda path: _leaf_mean_std(path, depth),
                         vocab=config.vocab_size, max_len=config.max_len, dtype=dtype)
        self.config = config


# ---------------------------------------------------------------------------
# shared pieces
# ---------------------------------------------------------------------------


def rms_norm(x: Any, scale: Any, eps: float) -> Any:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _relu2(x: Any) -> Any:
    return jnp.square(jax.nn.relu(x))


def _gated_group_norm(y: Any, z: Any, scale: Any, groups: int, eps: float) -> Any:
    """``RMSNorm over each of `groups` slices of ( y * silu(z) )``: the gate
    is applied before the grouped norm. y, z: [..., inner] float32."""
    g = y * jax.nn.silu(z)
    shaped = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    shaped = shaped * jax.lax.rsqrt(jnp.mean(jnp.square(shaped), axis=-1, keepdims=True) + eps)
    return shaped.reshape(g.shape) * scale.astype(jnp.float32)


def _mamba_split(cfg: NemotronHConfig, zxbcdt: Any) -> tuple[Any, Any, Any]:
    inner = cfg.mamba_inner
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + cfg.conv_dim],
            zxbcdt[..., inner + cfg.conv_dim:])


def _xbc_split(cfg: NemotronHConfig, xbc: Any) -> tuple[Any, Any, Any]:
    """Activated ``xBC`` -> xs [..., H, P], B and C [..., G, N], float32."""
    inner, gn = cfg.mamba_inner, cfg.n_groups * cfg.ssm_state_size
    lead = xbc.shape[:-1]
    xs = xbc[..., :inner].reshape(*lead, cfg.mamba_num_heads, cfg.mamba_head_dim)
    b = xbc[..., inner:inner + gn].reshape(*lead, cfg.n_groups, cfg.ssm_state_size)
    c = xbc[..., inner + gn:].reshape(*lead, cfg.n_groups, cfg.ssm_state_size)
    return xs.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32)


def _dt_and_decay(p: Any, dt_raw: Any) -> tuple[Any, Any]:
    """``Delta = softplus(dt + dt_bias)`` (no upper clamp) and ``A = -exp(A_log)``."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(p["A_log"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# M: Mamba-2
# ---------------------------------------------------------------------------


def ssd_chunked(xs: Any, dt: Any, a: Any, b: Any, c: Any, chunk: int) -> tuple[Any, Any]:
    """The chunked form of ``h_t = exp(dt_t a) h_{t-1} + dt_t xs_t (x) B_t``,
    ``y_t = h_t C_t`` from ``h_{-1} = 0``. xs [S, H, P]; dt [S, H]; a [H];
    b, c [S, G, N], all float32; S a multiple of ``chunk``. Returns
    (y [S, H, P], h after the last position [H, P, N])."""
    s, heads, p_dim = xs.shape
    groups, n = b.shape[1], b.shape[2]
    per = heads // groups
    nc = s // chunk
    xs = xs.reshape(nc, chunk, groups, per, p_dim)
    dt = dt.reshape(nc, chunk, groups, per)
    b = b.reshape(nc, chunk, groups, n)
    c = c.reshape(nc, chunk, groups, n)
    da = dt * a.reshape(groups, per)                          # [c, L, G, R], <= 0
    cum = jnp.cumsum(da, axis=1)                              # inclusive
    dtx = dt[..., None] * xs                                  # [c, L, G, R, P]
    # Inside a chunk: position l reads s <= l with decay exp(cum_l - cum_s).
    seg = cum[:, :, None] - cum[:, None, :]                   # [c, L(l), L(s), G, R]
    causal = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    decay = jnp.where(causal[None, :, :, None, None], jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    cb = jnp.einsum("clgn,csgn->clsg", c, b, precision=HIGHEST)
    y_diag = jnp.einsum("clsgr,csgrp->clgrp", cb[..., None] * decay, dtx, precision=HIGHEST)
    # What each chunk adds to the state at its own end ...
    to_end = jnp.exp(cum[:, -1:] - cum)                       # [c, L, G, R]
    states = jnp.einsum("clgr,clgrp,clgn->cgrpn", to_end, dtx, b, precision=HIGHEST)
    # ... carried across chunks by the one sequential part of the scan.
    chunk_decay = jnp.exp(cum[:, -1])                         # [c, G, R]

    def carry(h, inp):
        st, dec = inp
        return dec[..., None, None] * h + st, h

    h0 = jnp.zeros((groups, per, p_dim, n), jnp.float32)
    h_last, h_before = jax.lax.scan(carry, h0, (states, chunk_decay))
    y_off = jnp.einsum("clgn,cgrpn,clgr->clgrp", c, h_before, jnp.exp(cum), precision=HIGHEST)
    y = (y_diag + y_off).reshape(s, heads, p_dim)
    return y, h_last.reshape(heads, p_dim, n)


def mamba_prefill(p: Any, cfg: NemotronHConfig, u: Any, length: Any) -> tuple[Any, Any, Any]:
    """One prompt, padded at the end. u: [S, D] (normed). Returns
    (out [S, D], conv window [K-1, conv_dim] = the pre-activation xBC rows
    ``length-K+1 .. length-1``, h after position ``length - 1`` [H, P, N])."""
    s = u.shape[0]
    k = cfg.conv_kernel
    z, xbc, dt_raw = _mamba_split(cfg, u @ p["in_proj"]["kernel"])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    window = jax.lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
    w = p["conv"]["kernel"].astype(jnp.float32)
    conv = sum(padded[j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    act = jax.nn.silu(conv + p["conv"]["bias"].astype(jnp.float32)).astype(u.dtype)
    xs, b, c = _xbc_split(cfg, act)
    dt, a = _dt_and_decay(p, dt_raw)
    dt = jnp.where((jnp.arange(s) < length)[:, None], dt, 0.0)   # padding carries h unchanged
    pad = -s % cfg.chunk_size
    if pad:
        xs, dt, b, c = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)) for t in (xs, dt, b, c))
    y, h_last = ssd_chunked(xs, dt, a, b, c, cfg.chunk_size)
    y = y[:s] + p["D"].astype(jnp.float32)[None, :, None] * xs[:s]
    y = _gated_group_norm(y.reshape(s, -1), z.astype(jnp.float32), p["norm"]["scale"],
                          cfg.n_groups, cfg.norm_eps)
    return y.astype(u.dtype) @ p["out_proj"]["kernel"], window, h_last


def mamba_decode(p: Any, cfg: NemotronHConfig, u: Any, window: Any, h: Any,
                 active: Any) -> tuple[Any, Any, Any]:
    """One token per slot. u: [B, D]; window [B, K-1, conv_dim]; h
    [B, H, P, N] float32; rows with ``active`` false keep their state."""
    bsz = u.shape[0]
    z, xbc, dt_raw = _mamba_split(cfg, u @ p["in_proj"]["kernel"])
    full = jnp.concatenate([window, xbc[:, None].astype(window.dtype)], axis=1)   # [B, K, C]
    w = p["conv"]["kernel"].astype(jnp.float32)
    conv = jnp.sum(full.astype(jnp.float32) * w[None], axis=1)
    act = jax.nn.silu(conv + p["conv"]["bias"].astype(jnp.float32)).astype(u.dtype)
    xs, b, c = _xbc_split(cfg, act)                                   # [B,H,P], [B,G,N]
    dt, a = _dt_and_decay(p, dt_raw)                                  # [B,H], [H]
    groups, per = cfg.n_groups, cfg.mamba_num_heads // cfg.n_groups
    hg = h.reshape(bsz, groups, per, cfg.mamba_head_dim, cfg.ssm_state_size)
    decay = jnp.exp(dt * a).reshape(bsz, groups, per, 1, 1)
    dtx = (dt[..., None] * xs).reshape(bsz, groups, per, cfg.mamba_head_dim, 1)
    h_new = decay * hg + dtx * b[:, :, None, None, :]
    # Elementwise in float32 on purpose: a dot would round h to the MXU's inputs.
    y = jnp.sum(h_new * c[:, :, None, None, :], axis=-1).reshape(bsz, cfg.mamba_num_heads, -1)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xs
    y = _gated_group_norm(y.reshape(bsz, -1), z.astype(jnp.float32), p["norm"]["scale"],
                          cfg.n_groups, cfg.norm_eps)
    keep = active[:, None, None]
    return (y.astype(u.dtype) @ p["out_proj"]["kernel"],
            jnp.where(keep, full[:, 1:], window),
            jnp.where(keep[..., None], h_new.reshape(h.shape), h))


# ---------------------------------------------------------------------------
# *: grouped-query attention, no positions
# ---------------------------------------------------------------------------


def _qkv(p: Any, cfg: NemotronHConfig, u: Any) -> tuple[Any, Any, Any]:
    lead = u.shape[:-1]
    q = (u @ p["query"]["kernel"]).reshape(*lead, cfg.num_attention_heads, cfg.head_dim)
    k = (u @ p["key"]["kernel"]).reshape(*lead, cfg.num_key_value_heads, cfg.head_dim)
    v = (u @ p["value"]["kernel"]).reshape(*lead, cfg.num_key_value_heads, cfg.head_dim)
    return q, k, v


def gqa_causal_attention(q: Any, k: Any, v: Any) -> Any:
    """Full causal attention of one sequence with query heads grouped onto
    KV heads (no copies of K or V). q [S, H, Dh]; k, v [S, KV, Dh]. Scores
    and softmax in float32 (``dense_attention``'s discipline)."""
    s, heads, dh = q.shape
    kv = k.shape[1]
    qg = (q.astype(jnp.float32) * dh ** -0.5).reshape(s, kv, heads // kv, dh)
    scores = jnp.einsum("qkgd,tkd->kgqt", qg, k.astype(jnp.float32))
    mask = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    probs = jax.nn.softmax(jnp.where(mask[None, None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("kgqt,tkd->qkgd", probs, v.astype(jnp.float32))
    return out.reshape(s, heads, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# E: LatentMoE
# ---------------------------------------------------------------------------


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
        self.kv_layers = len(config.layers_of("*"))
        self.kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self._kv_index = {layer: i for i, layer in enumerate(config.layers_of("*"))}
        self._m_index = {layer: i for i, layer in enumerate(config.layers_of("M"))}
        self.expert_layers = len(config.layers_of("E"))
        self.experts_per_token = config.num_experts_per_tok

    def state_shapes(self, max_slots: int) -> dict:
        """The recurrent state beside the pages: per ``M`` layer one array
        each, slot-indexed, so a step updates each in place."""
        cfg = self.config
        n = len(self._m_index)
        return {
            "conv": [((max_slots, cfg.conv_kernel - 1, cfg.conv_dim), self.dtype)] * n,
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
                    out, window, h = mamba_prefill(p["mamba"], cfg, u, length)
                m = self._m_index[i]
                conv[m] = conv[m].at[slot].set(window.astype(conv[m].dtype))
                ssm[m] = ssm[m].at[slot].set(h)
            elif kind == "*":
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, u)
                    kv.write_prefill(self._kv_index[i], k, v)
                    att = gqa_causal_attention(q, k, v)
                    out = att.reshape(att.shape[0], -1) @ p["attn"]["out"]["kernel"]
            else:
                with jax.named_scope("moe"):
                    out, c = latent_moe(p["moe"], cfg, u, rows)
                counts.append(c)
            x = x + out
        last = jnp.take(x, length - 1, axis=0)
        logits = rms_norm(last, params["norm_f"]["scale"], cfg.norm_eps) @ params["head"]["kernel"]
        return logits.astype(jnp.float32), {"conv": conv, "ssm": ssm}, self._aux(counts)

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
                    out, conv[m], ssm[m] = mamba_decode(p["mamba"], cfg, u, conv[m], ssm[m], active)
            elif kind == "*":
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, u)
                    att = kv.write_attend(self._kv_index[i], q, k, v)
                    out = att.reshape(att.shape[0], -1) @ p["attn"]["out"]["kernel"]
            else:
                with jax.named_scope("moe"):
                    out, c = latent_moe(p["moe"], cfg, u, active)
                counts.append(c)
            x = x + out
        logits = rms_norm(x, params["norm_f"]["scale"], cfg.norm_eps) @ params["head"]["kernel"]
        return logits.astype(jnp.float32), {"conv": conv, "ssm": ssm}, self._aux(counts)

    @staticmethod
    def _aux(counts: list) -> dict:
        return {"expert_counts": jnp.stack(counts)} if counts else {}

    def work_attrs(self, aux: dict, rows: int) -> dict:
        """What one program run did in the expert layers, from the counts it
        returned (NumPy, ``[E layers, held]``) and the rows that held a
        token: the attributes of ``gen/step`` / ``gen/prefill``."""
        counts = aux["expert_counts"]
        pairs = int(counts.sum())
        return {
            "expert_pairs": pairs,
            # Pairs whose expert lives on another chip: what the exchange would carry.
            "expert_pairs_absent": rows * self.experts_per_token * self.expert_layers - pairs,
            "experts_hit": float((counts > 0).sum(axis=1).mean()),
            "expert_rows_max": int(counts.max()),
        }


def register_nemotron_h(name: str, config: NemotronHConfig) -> Any:
    """Register ``config`` as a servable ``kind="lm"`` model called ``name``."""
    from dmlc_tpu.models import registry

    spec = registry.ModelSpec(
        name, lambda dtype=jnp.float32: NemotronHModule(config, dtype),
        config.max_len, config.vocab_size, classifier=False, kind="lm",
        num_heads=config.num_attention_heads,
        family=lambda dtype: NemotronHFamily(config, dtype))
    registry.register(spec)
    return spec


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
