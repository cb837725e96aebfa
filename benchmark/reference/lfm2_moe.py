"""Plain reference for the LFM2-MoE block stack (``model_type: lfm2_moe``;
LiquidAI/LFM2-8B-A1B ``config.json``): per layer ``h = x + op(RMSNorm(x))``,
``x' = h + ffn(RMSNorm(h))``; ``op`` is a gated short convolution (``conv``)
or causal softmax attention with RMSNorm over each head's lanes of the query
and the key and rotary positions (``full_attention``); ``ffn`` is a gated
SiLU MLP in the first ``num_dense_layers`` layers and, in the others, the sum
over the ``num_experts_per_tok`` chosen experts of the same gated form at the
expert width; final RMSNorm, the head tied to the embedding.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one full
forward over prompt + served tokens: the conv is ``conv_L_cache`` shifted
products of ``z = B * u`` (no state, no window), attention is full and causal
over a dense mask one request at a time, the rotation is written out with
explicit cos and sin per position and pair, the experts are a loop over all
``num_experts`` with a dense 0/gate weight per token. No cache, no paging, no
state slots, no sorting of rows, no batching of steps. Imports nothing of the
program; weights are the benchmark's own seed-made arrays, upcast a layer (an
expert) at a time, so the 3.9 B parameters never stand in float32 at once.

Departures from the published modelling code, each the configuration file's
``assumed``: the tied head, ``head_dim = hidden_size / num_attention_heads``,
the ``rotate_half`` pairing (lane ``i`` with lane ``i + head_dim / 2``), the
``1e-6`` in the gate's normaliser. The program's kernels hold several
projections side by side (``B | C | u``, ``q | k | v``, ``W_1 | W_3``); the
reference reads the same leaves and cuts them where the equations do.

``check``, ``served_gaps`` and ``shapes_for`` are ``reference/nemotron_h.py``'s
(the gap by which the served token's logit lies below the reference's best,
over every served token of the sampled requests). They look ``logits_at`` and
``ROWS`` up in their own module, so a private copy of that module is loaded
here and given this file's.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

ROWS = 4  # requests per reference block: [4, 1408, 14336] float32 is 0.32 GB

CONV, FULL = "conv", "full_attention"


def _private_copy_of_sibling(stem: str):
    path = Path(__file__).with_name(f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}_for_lfm2_moe", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


shared = _private_copy_of_sibling("nemotron_h")
unflatten, _f32, _mm, _rms_norm, _silu, _quantize = (
    shared.unflatten, shared._f32, shared._mm, shared._rms_norm, shared._silu, shared._quantize)


def sizes(cfg: dict) -> dict:
    """The numbers the math reads, from a configuration file's keys."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "layer_types": tuple(cfg["layer_types"]), "dense_layers": int(cfg["num_dense_layers"]),
        "d": d, "heads": heads, "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": d // heads, "taps": int(cfg["conv_L_cache"]),
        "theta": float(cfg["rope_theta"]), "experts": int(cfg["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]), "width": int(cfg["intermediate_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "scaling": float(cfg["routed_scaling_factor"]), "norm_topk": bool(cfg["norm_topk_prob"]),
        "expert_bias": bool(cfg["use_expert_bias"]), "eps": float(cfg["norm_eps"]),
    }


def conv_operator(u, p, z, mode=None):
    """u: [B, T, D] (normed) -> [B, T, D]."""
    import jax.numpy as jnp

    d, taps, t = z["d"], z["taps"], u.shape[1]
    bcu = _mm(u, p["in_proj"]["kernel"], mode)
    b_gate, c_gate, x = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    gated = jnp.pad(b_gate * x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = _f32(p["conv"]["kernel"])                                    # [L, D], depthwise
    conv = sum(gated[:, j:j + t] * w[j] for j in range(taps))        # c_t = sum_j k_j z_{t-L+1+j}
    return _mm(c_gate * conv, p["out_proj"]["kernel"], mode)


def rotate(x, theta):
    """x: [B, T, H, Dh]; position ``t`` turns the pair (lane i, lane i + Dh/2)
    by ``t * theta^(-2i/Dh)``."""
    import jax.numpy as jnp

    t, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    freq = 1.0 / theta ** (2.0 * jnp.arange(half, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]      # [T, Dh/2]
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention_operator(u, p, z, mode=None):
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    heads, kv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    qkv = _mm(u, p["qkv"]["kernel"], mode)
    q = qkv[..., :heads * dh].reshape(b, t, heads, dh)
    k = qkv[..., heads * dh:(heads + kv) * dh].reshape(b, t, kv, dh)
    v = qkv[..., (heads + kv) * dh:].reshape(b, t, kv, dh)
    q = rotate(_rms_norm(q, p["q_norm"]["scale"], z["eps"]), z["theta"])
    k = rotate(_rms_norm(k, p["k_norm"]["scale"], z["eps"]), z["theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one(row):                                                    # a [H, T, T] mask at a time
        q1, k1, v1 = row
        scores = jnp.einsum("qhd,khd->hqk", q1, k1) / jnp.sqrt(float(dh))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v1)

    att = jax.lax.map(one, (q, k, v)).reshape(b, t, heads * dh)
    return _mm(att, p["out"]["kernel"], mode)


def gated_mlp(u, p, z, mode=None):
    both = _mm(u, p["gate_up"]["kernel"], mode)
    return _mm(_silu(both[..., :z["width"]]) * both[..., z["width"]:], p["down"]["kernel"], mode)


def route(u, p, z):
    """Dense [.., experts] gate weights: ``g_e`` for the chosen experts, 0
    elsewhere. The bias picks and does not weigh. Float32 in every mode."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ _f32(p["router"]["kernel"]))
    pick = s + _f32(p["router"]["bias"]) if z["expert_bias"] else s
    _, idx = jax.lax.top_k(pick, z["top_k"])
    g = s * jnp.sum(jax.nn.one_hot(idx, z["experts"], dtype=jnp.float32), axis=-2)
    if z["norm_topk"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-6)
    return g * z["scaling"]


def moe_ffn(u, p, z, mode=None):
    """``sum_e g_e W_2e (silu(W_1e u) * W_3e u)`` over all experts, one at a time."""
    import jax
    import jax.numpy as jnp

    gates = route(u, p, z)                                           # [B, T, E]
    f = z["expert_width"]
    u_q = _quantize(u, mode)

    def one(acc, inp):
        w13, w2, g = inp                                             # one expert, upcast here
        h = u_q @ _quantize(_f32(w13), mode)
        h = _silu(h[..., :f]) * h[..., f:]
        return acc + g[..., None] * (_quantize(h, mode) @ _quantize(_f32(w2), mode)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (p["experts"]["w13"], p["experts"]["w2"], jnp.moveaxis(gates, -1, 0)))
    return out


OPERATORS = {CONV: ("shortconv", conv_operator), FULL: ("attn", attention_operator)}


@functools.lru_cache(maxsize=None)
def _programs(frozen, mode):
    import jax
    import jax.numpy as jnp

    z = dict(frozen)

    def highest(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    def layer(kind, dense):
        name, operator = OPERATORS[kind]

        def run(x, p):
            x = x + operator(_rms_norm(x, p["operator_norm"]["scale"], z["eps"]), p[name], z, mode)
            u = _rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
            return x + (gated_mlp(u, p["mlp"], z, mode) if dense else moe_ffn(u, p["moe"], z, mode))

        return highest(run)

    def head(x, norm_f, table, positions):
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)       # [B, K, D]
        return _mm(_rms_norm(x, norm_f["scale"], z["eps"]), table.T, mode)   # the tied head

    embed = highest(lambda table, tokens: _f32(table[tokens]))
    layers = {(kind, dense): layer(kind, dense) for kind in OPERATORS for dense in (True, False)}
    return embed, layers, highest(head)


def logits_at(cfg: dict, flat: dict, tokens, positions, mode=None):
    """Logits [B, K, V] at ``positions`` [B, K] of ``tokens`` [B, T], float32."""
    params = unflatten(flat)["params"]
    z = sizes(cfg)
    embed, layers, head = _programs(tuple(sorted(z.items())), mode)
    x = embed(params["embed"]["embedding"], tokens)
    for i, kind in enumerate(z["layer_types"]):
        x = layers[kind, i < z["dense_layers"]](x, params[f"layer{i}"])
    return head(x, params["norm_f"], params["embed"]["embedding"], positions)


shared.logits_at, shared.ROWS = logits_at, ROWS
check, served_gaps, shapes_for = shared.check, shared.served_gaps, shared.shapes_for
