"""Plain reference for the DeepSeek-V3 block stack (``model_type:
deepseek_v3``; kakaocorp/kanana-2-30b-a3b-instruct-2601 ``config.json``): per
layer ``h = x + attn(RMSNorm(x))``, ``x' = h + ffn(RMSNorm(h))``; ``attn`` is
latent attention: ``q = W_q u`` in heads of ``q_nope | q_rope``, ``c | k_rope
= W_kva u`` with ``k_rope`` one row for all heads, ``c <- RMSNorm(c)``,
``k_nope_h = W_uk_h c`` and ``v_h = W_uv_h c``, ``q_rope`` and ``k_rope``
turned at the token's position, ``k_h = k_nope_h | k_rope``, causal softmax of
``q_h . k_h / sqrt(nope + rope)``, ``W_o`` over the heads' ``p_h v_h``; ``ffn``
is a gated SiLU MLP in the first ``first_k_dense_replace`` layers and, in the
others, the sum over the ``num_experts_per_tok`` chosen experts of the same
gated form at the expert width plus the shared experts (one such MLP of
``n_shared_experts`` expert widths, ungated); final RMSNorm, an untied head.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one full
forward over prompt + served tokens, in the EXPANDED form only: every
position's keys and values are made per head from its latent, attention is
full and causal over a dense mask one request at a time, the rotation is
written out with explicit cos and sin per position and pair, the experts are
a loop over the held experts with a dense 0/gate weight per token. No cache,
no latent row kept, no absorption of ``W_uk`` / ``W_uv`` into the query or the
result, no paging, no sorting of rows, no batching of steps. Imports nothing
of the program; weights are the benchmark's own seed-made arrays, upcast a
layer (an expert) at a time.

The configuration's cut is given to the reference as to the program
(model-configs guide, section 4): the router scores all
``published.n_routed_experts`` experts and keeps ``num_experts_per_tok``; only
experts ``deployment.experts_held = [first, count]`` add to the result, and
what the others would add is left out. Departures from the published model
are the file's ``assumed`` (the rotary pairing: lane ``i`` of the rotary lanes
with lane ``i + rope / 2``, the order the published code brings its adjacent
pairs into before it turns them).

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

ROWS = 2  # requests per reference block: [2, 1023, 128256] float32 logits are 1.05 GB


def _private_copy_of_sibling(stem: str):
    path = Path(__file__).with_name(f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}_for_deepseek_v3", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


shared = _private_copy_of_sibling("nemotron_h")
unflatten, _f32, _mm, _rms_norm, _silu, _quantize = (
    shared.unflatten, shared._f32, shared._mm, shared._rms_norm, shared._silu, shared._quantize)


def sizes(cfg: dict) -> dict:
    """The numbers the math reads, from a configuration file's keys."""
    experts = int(cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]))
    held = tuple(int(v) for v in cfg.get("deployment", {}).get("experts_held", (0, experts)))
    return {
        "layers": int(cfg["num_hidden_layers"]), "dense_layers": int(cfg["first_k_dense_replace"]),
        "d": int(cfg["hidden_size"]), "heads": int(cfg["num_attention_heads"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "v": int(cfg["v_head_dim"]), "rank": int(cfg["kv_lora_rank"]),
        "theta": float(cfg["rope_theta"]), "eps": float(cfg["rms_norm_eps"]),
        "experts": experts, "held": held, "top_k": int(cfg["num_experts_per_tok"]),
        "width": int(cfg["intermediate_size"]), "expert_width": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        "scaling": float(cfg["routed_scaling_factor"]), "norm_topk": bool(cfg["norm_topk_prob"]),
    }


def rotate(x, theta):
    """x: [B, T, .., R]; position ``t`` turns the pair (lane i, lane i + R/2)
    of the last axis by ``t * theta^(-2i/R)``."""
    import jax.numpy as jnp

    t, r = x.shape[1], x.shape[-1]
    half = r // 2
    freq = 1.0 / theta ** (2.0 * jnp.arange(half, dtype=jnp.float32) / r)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]      # [T, R/2]
    angle = angle.reshape(1, t, *([1] * (x.ndim - 3)), half)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def latent_attention(u, p, z, mode=None):
    """u: [B, T, D] (normed) -> [B, T, D], keys and values expanded per head."""
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    heads, nope, rope, rank = z["heads"], z["nope"], z["rope"], z["rank"]
    both = _mm(u, p["q_kva"]["kernel"], mode)
    q = both[..., :heads * (nope + rope)].reshape(b, t, heads, nope + rope)
    c = both[..., heads * (nope + rope):heads * (nope + rope) + rank]
    k_rope = rotate(both[..., heads * (nope + rope) + rank:], z["theta"])           # [B, T, rope]
    q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:], z["theta"])], axis=-1)
    c = _quantize(_rms_norm(c, p["kv_norm"]["scale"], z["eps"]), mode)
    k_nope = jnp.einsum("btc,hcn->bthn", c, _quantize(_f32(p["k_up"]), mode))
    v = jnp.einsum("btc,hcv->bthv", c, _quantize(_f32(p["v_up"]), mode))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :], (b, t, heads, rope))], axis=-1)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one(row):                                                    # a [H, T, T] mask at a time
        q1, k1, v1 = row
        scores = jnp.einsum("qhd,khd->hqk", q1, k1) / jnp.sqrt(float(nope + rope))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v1)

    att = jax.lax.map(one, (q, k, v)).reshape(b, t, heads * z["v"])
    return _mm(att, p["out"]["kernel"], mode)


def gated_mlp(u, p, width, mode=None):
    both = _mm(u, p["gate_up"]["kernel"], mode)
    return _mm(_silu(both[..., :width]) * both[..., width:], p["down"]["kernel"], mode)


def route(u, p, z):
    """Dense [.., experts] gate weights over ALL the model's experts: ``g_e``
    for the chosen, 0 elsewhere. The bias picks and does not weigh. Float32
    in every mode."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ _f32(p["router"]["kernel"]))
    _, idx = jax.lax.top_k(s + _f32(p["router"]["bias"]), z["top_k"])
    g = s * jnp.sum(jax.nn.one_hot(idx, z["experts"], dtype=jnp.float32), axis=-2)
    if z["norm_topk"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * z["scaling"]


def expert_layer(u, p, z, mode=None):
    """``sum over held e of g_e W_2e (silu(W_1e u) * W_3e u)``, one expert at
    a time, plus the shared experts' MLP, ungated."""
    import jax
    import jax.numpy as jnp

    first, count = z["held"]
    gates = route(u, p, z)[..., first:first + count]                 # [B, T, held]
    f = z["expert_width"]
    u_q = _quantize(u, mode)

    def one(acc, inp):
        w13, w2, g = inp                                             # one expert, upcast here
        h = u_q @ _quantize(_f32(w13), mode)
        h = _silu(h[..., :f]) * h[..., f:]
        return acc + g[..., None] * (_quantize(h, mode) @ _quantize(_f32(w2), mode)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(u),
                             (p["experts"]["w13"], p["experts"]["w2"], jnp.moveaxis(gates, -1, 0)))
    return routed + gated_mlp(u, p["shared"], z["shared_width"], mode)


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

    def layer(dense):
        def run(x, p):
            x = x + latent_attention(_rms_norm(x, p["attn_norm"]["scale"], z["eps"]), p["attn"], z, mode)
            u = _rms_norm(x, p["ffn_norm"]["scale"], z["eps"])
            return x + (gated_mlp(u, p["mlp"], z["width"], mode) if dense
                        else expert_layer(u, p["moe"], z, mode))

        return highest(run)

    def head(x, norm_f, head_p, positions):
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)       # [B, K, D]
        return _mm(_rms_norm(x, norm_f["scale"], z["eps"]), head_p["kernel"], mode)

    embed = highest(lambda table, tokens: _f32(table[tokens]))
    return embed, {dense: layer(dense) for dense in (True, False)}, highest(head)


def logits_at(cfg: dict, flat: dict, tokens, positions, mode=None):
    """Logits [B, K, V] at ``positions`` [B, K] of ``tokens`` [B, T], float32."""
    params = unflatten(flat)["params"]
    z = sizes(cfg)
    embed, layers, head = _programs(tuple(sorted(z.items())), mode)
    x = embed(params["embed"]["embedding"], tokens)
    for i in range(z["layers"]):
        x = layers[i < z["dense_layers"]](x, params[f"layer{i}"])
    return head(x, params["norm_f"], params["head"], positions)


shared.logits_at, shared.ROWS = logits_at, ROWS
check, served_gaps, shapes_for = shared.check, shared.served_gaps, shared.shapes_for
