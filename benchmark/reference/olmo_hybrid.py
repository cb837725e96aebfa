"""Plain reference for the Olmo-Hybrid block stack (``model_type:
olmo_hybrid``; allenai/Olmo-Hybrid-7B ``config.json``; Gated DeltaNet, Yang,
Kautz, Hatamizadeh, arXiv:2412.06464; OLMo 2's reordered norm and query/key
norm): per layer two residual branches with the norm on the branch's output,
``x <- x + RMSNorm(mixer(x))``, ``x <- x + RMSNorm(W_down(SiLU(W_gate x) *
W_up x))``; the mixer is the gated delta rule (``linear_attention``) or causal
softmax attention (``full_attention``); final RMSNorm, an untied head.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one full
forward over prompt + served tokens. The delta rule is the SEQUENTIAL
recurrence, a ``lax.scan`` over positions (no chunks, no WY transform): per
head ``S' = alpha_t S``, ``S = S' + beta_t (v_t - S' k_t) k_t^T``, ``o_t = S
q_t``. Attention is full and causal over a dense mask, one request at a time.
No cache, no paging, no state slots, no batching of steps. Imports nothing of
the program; weights are the benchmark's own seed-made arrays, upcast a layer
at a time, so the 4.1 B parameters never stand in float32 at once.

Departures from the equations of the two papers, each the configuration
file's ``assumed``: the reordered norm on BOTH kinds of layer; query/key
RMSNorm over the whole projection; ``rope_theta: null`` read as no position
term; ``beta = 2 sigmoid`` under ``linear_allow_neg_eigval``; L2-normalised
``q`` and ``k`` with ``1 / sqrt(d_k)`` on ``q``; the output gate and the
RMSNorm over ``d_v``. The program's kernels hold several projections side by
side (``q | k | v | gate``, ``b | a``, ``gate | up``); the reference reads
the same leaves and cuts them where the equations do.

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

ROWS = 4  # requests per reference block: [4, 1920, 22016] float32 is 0.68 GB

LINEAR, FULL = "linear_attention", "full_attention"


def _private_copy_of_sibling(stem: str):
    path = Path(__file__).with_name(f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}_for_olmo_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


shared = _private_copy_of_sibling("nemotron_h")
unflatten, _f32, _mm, _rms_norm, _silu = (
    shared.unflatten, shared._f32, shared._mm, shared._rms_norm, shared._silu)


def sizes(cfg: dict) -> dict:
    """The numbers the math reads, from a configuration file's keys."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "layer_types": tuple(cfg["layer_types"]), "d": d, "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]), "head_dim": d // heads,
        "l_heads": int(cfg["linear_num_key_heads"]), "dk": int(cfg["linear_key_head_dim"]),
        "dv": int(cfg["linear_value_head_dim"]), "conv": int(cfg["linear_conv_kernel_dim"]),
        "neg_eigval": bool(cfg["linear_allow_neg_eigval"]),
        "width": int(cfg["intermediate_size"]), "eps": float(cfg["rms_norm_eps"]),
    }


def deltanet_mixer(x, p, z, mode=None):
    """x: [B, T, D] (NOT normed: the norm is on the branch's output) -> [B, T, D]."""
    import jax
    import jax.numpy as jnp

    b, t, _ = x.shape
    heads, dk, dv, taps = z["l_heads"], z["dk"], z["dv"], z["conv"]
    kd, vd = heads * dk, heads * dv
    proj = _mm(x, p["qkvg"]["kernel"], mode)
    qkv, gate = proj[..., :2 * kd + vd], proj[..., 2 * kd + vd:]
    ba = _mm(x, p["ba"]["kernel"], mode)
    w = _f32(p["conv"]["kernel"])                                    # [K, C], depthwise
    padded = jnp.pad(qkv, ((0, 0), (taps - 1, 0), (0, 0)))
    act = _silu(sum(padded[:, j:j + t] * w[j] for j in range(taps)))
    q = act[..., :kd].reshape(b, t, heads, dk)
    k = act[..., kd:2 * kd].reshape(b, t, heads, dk)
    v = act[..., 2 * kd:].reshape(b, t, heads, dv)
    q = q / jnp.sqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / jnp.sqrt(float(dk))
    k = k / jnp.sqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    beta = jax.nn.sigmoid(ba[..., :heads]) * (2.0 if z["neg_eigval"] else 1.0)
    alpha = jnp.exp(-jnp.exp(_f32(p["A_log"])) * jax.nn.softplus(ba[..., heads:] + _f32(p["dt_bias"])))

    def one(s, inp):
        q_t, k_t, v_t, beta_t, alpha_t = inp                         # [B,H,dk] x2, [B,H,dv], [B,H] x2
        s = alpha_t[..., None, None] * s                             # S' = alpha S
        read = jnp.sum(s * k_t[:, :, None, :], axis=-1)              # S' k
        s = s + (beta_t[..., None] * (v_t - read))[..., None] * k_t[:, :, None, :]
        return s, jnp.sum(s * q_t[:, :, None, :], axis=-1)           # o = S q

    seq = lambda a: jnp.moveaxis(a, 1, 0)
    _, o = jax.lax.scan(one, jnp.zeros((b, heads, dv, dk), jnp.float32),
                        (seq(q), seq(k), seq(v), seq(beta), seq(alpha)))
    o = jnp.moveaxis(o, 0, 1)                                        # [B, T, H, dv]
    o = o / jnp.sqrt(jnp.mean(o * o, axis=-1, keepdims=True) + z["eps"]) * _f32(p["o_norm"]["scale"])
    return _mm(o.reshape(b, t, vd) * _silu(gate), p["out"]["kernel"], mode)


def attention_mixer(x, p, z, mode=None):
    import jax
    import jax.numpy as jnp

    b, t, _ = x.shape
    heads, kv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    q = _rms_norm(_mm(x, p["query"]["kernel"], mode), p["q_norm"]["scale"], z["eps"])
    k = _rms_norm(_mm(x, p["key"]["kernel"], mode), p["k_norm"]["scale"], z["eps"])
    v = _mm(x, p["value"]["kernel"], mode)
    q, k, v = q.reshape(b, t, heads, dh), k.reshape(b, t, kv, dh), v.reshape(b, t, kv, dh)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one(row):                                                    # a [H, T, T] mask at a time
        q1, k1, v1 = row
        scores = jnp.einsum("qhd,khd->hqk", q1, k1) / jnp.sqrt(float(dh))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v1)

    att = jax.lax.map(one, (q, k, v)).reshape(b, t, heads * dh)
    return _mm(att, p["out"]["kernel"], mode)


def gated_mlp(x, p, z, mode=None):
    both = _mm(x, p["gate_up"]["kernel"], mode)
    return _mm(_silu(both[..., :z["width"]]) * both[..., z["width"]:], p["down"]["kernel"], mode)


MIXERS = {LINEAR: ("deltanet", deltanet_mixer), FULL: ("attn", attention_mixer)}


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

    def layer(kind):
        name, mixer = MIXERS[kind]

        def run(x, p):
            x = x + _rms_norm(mixer(x, p[name], z, mode), p["mixer_norm"]["scale"], z["eps"])
            return x + _rms_norm(gated_mlp(x, p["mlp"], z, mode), p["mlp_norm"]["scale"], z["eps"])

        return highest(run)

    def head(x, norm_f, head_p, positions):
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)       # [B, K, D]
        return _mm(_rms_norm(x, norm_f["scale"], z["eps"]), head_p["kernel"], mode)

    embed = highest(lambda table, tokens: _f32(table[tokens]))
    return embed, {kind: layer(kind) for kind in MIXERS}, highest(head)


def logits_at(cfg: dict, flat: dict, tokens, positions, mode=None):
    """Logits [B, K, V] at ``positions`` [B, K] of ``tokens`` [B, T], float32."""
    params = unflatten(flat)["params"]
    z = sizes(cfg)
    embed, layers, head = _programs(tuple(sorted(z.items())), mode)
    x = embed(params["embed"]["embedding"], tokens)
    for i, kind in enumerate(z["layer_types"]):
        x = layers[kind](x, params[f"layer{i}"])
    return head(x, params["norm_f"], params["head"], positions)


shared.logits_at, shared.ROWS = logits_at, ROWS
check, served_gaps, shapes_for = shared.check, shared.served_gaps, shared.shapes_for
