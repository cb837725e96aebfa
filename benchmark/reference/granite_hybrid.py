"""Plain reference for the Granite-4.0-H block stack (``model_type:
granitemoehybrid`` without experts; ibm-granite/granite-4.0-h-micro
``config.json``; Mamba-2, arXiv:2405.21060): per layer two pre-norm
branches, each scaled by ``residual_multiplier`` before its residual add,
``h = x + r mixer(RMSNorm(x))``, ``x' = h + r W_out(SiLU(g) * u)`` with ``g |
u = W_in RMSNorm(h)``; the mixer is Mamba-2 (``mamba``) or causal
grouped-query attention with no position term (``attention``); ``x_0 =
embedding_multiplier E[token]``; logits ``RMSNorm(x) E^T / logits_scaling``.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one full
forward over prompt + served tokens, the multipliers written out where the
equations put them: the scores are ``q . k * attention_multiplier``, over a
dense causal mask, one request at a time. The Mamba-2 mixer is
``reference/nemotron_h.py``'s SEQUENTIAL recurrence (a ``lax.scan`` over
positions, no chunks), taken from a private copy of that module. No cache,
no paging, no state slots, no batching of steps. Imports nothing of the
program; weights are the benchmark's own seed-made arrays, upcast a layer at
a time, so the 3.19 B parameters never stand in float32 at once.

Departures from the published modelling code, each the configuration file's
``assumed``: the program's kernels hold ``q | k | v`` and the MLP's ``g | u``
side by side and the reference cuts them where the equations do.

``check``, ``served_gaps`` and ``shapes_for`` are ``reference/nemotron_h.py``'s
(the gap by which the served token's logit lies below the reference's best,
over every served token of the sampled requests). They look ``logits_at`` and
``ROWS`` up in their own module, so the private copy is given this file's.
"""

from __future__ import annotations

import functools
import importlib.util
from pathlib import Path

#: Requests per reference block: [4, 2688, 16384] float32 is 0.70 GB, the
#: head's [4, 639, 100352] 1.03 GB.
ROWS = 4

MAMBA, ATTENTION = "mamba", "attention"


def _private_copy_of_sibling(stem: str):
    path = Path(__file__).with_name(f"{stem}.py")
    spec = importlib.util.spec_from_file_location(f"bench_reference_{stem}_for_granite_hybrid", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


shared = _private_copy_of_sibling("nemotron_h")
unflatten, _f32, _mm, _rms_norm, _silu = (
    shared.unflatten, shared._f32, shared._mm, shared._rms_norm, shared._silu)


def sizes(cfg: dict) -> dict:
    """The numbers the math reads, from a configuration file's keys (under
    the names ``reference/nemotron_h``'s Mamba-2 mixer reads)."""
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return {
        "layer_types": tuple(cfg["layer_types"]), "d": d, "heads": heads,
        "kv_heads": int(cfg["num_key_value_heads"]), "head_dim": d // heads,
        "m_heads": int(cfg["mamba_n_heads"]), "m_head_dim": int(cfg["mamba_d_head"]),
        "groups": int(cfg["mamba_n_groups"]), "state": int(cfg["mamba_d_state"]),
        "conv": int(cfg["mamba_d_conv"]), "width": int(cfg["shared_intermediate_size"]),
        "eps": float(cfg["rms_norm_eps"]),
        "embedding_multiplier": float(cfg["embedding_multiplier"]),
        "residual_multiplier": float(cfg["residual_multiplier"]),
        "attention_multiplier": float(cfg["attention_multiplier"]),
        "logits_scaling": float(cfg["logits_scaling"]),
    }


def attention_mixer(u, p, z, mode=None):
    """u: [B, T, D] (normed) -> [B, T, D]; scores ``q . k * attention_multiplier``."""
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    heads, kv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    qkv = _mm(u, p["qkv"]["kernel"], mode)
    q = qkv[..., :heads * dh].reshape(b, t, heads, dh)
    k = qkv[..., heads * dh:(heads + kv) * dh].reshape(b, t, kv, dh)
    v = qkv[..., (heads + kv) * dh:].reshape(b, t, kv, dh)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]

    def one(row):                                                    # a [H, T, T] mask at a time
        q1, k1, v1 = row
        scores = jnp.einsum("qhd,khd->hqk", q1, k1) * z["attention_multiplier"]
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, v1)

    att = jax.lax.map(one, (q, k, v)).reshape(b, t, heads * dh)
    return _mm(att, p["out"]["kernel"], mode)


def gated_mlp(u, p, z, mode=None):
    both = _mm(u, p["gate_up"]["kernel"], mode)
    return _mm(_silu(both[..., :z["width"]]) * both[..., z["width"]:], p["down"]["kernel"], mode)


MIXERS = {MAMBA: ("mamba", shared.mamba_mixer), ATTENTION: ("attn", attention_mixer)}


@functools.lru_cache(maxsize=None)
def _programs(frozen, mode):
    import jax
    import jax.numpy as jnp

    z = dict(frozen)
    r = z["residual_multiplier"]

    def highest(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    def layer(kind):
        name, mixer = MIXERS[kind]

        def run(x, p):
            h = x + r * mixer(_rms_norm(x, p["input_norm"]["scale"], z["eps"]), p[name], z, mode)
            return h + r * gated_mlp(_rms_norm(h, p["mlp_norm"]["scale"], z["eps"]), p["mlp"], z, mode)

        return highest(run)

    def head(x, norm_f, table, positions):
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)       # [B, K, D]
        return _mm(_rms_norm(x, norm_f["scale"], z["eps"]), table.T, mode) / z["logits_scaling"]

    embed = highest(lambda table, tokens: _f32(table[tokens]) * z["embedding_multiplier"])
    return embed, {kind: layer(kind) for kind in MIXERS}, highest(head)


def logits_at(cfg: dict, flat: dict, tokens, positions, mode=None):
    """Logits [B, K, V] at ``positions`` [B, K] of ``tokens`` [B, T], float32."""
    params = unflatten(flat)["params"]
    z = sizes(cfg)
    embed, layers, head = _programs(tuple(sorted(z.items())), mode)
    x = embed(params["embed"]["embedding"], tokens)
    for i, kind in enumerate(z["layer_types"]):
        x = layers[kind](x, params[f"layer{i}"])
    return head(x, params["norm_f"], params["embed"]["embedding"], positions)


shared.logits_at, shared.ROWS = logits_at, ROWS
check, served_gaps, shapes_for = shared.check, shared.served_gaps, shared.shapes_for
