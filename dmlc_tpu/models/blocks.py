"""The blocks the model families behind the generation engine's seam share,
each defined once: ``models/nemotron_h``, ``olmo_hybrid``, ``lfm2_moe``,
``deepseek_v3`` and ``granite_hybrid`` import them from here and never from
one another. What differs between the families stays in each family's file:
its config, its parameter tree, its mixers, its ``_qkv`` and its walk over
the layer pattern.

The cached attention sublayer is the cache write and the attention over it
followed by the out projection; each family projects its own ``q``, ``k``,
``v`` (rotary, query/key norms, a turned ``q``) and hands them to
``attend_prefill`` / ``attend_decode`` inside its own ``jax.named_scope``.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp


def layer_index(kinds: Sequence[str], kind: str) -> dict[int, int]:
    """``{layer: i}``: the ``i``-th layer of kind ``kind`` in the stack
    ``kinds`` is layer ``layer`` (in order, so ``list(...)`` is the layers)."""
    layers = [n for n, k in enumerate(kinds) if k == kind]
    return {layer: i for i, layer in enumerate(layers)}


def rms_norm(x: Any, scale: Any, eps: float) -> Any:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary positions
# ---------------------------------------------------------------------------


def rotary_tables(positions: Any, head_dim: int, theta: float) -> tuple[Any, Any]:
    """``positions`` [..] int -> (cos, sin) [.., head_dim / 2] float32: pair
    ``i`` of a head turns by ``pos * theta^(-2i / head_dim)``."""
    half = head_dim // 2
    inv_freq = jnp.exp(jnp.arange(half, dtype=jnp.float32) * (-2.0 * math.log(theta) / head_dim))
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angle), jnp.sin(angle)


def apply_rotary(x: Any, cos: Any, sin: Any) -> Any:
    """x [.., heads, head_dim]; cos, sin [.., head_dim / 2] (one row a
    position). Lanes ``i`` and ``i + head_dim/2`` are one pair: ``(a, b) ->
    (a cos - b sin, b cos + a sin)``, in float32, back in x's type."""
    half = x.shape[-1] // 2
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    cos, sin = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# grouped-query attention and the cached attention sublayer
# ---------------------------------------------------------------------------


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


def attend_prefill(kv: Any, index: int, q: Any, k: Any, v: Any, out_kernel: Any) -> Any:
    """One prompt's attention layer ``index``: its K/V into the pages, full
    causal attention over the prompt, the out projection. -> [S, D]."""
    kv.write_prefill(index, k, v)
    att = gqa_causal_attention(q, k, v)
    return att.reshape(att.shape[0], -1) @ out_kernel


def attend_decode(kv: Any, index: int, q: Any, k: Any, v: Any, out_kernel: Any) -> Any:
    """One token per slot at attention layer ``index``: the new K/V appended
    and attended with the cache, the out projection. -> [B, D]."""
    att = kv.write_attend(index, q, k, v)
    return att.reshape(att.shape[0], -1) @ out_kernel


# ---------------------------------------------------------------------------
# the gated feed-forwards and what an expert layer reports
# ---------------------------------------------------------------------------


def gated_mlp(p: Any, x: Any) -> Any:
    """``W_down (SiLU(x W_gate) * x W_up)`` over the fused ``gate_up`` kernel."""
    both = x @ p["gate_up"]["kernel"]
    width = both.shape[-1] // 2
    return (jax.nn.silu(both[..., :width]) * both[..., width:]) @ p["down"]["kernel"]


def gated_expert(h: Any) -> Any:
    """The expert's form ``held_experts_ffn`` is handed: ``h = x (W_1 | W_3)``
    -> ``silu(x W_1) * x W_3``."""
    width = h.shape[-1] // 2
    return jax.nn.silu(h[..., :width]) * h[..., width:]


def expert_aux(counts: list) -> dict:
    """A program's ``aux``: the expert layers' pair counts, one row a layer."""
    return {"expert_counts": jnp.stack(counts)} if counts else {}


def expert_attrs(counts: Any, rows: int, top_k: int, expert_layers: int) -> dict:
    """What one program run did in the expert layers, from the counts it
    returned (NumPy, ``[expert layers, held]``) and the rows that held a
    token: attributes of ``gen/step`` / ``gen/prefill``."""
    pairs = int(counts.sum())
    return {
        "expert_pairs": pairs,
        # Pairs whose expert lives on another chip: what the exchange would carry.
        "expert_pairs_absent": rows * top_k * expert_layers - pairs,
        "experts_hit": float((counts > 0).sum(axis=1).mean()),
        "expert_rows_max": int(counts.max()),
    }
