"""The Olmo-Hybrid family: gated delta-rule linear attention 3:1 with full
softmax attention (``model_type: olmo_hybrid``).

``layer_types`` names the mixer of each layer: ``linear_attention`` is a
Gated DeltaNet mixer (Yang, Kautz, Hatamizadeh, arXiv:2412.06464),
``full_attention`` causal softmax attention with RMSNorm on the query and key
projections and no position term (``rope_theta`` is null in the source). A
layer has TWO residual branches with the norm on the branch's output (OLMo
2's reordered norm): ``x <- x + RMSNorm(mixer(x))``, ``x <- x +
RMSNorm(MLP(x))``, ``MLP(x) = W_down(SiLU(W_gate x) * W_up x)``; final
RMSNorm, untied head. Like ``models/nemotron_h`` this file owns the math and
nothing of serving: a config read from the published keys, the parameter
tree, and per layer kind *prefill over a padded prompt* and *one decode
step* over explicit state, reached by the generation engine through
``OlmoHybridFamily.prefill`` / ``.decode``.

The delta rule, per token ``x_t`` and head (``d_k`` key lanes, ``d_v`` value
lanes): ``q, k, v = SiLU(conv(W x))`` (depthwise causal conv over the
``q | k | v`` channels), ``q <- q / |q| / sqrt(d_k)``, ``k <- k / |k|``,
``beta_t = sigmoid(w_b x_t)`` (times 2 under ``linear_allow_neg_eigval``),
``alpha_t = exp(-exp(A_log) softplus(w_a x_t + dt_bias))``; the state ``S``
in ``R^{d_v x d_k}``, float32: ``S' = alpha_t S_{t-1}``, ``S_t = S' + beta_t
(v_t - S' k_t) k_t^T``, ``o_t = S_t q_t``; the mixer's output is ``W_o [
RMSNorm_{d_v}(o_t) * SiLU(W_g x_t) ]``.

State a slot carries between steps (docs/GENERATE.md, "Two kinds of per-slot
state"): per full-attention layer its K/V in pages (the engine's pools); per
linear layer the last ``conv - 1`` pre-activation rows of the ``q | k | v``
channels (the three conv windows side by side in one array) and the matrix
state ``S`` ``[heads, d_v, d_k]`` in float32.

Prefill runs the chunked form of the recurrence (``CHUNK`` positions: inside
a chunk the updates compose through one unit-lower-triangular solve, the
WY/UT transform; across chunks two matmuls against the carried state);
decode the one-step recurrence; tests pin both to the sequential definition.
Padded prefill is exact: padding sits at the END, and at padded positions
``beta = 0`` and ``alpha = 1``, where ``S_t = S_{t-1}``, so the state after
the padded scan IS the state after position ``length - 1``; the conv windows
are rows ``length-3 .. length-1``.

Not built, by mechanism: rotary positions (a config whose ``rope_theta`` is
not null is refused), value heads that outnumber key heads in a linear layer.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from dmlc_tpu.models.blocks import attend_decode, attend_prefill, gated_mlp, layer_index, rms_norm

HIGHEST = jax.lax.Precision.HIGHEST

#: Positions a chunk of the delta rule's prefill: the triangular solve is
#: CHUNK x CHUNK a head, and CHUNK positions go to the carried state at once.
CHUNK = 64

LINEAR, FULL = "linear_attention", "full_attention"


@dataclass(frozen=True)
class OlmoHybridConfig:
    """The published keys this family reads, plus ``max_len`` (the serving
    length)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    linear_num_key_heads: int
    linear_num_value_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    linear_conv_kernel_dim: int
    linear_allow_neg_eigval: bool = True
    rms_norm_eps: float = 1e-6
    max_len: int = 2048

    def __post_init__(self) -> None:
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        if not self.layer_types or set(self.layer_types) - {LINEAR, FULL}:
            raise ValueError(f"layer_types {self.layer_types!r}: each {LINEAR!r} or {FULL!r}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide into num_attention_heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("query heads must divide into their KV heads")
        if self.linear_num_value_heads != self.linear_num_key_heads:
            raise ValueError("a linear layer with more value heads than key heads is not built")

    @classmethod
    def from_published(cls, cfg: dict, **overrides: Any) -> "OlmoHybridConfig":
        """From a ``config.json``-shaped dict: every field of this class the
        dict names is taken, ``overrides`` win. What the dict says of a
        mechanism this family does not build is refused, not ignored."""
        picked = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        picked.update(overrides)
        config = cls(**picked)
        depth = cfg.get("num_hidden_layers", len(config.layer_types))
        if depth != len(config.layer_types):
            raise ValueError(f"num_hidden_layers {depth} but {len(config.layer_types)} layer_types")
        if cfg.get("hidden_act", "silu") != "silu":
            raise ValueError(f"hidden_act {cfg['hidden_act']!r}: only 'silu' is built")
        if (cfg.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise ValueError("rotary positions are not built (rope_theta must be null)")
        return config

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the causal conv runs over: ``q | k | v``."""
        return 2 * self.key_dim + self.value_dim

    def layers_of(self, kind: str) -> list[int]:
        return list(layer_index(self.layer_types, kind))


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------


def param_shapes(cfg: OlmoHybridConfig) -> dict:
    """Nested {name: shape} of the family's parameters; no bias anywhere.
    Projections that read the same input share one kernel (``q | k | v |
    gate``, ``b | a``, ``gate | up``), every boundary on a multiple of 128
    lanes at the published widths."""
    d, heads = cfg.hidden_size, cfg.linear_num_key_heads
    q_dim = cfg.num_attention_heads * cfg.head_dim
    kv_dim = cfg.num_key_value_heads * cfg.head_dim
    tree: dict = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i, kind in enumerate(cfg.layer_types):
        if kind == LINEAR:
            mixer = {"deltanet": {
                "qkvg": {"kernel": (d, cfg.conv_dim + cfg.value_dim)},
                "ba": {"kernel": (d, 2 * heads)},
                "conv": {"kernel": (cfg.linear_conv_kernel_dim, cfg.conv_dim)},
                "A_log": (heads,), "dt_bias": (heads,),
                "o_norm": {"scale": (cfg.linear_value_head_dim,)},
                "out": {"kernel": (cfg.value_dim, d)},
            }}
        else:
            mixer = {"attn": {
                "query": {"kernel": (d, q_dim)}, "key": {"kernel": (d, kv_dim)},
                "value": {"kernel": (d, kv_dim)},
                "q_norm": {"scale": (q_dim,)}, "k_norm": {"scale": (kv_dim,)},
                "out": {"kernel": (q_dim, d)},
            }}
        tree[f"layer{i}"] = {
            **mixer, "mixer_norm": {"scale": (d,)},
            "mlp": {"gate_up": {"kernel": (d, 2 * cfg.intermediate_size)},
                    "down": {"kernel": (cfg.intermediate_size, d)}},
            "mlp_norm": {"scale": (d,)},
        }
    tree["norm_f"] = {"scale": (d,)}
    tree["head"] = {"kernel": (d, cfg.vocab_size)}
    return tree


def _leaf_mean_std(path: str, depth: int) -> tuple[float, float]:
    """Seed init (a served configuration brings its own table: the
    benchmark's is in its configuration file). Under the reordered norm a
    branch's weight in the residual stream is its norm's scale, whatever its
    kernels' spread: 0.15 keeps the current token the larger part of the
    stream, so greedy streams of seed-drawn weights stay distinct."""
    if path.endswith(("mixer_norm/scale", "mlp_norm/scale")):
        return 0.15, 0.01
    if path.endswith("scale"):
        return 1.0, 0.05
    if path.endswith("A_log"):
        return 0.0, 0.5
    if path.endswith("dt_bias"):
        return -4.0, 0.5
    if path.endswith("conv/kernel"):
        return 0.0, 0.3
    if path.endswith("ba/kernel"):
        return 0.0, 0.005
    if path.endswith("embed/embedding"):
        return 0.0, 1.0
    if path.endswith(("out/kernel", "down/kernel")):
        return 0.0, 0.02 / (2.0 * depth) ** 0.5
    return 0.0, 0.02


# ---------------------------------------------------------------------------
# linear_attention: the gated delta rule
# ---------------------------------------------------------------------------


def _causal_conv_silu(rows: Any, kernel: Any, out_rows: int, dtype: Any) -> Any:
    """``rows`` [.., out_rows + K - 1, C] (the K - 1 rows before the first
    output row in front) -> SiLU of the depthwise causal conv, [.., out_rows, C]."""
    w = kernel.astype(jnp.float32)
    conv = sum(rows[..., j:j + out_rows, :].astype(jnp.float32) * w[j] for j in range(w.shape[0]))
    return jax.nn.silu(conv).astype(dtype)


def _heads_qkv(cfg: OlmoHybridConfig, act: Any) -> tuple[Any, Any, Any]:
    """Activated ``q | k | v`` channels -> q, k [.., H, d_k] (L2-normalised,
    q scaled by ``1 / sqrt(d_k)``) and v [.., H, d_v], float32."""
    lead, heads, dk = act.shape[:-1], cfg.linear_num_key_heads, cfg.linear_key_head_dim
    act = act.astype(jnp.float32)
    q = act[..., :cfg.key_dim].reshape(*lead, heads, dk)
    k = act[..., cfg.key_dim:2 * cfg.key_dim].reshape(*lead, heads, dk)
    v = act[..., 2 * cfg.key_dim:].reshape(*lead, heads, cfg.linear_value_head_dim)
    unit = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
    return unit(q) * dk ** -0.5, unit(k), v


def _beta_and_log_alpha(p: Any, cfg: OlmoHybridConfig, ba: Any) -> tuple[Any, Any]:
    """``ba`` [.., 2H] = ``w_b x | w_a x`` -> beta in (0, 1), or (0, 2) where
    negative eigenvalues are allowed, and ``log alpha`` <= 0, both [.., H]."""
    heads = cfg.linear_num_key_heads
    ba = ba.astype(jnp.float32)
    beta = jax.nn.sigmoid(ba[..., :heads]) * (2.0 if cfg.linear_allow_neg_eigval else 1.0)
    log_alpha = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        ba[..., heads:] + p["dt_bias"].astype(jnp.float32))
    return beta, log_alpha


def _gated_head_norm(o: Any, gate: Any, scale: Any, eps: float) -> Any:
    """``RMSNorm over d_v of o, times SiLU(gate)``. o [.., H, d_v] float32;
    gate [.., H * d_v] -> [.., H * d_v] float32."""
    normed = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + eps)
    normed = normed * scale.astype(jnp.float32)
    return normed.reshape(gate.shape) * jax.nn.silu(gate.astype(jnp.float32))


def solve_unit_lower(below: Any, rhs: Any, block: int = 16) -> Any:
    """``X`` with ``(I + below) X = rhs``; ``below`` [.., L, L] strictly lower
    triangular, ``rhs`` [.., L, D]. Forward substitution by blocks of
    ``block`` rows: the diagonal blocks are inverted row by row (``block``
    short steps, every block of every chunk and head at once), then block
    row b is ``inv_b (rhs_b - below[b, :b] X[:b])``, ``L / block`` matmuls
    deep. (XLA's own triangular solve runs as one custom call a layer that
    took a third of a prefill on the chip: PERF.md, PR 31.)"""
    size = below.shape[-1]
    block = block if size % block == 0 else size
    starts = range(0, size, block)
    diag = jnp.stack([below[..., b:b + block, b:b + block] for b in starts], axis=-3)
    eye = jnp.eye(block, dtype=below.dtype)
    rows = [jnp.broadcast_to(eye[0], diag.shape[:-2] + (block,))]
    for i in range(1, block):      # row i of (I + diag)^-1: e_i - diag[i, :i] @ rows[:i]
        rows.append(eye[i] - jnp.einsum("...j,...jk->...k", diag[..., i, :i],
                                        jnp.stack(rows, axis=-2), precision=HIGHEST))
    inverse = jnp.stack(rows, axis=-2)                            # [.., L / block, block, block]
    solved: list = []
    for n, b in enumerate(starts):
        r = rhs[..., b:b + block, :]
        if solved:
            r = r - jnp.einsum("...ij,...jd->...id", below[..., b:b + block, :b],
                               jnp.concatenate(solved, axis=-2), precision=HIGHEST)
        solved.append(jnp.einsum("...ij,...jd->...id", inverse[..., n, :, :], r,
                                 precision=HIGHEST))
    return jnp.concatenate(solved, axis=-2)


def delta_rule_chunked(q: Any, k: Any, v: Any, beta: Any, log_alpha: Any,
                       chunk: int) -> tuple[Any, Any]:
    """The chunked form of ``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) +
    beta_t v_t k_t^T``, ``o_t = S_t q_t`` from ``S_{-1} = 0``. q, k [S, H,
    d_k]; v [S, H, d_v]; beta, log_alpha [S, H], all float32; S a multiple of
    ``chunk``. Returns (o [S, H, d_v], S after the last position [H, d_v, d_k]).

    Inside a chunk that enters with state ``S_0``, with ``g_i`` the running
    sum of ``log alpha`` and ``u_i = beta_i (v_i - alpha_i S_{i-1} k_i)``:
    ``S_i = e^{g_i} S_0 + sum_{j<=i} e^{g_i-g_j} u_j k_j^T``, so ``(I + B) U =
    diag(beta) (V - diag(e^g) K S_0^T)`` with ``B_ij = beta_i e^{g_i-g_j} k_i.k_j``
    below the diagonal: one unit-lower-triangular solve a chunk gives ``U =
    W_v - W_k S_0^T``, and the chunk's outputs and the state it leaves are
    matmuls of ``U`` against ``S_0``, which is the one sequential part."""
    s, heads, dk = q.shape
    dv = v.shape[-1]
    nc = s // chunk
    by_chunk = lambda t: jnp.moveaxis(t.reshape(nc, chunk, heads, -1), 2, 1)   # [c, H, L, .]
    q, k, v = by_chunk(q), by_chunk(k), by_chunk(v)
    beta = by_chunk(beta)                                        # [c, H, L, 1]
    cum = jnp.cumsum(by_chunk(log_alpha)[..., 0], axis=-1)        # [c, H, L], inclusive
    # Position i reads j <= i with decay e^{g_i - g_j} <= 1.
    decay = jnp.exp(jnp.minimum(cum[..., :, None] - cum[..., None, :], 0.0))
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    kk = jnp.einsum("chid,chjd->chij", k, k, precision=HIGHEST)
    below = jnp.where(row > col, beta * kk * decay, 0.0)
    rhs = jnp.concatenate([beta * v, beta * jnp.exp(cum)[..., None] * k], axis=-1)
    solved = solve_unit_lower(below, rhs)
    w_v, w_k = solved[..., :dv], solved[..., dv:]
    qk = jnp.einsum("chid,chjd->chij", q, k, precision=HIGHEST)
    attend = jnp.where(row >= col, qk * decay, 0.0)
    q_in = q * jnp.exp(cum)[..., None]                          # reads the entering state
    k_out = k * jnp.exp(cum[..., -1:] - cum)[..., None]         # what reaches the chunk's end
    through = jnp.exp(cum[..., -1])                              # [c, H]

    def carry(state, inp):
        w_v, w_k, attend, q_in, k_out, through = inp
        u = w_v - jnp.einsum("hld,hvd->hlv", w_k, state, precision=HIGHEST)
        o = (jnp.einsum("hld,hvd->hlv", q_in, state, precision=HIGHEST)
             + jnp.einsum("hlj,hjv->hlv", attend, u, precision=HIGHEST))
        state = (through[:, None, None] * state
                 + jnp.einsum("hlv,hld->hvd", u, k_out, precision=HIGHEST))
        return state, o

    state, o = jax.lax.scan(carry, jnp.zeros((heads, dv, dk), jnp.float32),
                            (w_v, w_k, attend, q_in, k_out, through))
    return jnp.moveaxis(o, 1, 2).reshape(s, heads, dv), state


def deltanet_prefill(p: Any, cfg: OlmoHybridConfig, x: Any, length: Any) -> tuple[Any, Any, Any]:
    """One prompt, padded at the end. x: [S, D]. Returns (out [S, D], the
    conv windows [K-1, conv_dim] = the pre-activation ``q | k | v`` rows
    ``length-K+1 .. length-1``, S after position ``length - 1`` [H, d_v, d_k])."""
    s, taps = x.shape[0], cfg.linear_conv_kernel_dim
    proj = x @ p["qkvg"]["kernel"]
    qkv, gate = proj[:, :cfg.conv_dim], proj[:, cfg.conv_dim:]
    padded = jnp.concatenate([jnp.zeros((taps - 1, cfg.conv_dim), qkv.dtype), qkv], axis=0)
    window = jax.lax.dynamic_slice_in_dim(padded, length, taps - 1, axis=0)
    q, k, v = _heads_qkv(cfg, _causal_conv_silu(padded, p["conv"]["kernel"], s, x.dtype))
    beta, log_alpha = _beta_and_log_alpha(p, cfg, x @ p["ba"]["kernel"])
    real = (jnp.arange(s) < length)[:, None]
    beta = jnp.where(real, beta, 0.0)             # padding writes nothing ...
    log_alpha = jnp.where(real, log_alpha, 0.0)   # ... and carries S unchanged
    pad = -s % CHUNK
    if pad:
        q, k, v, beta, log_alpha = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1))
                                    for t in (q, k, v, beta, log_alpha))
    o, state = delta_rule_chunked(q, k, v, beta, log_alpha, CHUNK)
    y = _gated_head_norm(o[:s], gate, p["o_norm"]["scale"], cfg.rms_norm_eps)
    return y.astype(x.dtype) @ p["out"]["kernel"], window, state


def deltanet_decode(p: Any, cfg: OlmoHybridConfig, x: Any, window: Any, state: Any,
                    active: Any) -> tuple[Any, Any, Any]:
    """One token per slot. x: [B, D]; window [B, K-1, conv_dim]; state
    [B, H, d_v, d_k] float32; rows with ``active`` false keep their state."""
    proj = x @ p["qkvg"]["kernel"]
    qkv, gate = proj[:, :cfg.conv_dim], proj[:, cfg.conv_dim:]
    full = jnp.concatenate([window, qkv[:, None].astype(window.dtype)], axis=1)   # [B, K, C]
    q, k, v = _heads_qkv(cfg, _causal_conv_silu(full, p["conv"]["kernel"], 1, x.dtype)[:, 0])
    beta, log_alpha = _beta_and_log_alpha(p, cfg, x @ p["ba"]["kernel"])
    alpha = jnp.exp(log_alpha)[..., None]                                # [B, H, 1]
    # Elementwise in float32 on purpose: a dot would round S to the MXU's
    # inputs. ``S k`` and ``S q`` come from ONE pass over S: with u = beta (v
    # - alpha S k), the new state is alpha S + u k^T and its read alpha S q
    # + u (k . q), so S is read twice and written once a step.
    s_k = jnp.sum(state * k[:, :, None, :], axis=-1)                     # [B, H, d_v]
    s_q = jnp.sum(state * q[:, :, None, :], axis=-1)
    u = beta[..., None] * (v - alpha * s_k)
    new = alpha[..., None] * state + u[..., None] * k[:, :, None, :]
    o = alpha * s_q + u * jnp.sum(k * q, axis=-1, keepdims=True)
    y = _gated_head_norm(o, gate, p["o_norm"]["scale"], cfg.rms_norm_eps)
    keep = active[:, None, None]
    return (y.astype(x.dtype) @ p["out"]["kernel"],
            jnp.where(keep, full[:, 1:], window),
            jnp.where(keep[..., None], new, state))


# ---------------------------------------------------------------------------
# full_attention: query/key norm, no positions
# ---------------------------------------------------------------------------


def _qkv(p: Any, cfg: OlmoHybridConfig, x: Any) -> tuple[Any, Any, Any]:
    """RMSNorm over the WHOLE query and key projections, then heads."""
    lead = x.shape[:-1]
    q = rms_norm(x @ p["query"]["kernel"], p["q_norm"]["scale"], cfg.rms_norm_eps)
    k = rms_norm(x @ p["key"]["kernel"], p["k_norm"]["scale"], cfg.rms_norm_eps)
    v = x @ p["value"]["kernel"]
    return (q.reshape(*lead, cfg.num_attention_heads, cfg.head_dim),
            k.reshape(*lead, cfg.num_key_value_heads, cfg.head_dim),
            v.reshape(*lead, cfg.num_key_value_heads, cfg.head_dim))


# ---------------------------------------------------------------------------
# the two functions the engine calls
# ---------------------------------------------------------------------------


class OlmoHybridFamily:
    """The engine's view of one registered Olmo-Hybrid model (the seam of
    ``generate/engine.py``: ``prefill`` and ``decode`` over explicit state)."""

    def __init__(self, config: OlmoHybridConfig, dtype: Any) -> None:
        self.config = config
        self.dtype = dtype
        self.vocab = config.vocab_size
        self.max_len = config.max_len
        self._kv_index = layer_index(config.layer_types, FULL)
        self._linear_index = layer_index(config.layer_types, LINEAR)
        self.kv_layers = len(self._kv_index)
        self.kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.state_bytes_per_slot = sum(
            jnp.dtype(dtype).itemsize * math.prod(shape[1:])
            for leaves in self.state_shapes(1).values() for shape, dtype in leaves)

    def state_shapes(self, max_slots: int) -> dict:
        """The recurrent state beside the pages: per linear layer one array
        each, slot-indexed, so a step updates each in place."""
        cfg = self.config
        n = len(self._linear_index)
        return {
            "conv": [((max_slots, cfg.linear_conv_kernel_dim - 1, cfg.conv_dim), self.dtype)] * n,
            "delta": [((max_slots, cfg.linear_num_key_heads, cfg.linear_value_head_dim,
                        cfg.linear_key_head_dim), jnp.float32)] * n,
        }

    def _branches(self, p: Any, x: Any, mixed: Any) -> Any:
        """Both residual branches of a layer, the norm on each branch's output."""
        cfg = self.config
        x = x + rms_norm(mixed, p["mixer_norm"]["scale"], cfg.rms_norm_eps)
        with jax.named_scope("mlp"):
            return x + rms_norm(gated_mlp(p["mlp"], x), p["mlp_norm"]["scale"],
                                cfg.rms_norm_eps)

    def prefill(self, params: Any, tokens: Any, length: Any, slot: Any, kv: Any,
                state: Any) -> tuple[Any, Any, Any]:
        """tokens [1, S] padded at the end -> (logits at ``length - 1`` [V]
        float32, state with slot ``slot`` overwritten whole, aux)."""
        cfg = self.config
        x = params["embed"]["embedding"][tokens[0]].astype(self.dtype)
        conv, delta = list(state["conv"]), list(state["delta"])
        for i, kind in enumerate(cfg.layer_types):
            p = params[f"layer{i}"]
            if kind == LINEAR:
                with jax.named_scope("deltanet"):
                    mixed, window, s_last = deltanet_prefill(p["deltanet"], cfg, x, length)
                m = self._linear_index[i]
                conv[m] = conv[m].at[slot].set(window.astype(conv[m].dtype))
                delta[m] = delta[m].at[slot].set(s_last)
                # The slot's rows are written before the next layer starts: left to
                # the end they keep every layer's activations alive (1.6 GB of
                # temporaries at 12 linear layers against 0.6, compiled for a v5e).
                mixed, conv[m], delta[m] = jax.lax.optimization_barrier(
                    (mixed, conv[m], delta[m]))
            else:
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, x)
                    mixed = attend_prefill(kv, self._kv_index[i], q, k, v,
                                           p["attn"]["out"]["kernel"])
            x = self._branches(p, x, mixed)
        last = jnp.take(x, length - 1, axis=0)
        logits = rms_norm(last, params["norm_f"]["scale"], cfg.rms_norm_eps) @ params["head"]["kernel"]
        return logits.astype(jnp.float32), {"conv": conv, "delta": delta}, {}

    def decode(self, params: Any, tokens: Any, lengths: Any, active: Any, kv: Any,
               state: Any) -> tuple[Any, Any, Any]:
        """tokens [B] -> (logits [B, V] float32, state, aux)."""
        cfg = self.config
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        conv, delta = list(state["conv"]), list(state["delta"])
        for i, kind in enumerate(cfg.layer_types):
            p = params[f"layer{i}"]
            if kind == LINEAR:
                m = self._linear_index[i]
                with jax.named_scope("deltanet"):
                    mixed, conv[m], delta[m] = deltanet_decode(
                        p["deltanet"], cfg, x, conv[m], delta[m], active)
            else:
                with jax.named_scope("attn"):
                    q, k, v = _qkv(p["attn"], cfg, x)
                    mixed = attend_decode(kv, self._kv_index[i], q, k, v,
                                          p["attn"]["out"]["kernel"])
            x = self._branches(p, x, mixed)
        logits = rms_norm(x, params["norm_f"]["scale"], cfg.rms_norm_eps) @ params["head"]["kernel"]
        # No position term: ``lengths`` only says how much the attention read.
        read = jnp.sum(jnp.where(active, lengths + 1, 0))
        return logits.astype(jnp.float32), {"conv": conv, "delta": delta}, {"kv_tokens_read": read}

    def work_attrs(self, aux: dict, rows: int) -> dict:
        """The attributes of ``gen/step`` (``aux`` of ``decode``; ``rows``
        active slots, each reading and writing its state) and ``gen/prefill``
        (no ``aux``; ``rows`` prompt tokens, one slot's state written)."""
        out = {"linear_layers": len(self._linear_index)}
        if "kv_tokens_read" in aux:
            out.update(state_bytes_touched=2 * rows * self.state_bytes_per_slot,
                       kv_tokens_read=int(aux["kv_tokens_read"]))
        else:
            out.update(state_bytes_touched=self.state_bytes_per_slot, prompt_tokens=rows)
        return out


def register_olmo_hybrid(name: str, config: OlmoHybridConfig) -> Any:
    """Register ``config`` as a servable ``kind="lm"`` model called ``name``."""
    from dmlc_tpu.models import registry

    depth = len(config.layer_types)
    return registry.register_family(name, config, param_shapes(config),
                                    lambda path: _leaf_mean_std(path, depth), OlmoHybridFamily)


#: The CPU tests' preset: the published period ``L L L F`` twice, four heads
#: of every kind, key lanes half the value lanes as published.
OLMO_HYBRID_TINY = OlmoHybridConfig(
    vocab_size=256, hidden_size=64, intermediate_size=160,
    layer_types=(LINEAR, LINEAR, LINEAR, FULL) * 2,
    num_attention_heads=4, num_key_value_heads=4,
    linear_num_key_heads=4, linear_num_value_heads=4,
    linear_key_head_dim=8, linear_value_head_dim=16, linear_conv_kernel_dim=4,
    max_len=256)
