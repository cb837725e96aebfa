"""The DeepSeek-V3 family (``model_type: deepseek_v3``): latent attention
over ONE cached row a position, a leading dense layer, then gated experts
behind a sigmoid router beside shared experts that every token takes.

A layer is ``h = x + attn(RMSNorm(x))``, ``x' = h + ffn(RMSNorm(h))``; final
RMSNorm, an untied head. ``ffn`` is a gated SiLU MLP ``W_2 (silu(W_1 u) * W_3
u)`` in the first ``first_k_dense_replace`` layers and the expert layer in
the others. Like its siblings this file owns the math and nothing of
serving: a config read from the published keys, the parameter tree, and
*prefill over a padded prompt* and *one decode step* over explicit state,
reached by the generation engine through ``DeepseekV3Family.prefill`` /
``.decode``.

Latent attention, per token ``u`` (normed), ``H`` heads: ``q = W_q u`` in
heads of ``qk_nope_head_dim + qk_rope_head_dim`` lanes, ``q_nope | q_rope``;
``c | k_rope = W_kva u`` (``kv_lora_rank | qk_rope_head_dim``; ``k_rope`` is
ONE row for all heads); ``c <- RMSNorm(c)``; ``k_nope_h = W_uk_h c``, ``v_h =
W_uv_h c``; ``q_rope`` and ``k_rope`` turned at the token's position (the
tables and the pairing are ``models/blocks``': lane ``i`` of the rotary
lanes with lane ``i + d/2``; the published weights pair adjacent lanes and
the published code moves them to this order in every forward, which a
loader does once to the rotary columns of ``W_q`` and ``W_kva``: ``q . k``
sums over pairs and does not know their order); ``k_h = k_nope_h | k_rope``;
causal softmax of ``q_h . k_h / sqrt(qk_nope_head_dim + qk_rope_head_dim)``
in float32; ``o = W_o concat_h(p_h v_h)``.

What is cached, per token and layer: ``c`` after its norm and ``k_rope``
after its turn, ``kv_lora_rank + qk_rope_head_dim`` values for all heads
(``latent_row``: the engine then keeps one pool and no V pool,
generate/kvcache.py). **Prefill** writes that row for every prompt position
and attends in the expanded form above. **Decode** never expands: ``q_lat_h
= q_nope_h W_uk_h``, scores ``= (q_lat_h . c_t + q_rope_h . k_rope_t) *
scale``, ``o_h = (sum_t p_t c_t) W_uv_h`` (``kv.write_attend_latent``: the
heads' ``q_lat | q_rope`` against the rows as stored, the weighted sum over a
row's first ``kv_lora_rank`` lanes). The two are the same function.

The router: ``s = sigmoid(W_g u)`` in float32 over every expert; the
``num_experts_per_tok`` of largest ``s + b`` are chosen
(``e_score_correction_bias`` picks, it does not weigh); ``g = s[chosen] /
(sum + 1e-20)`` under ``norm_topk_prob``, times ``routed_scaling_factor``.
``experts_held = (first, count)`` names the routed experts that live on this
chip (expert parallelism: ``parallel/moe.held_experts_ffn``); what the others
would add is left out. The ``n_shared_experts`` shared experts are one gated
MLP of ``n_shared_experts * moe_intermediate_size``, ungated, added.

Not built, by mechanism (a config that asks for one is refused): a low-rank
query path (``q_lora_rank``), scaled rotary tables (``rope_scaling`` and its
``mscale``), a group-limited top-k over more than one group, a softmax
router, biased projections, an activation other than SiLU, expert layers at
a stride (``moe_layer_freq``), a tied head.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

from dmlc_tpu.models.blocks import (
    apply_rotary, expert_attrs, expert_aux, gated_expert, gated_mlp, rms_norm, rotary_tables)
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk

#: A cached row is stored on whole tiles of this many lanes (576 -> 640):
#: the chip's memory tiles a pool's trailing axis by 128 lanes whatever it is
#: told (a ``[.., 16, 576]`` bfloat16 pool IS 640-lane rows there), and the
#: chip's compiler refuses the decode kernel's copy of a 576-lane page
#: ("must be aligned to tiling (128)"). PERF.md, finding PR 37.1.
ROW_LANES = 128

#: Query rows of one block of a prefill's expanded attention: the float32
#: scores of a block are ``[heads, block, S]`` and not ``[heads, S, S]``.
PREFILL_QUERY_BLOCK = 512


@dataclass(frozen=True)
class DeepseekV3Config:
    """The published keys this family reads, plus ``experts_held`` (the cut
    of expert parallelism; ``None`` holds every expert) and ``max_len`` (the
    serving length)."""

    vocab_size: int
    hidden_size: int
    intermediate_size: int
    moe_intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    first_k_dense_replace: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-6
    experts_held: tuple[int, int] | None = None
    max_len: int = 2048

    def __post_init__(self) -> None:
        if self.qk_rope_head_dim % 2:
            raise ValueError("qk_rope_head_dim must be even: its lanes turn in pairs")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(f"first_k_dense_replace {self.first_k_dense_replace} outside the stack")
        if not 1 <= self.num_experts_per_tok <= self.n_routed_experts:
            raise ValueError("num_experts_per_tok must lie in 1..n_routed_experts")
        first, count = self.held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} outside 0..{self.n_routed_experts}")

    @classmethod
    def from_published(cls, cfg: dict, **overrides: Any) -> "DeepseekV3Config":
        """From a ``config.json``-shaped dict: every field of this class the
        dict names is taken, ``overrides`` win. What the dict says of a
        mechanism this family does not build is refused, not ignored."""
        picked = {k: cfg[k] for k in cls.__dataclass_fields__ if k in cfg}
        picked.update(overrides)
        if picked.get("experts_held") is not None:
            picked["experts_held"] = tuple(int(v) for v in picked["experts_held"])
        refused = {
            "q_lora_rank": (None, "a low-rank query path is not built"),
            "rope_scaling": (None, "scaled rotary tables (and their mscale) are not built"),
            "attention_bias": (False, "biased projections are not built"),
            "hidden_act": ("silu", "only 'silu' is built"),
            "scoring_func": ("sigmoid", "only the sigmoid router is built"),
            "topk_method": ("noaux_tc", "only the score-correction-bias router is built"),
            "n_group": (1, "a group-limited top-k over more than one group is not built"),
            "topk_group": (1, "a group-limited top-k over more than one group is not built"),
            "moe_layer_freq": (1, "expert layers at a stride are not built"),
            "tie_word_embeddings": (False, "a tied head is not built"),
        }
        for key, (built, why) in refused.items():
            if cfg.get(key, built) != built:
                raise ValueError(f"{key} {cfg[key]!r}: {why}")
        return cls(**picked)

    @property
    def held(self) -> tuple[int, int]:
        return self.experts_held if self.experts_held is not None else (0, self.n_routed_experts)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """What a token caches a layer: ``c | k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def expert_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace


# ---------------------------------------------------------------------------
# the parameter tree
# ---------------------------------------------------------------------------


def param_shapes(cfg: DeepseekV3Config) -> dict:
    """Nested {name: shape} of the family's parameters; no bias but the
    router's score-correction bias. ``q | c | k_rope`` read the same input
    and share one kernel; ``W_kvb`` is kept a head at a time in the two parts
    the absorbed decode multiplies by (``k_up``: ``c -> k_nope_h``, ``v_up``:
    ``c -> v_h``); ``W_1 | W_3`` of an MLP and of each expert side by side."""
    d, heads = cfg.hidden_size, cfg.num_attention_heads
    held = cfg.held[1]
    tree: dict = {"embed": {"embedding": (cfg.vocab_size, d)}}
    for i in range(cfg.num_hidden_layers):
        attn = {
            "q_kva": {"kernel": (d, heads * cfg.qk_head_dim + cfg.latent_dim)},
            "kv_norm": {"scale": (cfg.kv_lora_rank,)},
            "k_up": (heads, cfg.kv_lora_rank, cfg.qk_nope_head_dim),
            "v_up": (heads, cfg.kv_lora_rank, cfg.v_head_dim),
            "out": {"kernel": (heads * cfg.v_head_dim, d)},
        }
        if i < cfg.first_k_dense_replace:
            ffn = {"mlp": {"gate_up": {"kernel": (d, 2 * cfg.intermediate_size)},
                           "down": {"kernel": (cfg.intermediate_size, d)}}}
        else:
            shared = cfg.n_shared_experts * cfg.moe_intermediate_size
            ffn = {"moe": {
                "router": {"kernel": (d, cfg.n_routed_experts), "bias": (cfg.n_routed_experts,)},
                "experts": {"w13": (held, d, 2 * cfg.moe_intermediate_size),
                            "w2": (held, cfg.moe_intermediate_size, d)},
                "shared": {"gate_up": {"kernel": (d, 2 * shared)},
                           "down": {"kernel": (shared, d)}},
            }}
        tree[f"layer{i}"] = {"attn": attn, **ffn, "attn_norm": {"scale": (d,)},
                             "ffn_norm": {"scale": (d,)}}
    tree["norm_f"] = {"scale": (d,)}
    tree["head"] = {"kernel": (d, cfg.vocab_size)}
    return tree


def _leaf_mean_std(path: str, depth: int, width: int) -> tuple[float, float]:
    """Seed init (a served configuration brings its own table: the
    benchmark's is in its configuration file). The score-correction bias is
    drawn away from zero, so that choosing by ``s + b`` and weighing by ``s``
    differ; the embedding wide enough that the current token stays the
    larger part of the stream; the untied head ``1 / sqrt(width)``: logits of
    spread about 1 off a normed stream."""
    if path.endswith("head/kernel"):
        return 0.0, width ** -0.5
    if path.endswith("scale"):
        return 1.0, 0.05
    if path.endswith("router/bias"):
        return 0.0, 0.05
    if path.endswith("embed/embedding"):
        return 0.0, 1.0
    if path.endswith(("out/kernel", "down/kernel", "experts/w2")):
        return 0.0, 0.02 / (2.0 * depth) ** 0.5
    if path.endswith(("k_up", "v_up")):
        return 0.0, 0.05
    return 0.0, 0.02


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def project(p: Any, cfg: DeepseekV3Config, u: Any, cos: Any, sin: Any) -> tuple[Any, Any, Any]:
    """``u`` [.., D] (normed) -> (``q_nope`` [.., H, nope], ``q_rope`` [.., H,
    rope] turned, the row to cache [.., rank + rope]: ``c`` after its norm |
    ``k_rope`` after its turn). ``cos``, ``sin``: one row a row of ``u``."""
    lead, heads = u.shape[:-1], cfg.num_attention_heads
    both = u @ p["q_kva"]["kernel"]
    q = both[..., :heads * cfg.qk_head_dim].reshape(*lead, heads, cfg.qk_head_dim)
    c = both[..., heads * cfg.qk_head_dim:heads * cfg.qk_head_dim + cfg.kv_lora_rank]
    k_rope = both[..., heads * cfg.qk_head_dim + cfg.kv_lora_rank:]
    q_rope = apply_rotary(q[..., cfg.qk_nope_head_dim:], cos, sin)
    k_rope = apply_rotary(k_rope[..., None, :], cos, sin)[..., 0, :]
    c = rms_norm(c, p["kv_norm"]["scale"], cfg.rms_norm_eps)
    return q[..., :cfg.qk_nope_head_dim], q_rope, jnp.concatenate([c, k_rope], axis=-1)


def expanded_causal_attention(p: Any, cfg: DeepseekV3Config, q_nope: Any, q_rope: Any,
                              row: Any) -> Any:
    """One sequence in the EXPANDED form: keys and values per head from the
    latent (``k_nope_h = W_uk_h c``, ``v_h = W_uv_h c``), full causal softmax
    in float32, a block of query rows at a time. q_nope [S, H, nope]; q_rope
    [S, H, rope]; row [S, rank + rope] -> [S, H, v]."""
    s = row.shape[0]
    c, k_rope = row[:, :cfg.kv_lora_rank], row[:, cfg.kv_lora_rank:]
    f32 = jnp.float32
    k_nope = jnp.einsum("tc,hcn->thn", c, p["k_up"]).astype(f32)
    v = jnp.einsum("tc,hcv->thv", c, p["v_up"]).astype(f32)
    k_rope = k_rope.astype(f32)
    scale = cfg.qk_head_dim ** -0.5
    block = PREFILL_QUERY_BLOCK if s % PREFILL_QUERY_BLOCK == 0 else s

    def attend(args: Any) -> Any:
        first, qn, qr = args
        scores = (jnp.einsum("qhn,thn->hqt", qn.astype(f32) * scale, k_nope)
                  + jnp.einsum("qhr,tr->hqt", qr.astype(f32) * scale, k_rope))
        mask = jnp.arange(s)[None, :] <= first + jnp.arange(block)[:, None]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", probs, v)

    blocks = s // block
    out = jax.lax.map(attend, (jnp.arange(blocks) * block,
                               q_nope.reshape(blocks, block, *q_nope.shape[1:]),
                               q_rope.reshape(blocks, block, *q_rope.shape[1:])))
    return out.reshape(s, *out.shape[2:]).astype(row.dtype)


def absorbed_queries(p: Any, q_nope: Any, q_rope: Any) -> Any:
    """``q_lat_h = q_nope_h W_uk_h`` beside ``q_rope_h``: the heads' queries
    against a row as cached. [B, H, nope], [B, H, rope] -> [B, H, rank + rope]."""
    q_lat = jnp.einsum("bhn,hcn->bhc", q_nope, p["k_up"])
    return jnp.concatenate([q_lat, q_rope], axis=-1)


def absorbed_values(p: Any, attended: Any) -> Any:
    """``o_h = (sum_t p_t c_t) W_uv_h``: [B, H, rank] -> [B, H, v]."""
    return jnp.einsum("bhc,hcv->bhv", attended, p["v_up"])


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def routed_experts(p: Any, cfg: DeepseekV3Config, u: Any, rows: Any) -> tuple[Any, Any]:
    """This chip's part of the routed experts' sum. u: [T, D] (normed);
    ``rows`` [T] bool, the rows that hold a token. Returns (routed [T, D]
    float32, held token-expert pairs per held expert [count])."""
    idx, gates = route_sigmoid_topk(
        u, p["router"]["kernel"], p["router"]["bias"], cfg.num_experts_per_tok,
        scaling=cfg.routed_scaling_factor, normalize=cfg.norm_topk_prob)
    return held_experts_ffn(
        u, p["experts"]["w13"], p["experts"]["w2"], idx, gates, cfg.held, rows,
        n_experts=cfg.n_routed_experts, activation=gated_expert)


def expert_layer(p: Any, cfg: DeepseekV3Config, u: Any, rows: Any) -> tuple[Any, Any]:
    """The routed part held here plus the shared experts, which every token
    takes ungated: (out [T, D], pairs per held expert [count])."""
    with jax.named_scope("moe"):
        routed, counts = routed_experts(p, cfg, u, rows)
    with jax.named_scope("shared"):
        shared = gated_mlp(p["shared"], u)
    return routed.astype(u.dtype) + shared, counts


# ---------------------------------------------------------------------------
# the two functions the engine calls
# ---------------------------------------------------------------------------


class DeepseekV3Family:
    """The engine's view of one registered DeepSeek-V3 model (the seam of
    ``generate/engine.py``: ``prefill`` and ``decode`` over explicit state).
    Every layer caches, and what it caches is one latent row: ``latent_row``
    lanes as stored, of which ``config.latent_dim`` are the algorithm's."""

    def __init__(self, config: DeepseekV3Config, dtype: Any) -> None:
        self.config = config
        self.dtype = dtype
        self.vocab = config.vocab_size
        self.max_len = config.max_len
        self.kv_layers = config.num_hidden_layers
        self.latent_row = -(-config.latent_dim // ROW_LANES) * ROW_LANES
        self.latent_bytes_per_token = (
            config.num_hidden_layers * config.latent_dim * jnp.dtype(dtype).itemsize)

    def state_shapes(self, max_slots: int) -> dict:
        return {}

    def _ffn(self, p: Any, i: int, x: Any, rows: Any, counts: list) -> Any:
        """``x + ffn(RMSNorm(x))``: the gated MLP in the leading dense layers,
        the expert layer in the others (whose pair counts join ``counts``)."""
        u = rms_norm(x, p["ffn_norm"]["scale"], self.config.rms_norm_eps)
        if i < self.config.first_k_dense_replace:
            with jax.named_scope("mlp"):
                return x + gated_mlp(p["mlp"], u)
        out, c = expert_layer(p["moe"], self.config, u, rows)
        counts.append(c)
        return x + out

    def _logits(self, params: Any, x: Any) -> Any:
        x = rms_norm(x, params["norm_f"]["scale"], self.config.rms_norm_eps)
        return (x @ params["head"]["kernel"]).astype(jnp.float32)

    def prefill(self, params: Any, tokens: Any, length: Any, slot: Any, kv: Any,
                state: Any) -> tuple[Any, Any, Any]:
        """tokens [1, S] padded at the end -> (logits at ``length - 1`` [V]
        float32, state, aux). Exact because padding sits at the END under a
        causal mask: no real position can attend to it."""
        del slot
        cfg = self.config
        x = params["embed"]["embedding"][tokens[0]].astype(self.dtype)
        positions = jnp.arange(x.shape[0])
        rows = positions < length
        cos, sin = rotary_tables(positions, cfg.qk_rope_head_dim, cfg.rope_theta)
        counts: list = []
        for i in range(cfg.num_hidden_layers):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["attn_norm"]["scale"], cfg.rms_norm_eps)
            with jax.named_scope("mla"):
                q_nope, q_rope, row = project(p["attn"], cfg, u, cos, sin)
                kv.write_prefill_latent(i, row)
                att = expanded_causal_attention(p["attn"], cfg, q_nope, q_rope, row)
                out = att.reshape(att.shape[0], -1) @ p["attn"]["out"]["kernel"]
            x = self._ffn(p, i, x + out, rows, counts)
        logits = self._logits(params, jnp.take(x, length - 1, axis=0))
        return logits, state, expert_aux(counts)

    def decode(self, params: Any, tokens: Any, lengths: Any, active: Any, kv: Any,
               state: Any) -> tuple[Any, Any, Any]:
        """tokens [B] -> (logits [B, V] float32, state, aux). Slot ``b``'s
        token sits at position ``lengths[b]``: its query and its key turn
        there. No key or value is expanded: the absorbed form."""
        cfg = self.config
        x = params["embed"]["embedding"][tokens].astype(self.dtype)
        cos, sin = rotary_tables(lengths, cfg.qk_rope_head_dim, cfg.rope_theta)
        counts: list = []
        for i in range(cfg.num_hidden_layers):
            p = params[f"layer{i}"]
            u = rms_norm(x, p["attn_norm"]["scale"], cfg.rms_norm_eps)
            with jax.named_scope("mla"):
                q_nope, q_rope, row = project(p["attn"], cfg, u, cos, sin)
                attended = kv.write_attend_latent(
                    i, absorbed_queries(p["attn"], q_nope, q_rope), row,
                    value_lanes=cfg.kv_lora_rank, scale=cfg.qk_head_dim ** -0.5)
                att = absorbed_values(p["attn"], attended)
                out = att.reshape(att.shape[0], -1) @ p["attn"]["out"]["kernel"]
            x = self._ffn(p, i, x + out, active, counts)
        aux = expert_aux(counts)
        aux["kv_tokens_read"] = jnp.sum(jnp.where(active, lengths + 1, 0))
        return self._logits(params, x), state, aux

    def work_attrs(self, aux: dict, rows: int) -> dict:
        """The attributes of ``gen/step`` (``aux`` of ``decode``; ``rows``
        active slots) and ``gen/prefill`` (the runs' counts summed; ``rows``
        prompt tokens): the expert layers' work (``expert_attrs``; NumPy counts
        ``[expert layers, held]``), the cache's as the algorithm counts it
        (``latent_dim`` values a token a layer, whatever a row is stored as)."""
        cfg = self.config
        out = {"latent_layers": cfg.num_hidden_layers}
        counts = aux.get("expert_counts")
        if counts is not None:
            out.update(expert_attrs(counts, rows, cfg.num_experts_per_tok, cfg.expert_layers))
        if "kv_tokens_read" in aux:
            read = int(aux["kv_tokens_read"])
            out.update(kv_tokens_read=read, latent_bytes_read=read * self.latent_bytes_per_token)
        else:
            out.update(prompt_tokens=rows)
        return out


def register_deepseek_v3(name: str, config: DeepseekV3Config) -> Any:
    """Register ``config`` as a servable ``kind="lm"`` model called ``name``."""
    from dmlc_tpu.models import registry

    depth, width = config.num_hidden_layers, config.hidden_size
    return registry.register_family(name, config, param_shapes(config),
                                    lambda path: _leaf_mean_std(path, depth, width),
                                    DeepseekV3Family)


#: The CPU tests' preset: a dense layer, then three expert layers; four heads
#: of 16 | 8 query lanes and 16 value lanes over a latent of 32 | 8; 16
#: experts top 3, 4 of them held here (rank 1 of four), two shared.
DEEPSEEK_V3_TINY = DeepseekV3Config(
    vocab_size=256, hidden_size=64, intermediate_size=160, moe_intermediate_size=32,
    num_hidden_layers=4, num_attention_heads=4, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, n_routed_experts=16, n_shared_experts=2,
    num_experts_per_tok=3, first_k_dense_replace=1, routed_scaling_factor=2.448,
    rope_theta=10000.0, experts_held=(4, 4), max_len=256)
