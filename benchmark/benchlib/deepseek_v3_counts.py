"""Parameters, operations and bytes of the DeepSeek-V3 block stack, from a
configuration file's keys (``configs/kanana-2-30b-a3b.json``): what the
ALGORITHM needs from the shapes, whatever implements it. A multiply-accumulate
is 2 FLOPs; norms, activations, the rotation and the router's top-k are left
out as sub-percent.

A layer's parameters: latent attention (``W_q`` onto 32 heads of 128 | 64,
``W_kva`` onto the latent 512 | 64, the latent's norm, ``W_kvb`` from the
latent onto 32 heads of 128 | 128, ``W_o``), two norms, and the feed-forward:
three matrices at ``intermediate_size`` in the first ``first_k_dense_replace``
layers; in the others the router with its score-correction bias, the shared
experts (three matrices at ``n_shared_experts * moe_intermediate_size``) and
three matrices at ``moe_intermediate_size`` for each routed expert. The head
is untied: embedding and head are counted each.

The file's ``num_hidden_layers`` and ``n_routed_experts`` are the CUT (the
layers that run here, the experts held here); ``published`` carries the
model's own, and the router scores ``published.n_routed_experts`` experts.
"""

from __future__ import annotations

BF16 = 2


def sizes(cfg: dict, *, published: bool = False) -> dict:
    """``published``: the model as its source has it (every layer, every
    expert held), not the cut this chip runs."""
    whole = cfg.get("published", {})
    experts = int(whole.get("n_routed_experts", cfg["n_routed_experts"]))
    layers = int(whole["num_hidden_layers"] if published else cfg["num_hidden_layers"])
    held = experts if published else int(cfg["n_routed_experts"])
    dense = int(cfg["first_k_dense_replace"])
    return {
        "layers": layers, "n_dense": dense, "n_moe": layers - dense,
        "d": int(cfg["hidden_size"]), "vocab": int(cfg["vocab_size"]),
        "heads": int(cfg["num_attention_heads"]), "nope": int(cfg["qk_nope_head_dim"]),
        "rope": int(cfg["qk_rope_head_dim"]), "v": int(cfg["v_head_dim"]),
        "rank": int(cfg["kv_lora_rank"]), "width": int(cfg["intermediate_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        "experts": experts, "held": held, "top_k": int(cfg["num_experts_per_tok"]),
    }


def latent_dim(z: dict) -> int:
    """Values a token caches a layer: the compressed K/V and the rotary key."""
    return z["rank"] + z["rope"]


def attention_matrix_params(z: dict) -> int:
    return (z["d"] * z["heads"] * (z["nope"] + z["rope"]) + z["d"] * latent_dim(z)
            + z["rank"] * z["heads"] * (z["nope"] + z["v"]) + z["heads"] * z["v"] * z["d"])


def attention_params(z: dict) -> int:
    return attention_matrix_params(z) + z["rank"]


def mlp_params(z: dict) -> int:
    return 3 * z["d"] * z["width"]


def expert_params(z: dict) -> int:
    return 3 * z["d"] * z["expert_width"]


def shared_params(z: dict) -> int:
    return 3 * z["d"] * z["shared_width"]


def router_params(z: dict) -> int:
    return z["d"] * z["experts"] + z["experts"]


def _total(z: dict) -> int:
    every_layer = attention_params(z) + 2 * z["d"]
    moe = router_params(z) + shared_params(z) + z["held"] * expert_params(z)
    return (z["layers"] * every_layer + z["n_dense"] * mlp_params(z) + z["n_moe"] * moe
            + 2 * z["vocab"] * z["d"] + z["d"])


def total_params(cfg: dict) -> int:
    """Parameters this chip holds: the file's layers, the experts held."""
    return _total(sizes(cfg))


def published_params(cfg: dict) -> int:
    """Parameters of the model as published."""
    return _total(sizes(cfg, published=True))


def latent_bytes_per_token(cfg: dict) -> int:
    """What a cached position costs to read, every layer: the algorithm's
    ``kv_lora_rank + qk_rope_head_dim`` values, whatever a row is stored as."""
    z = sizes(cfg)
    return z["layers"] * latent_dim(z) * BF16


def kernel_flops_per_position(cfg: dict) -> int:
    """The absorbed decode attention over one cached position, every layer:
    per head ``latent_dim`` multiply-adds for the score and ``kv_lora_rank``
    for the weighted sum, in ONE pass (a kernel that makes its float32
    products of three bfloat16 terms does three times this and is credited
    with this)."""
    z = sizes(cfg)
    return z["layers"] * 2 * z["heads"] * (latent_dim(z) + z["rank"])


def step_fixed_bytes(cfg: dict, experts_hit: float) -> float:
    """Bytes every decode step must read whatever its batch: every layer's
    attention matrices and norms, the dense feed-forward, the routers, the
    shared experts, the final norm and the head, and each held expert that
    got a row once. The embedding rows a step gathers (one a resident) are
    left out."""
    z = sizes(cfg)
    dense = (z["layers"] * (attention_params(z) + 2 * z["d"]) + z["n_dense"] * mlp_params(z)
             + z["n_moe"] * (router_params(z) + shared_params(z)) + z["vocab"] * z["d"] + z["d"])
    return float(BF16) * (dense + z["n_moe"] * experts_hit * expert_params(z))


def moe_step_bytes(cfg: dict, experts_hit: float) -> float:
    """What the routed expert layers of one decode step must read: each held
    expert that got a row once (``experts_hit`` a layer) and the router with
    its bias, every expert layer. The shared experts are not routed and are
    left out."""
    z = sizes(cfg)
    return float(BF16) * z["n_moe"] * (experts_hit * expert_params(z) + router_params(z))


def step_bytes(cfg: dict, contexts, experts_hit: float) -> float:
    """One decode step for the residents whose cached lengths are ``contexts``."""
    return step_fixed_bytes(cfg, experts_hit) + latent_bytes_per_token(cfg) * float(sum(contexts))


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token attending ``context`` cached positions, WITHOUT its
    routed experts (``expert_pair_flops`` a pair held here): the projections
    onto the queries and the latent, the absorbed query and value products
    (``q_nope W_uk``, ``W_uv`` after the weighted sum), attention over the
    latent itself, the output projection, the dense feed-forward, the routers
    and the shared experts, the head. The absorbed form is the cheaper way
    to decode, so this is the smaller count."""
    z = sizes(cfg)
    attn = (z["d"] * z["heads"] * (z["nope"] + z["rope"]) + z["d"] * latent_dim(z)
            + z["heads"] * z["nope"] * z["rank"] + z["heads"] * z["rank"] * z["v"]
            + z["heads"] * z["v"] * z["d"])
    ffn = z["n_dense"] * mlp_params(z) + z["n_moe"] * (z["d"] * z["experts"] + shared_params(z))
    return (2.0 * (z["layers"] * attn + ffn) + float(kernel_flops_per_position(cfg)) * context
            + 2.0 * z["d"] * z["vocab"])


def expert_pair_flops(cfg: dict) -> float:
    """One token through one routed expert."""
    return 2.0 * expert_params(sizes(cfg))


def prefill_flops(cfg: dict, prompt: int, held_pairs: int = 0) -> float:
    """One prompt of ``prompt`` tokens in the EXPANDED form: every position
    through the projections (keys and values per head from the latent: the
    whole ``W_kvb``), causal attention over half the square at 192 | 128 lanes
    a head, the feed-forwards, ``held_pairs`` token-expert pairs held here,
    one position through the head."""
    z = sizes(cfg)
    per_token = 2.0 * (z["layers"] * attention_matrix_params(z) + z["n_dense"] * mlp_params(z)
                       + z["n_moe"] * (z["d"] * z["experts"] + shared_params(z)))
    square = z["layers"] * z["heads"] * (z["nope"] + z["rope"] + z["v"]) * float(prompt) * prompt
    return (prompt * per_token + square + held_pairs * expert_pair_flops(cfg)
            + 2.0 * z["d"] * z["vocab"])
