"""Parameters, operations and bytes of the LFM2-MoE block stack, from a
configuration file's keys (``configs/lfm2-8b-a1b.json`` or the published
``config.json``): what the ALGORITHM needs from the shapes, whatever
implements it. A multiply-accumulate is 2 FLOPs; norms, activations, the
conv's 3 taps, the rotation and the router's top-k are left out as
sub-percent.

A layer's parameters: the operator (gated short convolution: ``W_in`` onto
``B | C | u``, the taps, ``W_out``; attention: ``W_q``, ``W_k``, ``W_v``,
``W_o`` and the two per-head norms of ``head_dim`` lanes), the feed-forward
(three matrices at ``intermediate_size`` in the first ``num_dense_layers``
layers; in the others ``num_experts`` times three matrices at
``moe_intermediate_size``, the router and its expert bias) and the two norms.
The head is the embedding's own matrix and is counted once.
"""

from __future__ import annotations

CONV, FULL = "conv", "full_attention"
BF16 = 2


def sizes(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    dense = int(cfg["num_dense_layers"])
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    head_dim = d // heads
    return {
        "n_conv": kinds.count(CONV), "n_full": kinds.count(FULL),
        "n_dense": dense, "n_moe": len(kinds) - dense,
        "d": d, "vocab": int(cfg["vocab_size"]), "width": int(cfg["intermediate_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]), "experts": int(cfg["num_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]), "taps": int(cfg["conv_L_cache"]),
        "head_dim": head_dim, "q_dim": heads * head_dim,
        "kv_dim": int(cfg["num_key_value_heads"]) * head_dim,
        "expert_bias": bool(cfg.get("use_expert_bias", True)),
    }


def conv_matrix_params(z: dict) -> int:
    """The conv operator's projections: what a token multiplies."""
    return 3 * z["d"] * z["d"] + z["d"] * z["d"]


def conv_operator_params(z: dict) -> int:
    return conv_matrix_params(z) + z["taps"] * z["d"]


def attention_matrix_params(z: dict) -> int:
    return 2 * z["d"] * z["q_dim"] + 2 * z["d"] * z["kv_dim"]


def attention_operator_params(z: dict) -> int:
    return attention_matrix_params(z) + 2 * z["head_dim"]


def mlp_params(z: dict) -> int:
    return 3 * z["d"] * z["width"]


def expert_params(z: dict) -> int:
    return 3 * z["d"] * z["expert_width"]


def router_params(z: dict) -> int:
    return z["d"] * z["experts"] + (z["experts"] if z["expert_bias"] else 0)


def moe_params(z: dict) -> int:
    return z["experts"] * expert_params(z) + router_params(z)


def operator_params_total(z: dict) -> int:
    """Every layer's operator and its two norms."""
    return (z["n_conv"] * conv_operator_params(z) + z["n_full"] * attention_operator_params(z)
            + (z["n_conv"] + z["n_full"]) * 2 * z["d"])


def total_params(cfg: dict) -> int:
    """Parameters the file's model holds, the tied head counted once."""
    z = sizes(cfg)
    return (operator_params_total(z) + z["n_dense"] * mlp_params(z) + z["n_moe"] * moe_params(z)
            + z["vocab"] * z["d"] + z["d"])


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot: per conv layer the last ``taps - 1`` rows
    of the gated product, in the serving type."""
    z = sizes(cfg)
    return z["n_conv"] * (z["taps"] - 1) * z["d"] * BF16


def kv_bytes_per_token(cfg: dict) -> int:
    z = sizes(cfg)
    return z["n_full"] * 2 * z["kv_dim"] * BF16


def expert_bytes(cfg: dict) -> float:
    """Every expert of every expert layer, once: what the dense form of a
    decode step reads of them whatever the routing."""
    z = sizes(cfg)
    return float(BF16) * z["n_moe"] * z["experts"] * expert_params(z)


def moe_step_bytes(cfg: dict, experts_hit: float) -> float:
    """What the expert layers must read in one decode step: each expert that
    got a row once (``experts_hit``: the mean over the expert layers, measured
    in the same window, never "all of them") and the router with its bias.
    The feed-forward's pre-norm (4 KB a layer) is left out: its reduction is
    fused into the operator's output product, which is not an expert-layer
    event (``kernels/gated_moe_mixer.py``)."""
    z = sizes(cfg)
    return float(BF16) * z["n_moe"] * (experts_hit * expert_params(z) + router_params(z))


def step_fixed_bytes(cfg: dict, experts_hit: float) -> float:
    """Bytes every decode step must read whatever its batch: every operator,
    the dense feed-forwards, the final norm and the head (= the embedding's
    matrix, read whole as the head: the embedding rows a step gathers are
    inside it), and each expert that got a row once."""
    z = sizes(cfg)
    dense = (operator_params_total(z) + z["n_dense"] * mlp_params(z)
             + z["n_moe"] * router_params(z) + z["vocab"] * z["d"] + z["d"])
    return float(BF16) * (dense + z["n_moe"] * experts_hit * expert_params(z))


def token_bytes(cfg: dict, context: int) -> float:
    """Bytes one resident adds to a step: its conv windows read and written,
    its ``context`` cached K and V read."""
    return 2.0 * state_bytes_per_slot(cfg) + kv_bytes_per_token(cfg) * float(context)


def step_bytes(cfg: dict, contexts, experts_hit: float) -> float:
    """One decode step for the residents whose cached lengths are ``contexts``."""
    return step_fixed_bytes(cfg, experts_hit) + sum(token_bytes(cfg, c) for c in contexts)


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token attending ``context`` cached positions: every
    operator's projections, the dense feed-forwards, the router, the
    ``top_k`` experts the token is sent to (NOT the experts a dense form
    multiplies it by), attention over its own context, the head."""
    z = sizes(cfg)
    ffn = (z["n_dense"] * mlp_params(z)
           + z["n_moe"] * (z["top_k"] * expert_params(z) + z["d"] * z["experts"]))
    ops = z["n_conv"] * conv_matrix_params(z) + z["n_full"] * attention_matrix_params(z)
    return (2.0 * (ops + ffn) + z["n_full"] * 4.0 * context * z["q_dim"]
            + 2.0 * z["d"] * z["vocab"])


def prefill_flops(cfg: dict, prompt: int) -> float:
    """One prompt of ``prompt`` tokens: every position through the operators
    and feed-forwards, causal attention over half the square, one position
    through the head."""
    z = sizes(cfg)
    per_token = decode_token_flops(cfg, 0) - 2.0 * z["d"] * z["vocab"]
    return prompt * (per_token + z["n_full"] * 2.0 * prompt * z["q_dim"]) + 2.0 * z["d"] * z["vocab"]
