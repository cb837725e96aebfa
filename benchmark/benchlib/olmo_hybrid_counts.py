"""Parameters, operations and bytes of the Olmo-Hybrid block stack, from a
configuration file's keys (``configs/olmo-hybrid-7b.json`` or the published
``config.json``): what the ALGORITHM needs from the shapes, whatever
implements it. A multiply-accumulate is 2 FLOPs; norms, activations and the
conv's 4 taps are left out as sub-percent; the delta rule is counted (8 FLOPs
a state element a token: decay, ``S k``, the rank-one update, ``S q``).

A layer's parameters: the mixer (gated delta rule: ``W_q, W_k`` onto ``heads
x d_k``, ``W_v, W_g`` onto ``heads x d_v``, ``w_b, w_a`` onto ``heads``, the
conv's taps, ``A_log``, ``dt_bias``, the norm over ``d_v``, ``W_o``; full
attention: four square projections and the query and key norms), the gated
MLP's three matrices and the two norms on the branches' outputs.
"""

from __future__ import annotations

LINEAR, FULL = "linear_attention", "full_attention"
BF16 = 2


def sizes(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    l_heads = int(cfg["linear_num_key_heads"])
    return {
        "n_linear": kinds.count(LINEAR), "n_full": kinds.count(FULL),
        "d": d, "vocab": int(cfg["vocab_size"]), "width": int(cfg["intermediate_size"]),
        "q_dim": d, "kv_dim": int(cfg["num_key_value_heads"]) * (d // heads),
        "l_heads": l_heads, "key_dim": l_heads * int(cfg["linear_key_head_dim"]),
        "value_dim": int(cfg["linear_num_value_heads"]) * int(cfg["linear_value_head_dim"]),
        "dv": int(cfg["linear_value_head_dim"]), "taps": int(cfg["linear_conv_kernel_dim"]),
    }


def mlp_params(z: dict) -> int:
    return 3 * z["d"] * z["width"]


def deltanet_matrix_params(z: dict) -> int:
    """The mixer's projections: what a token multiplies."""
    return z["d"] * (2 * z["key_dim"] + 2 * z["value_dim"] + 2 * z["l_heads"]) + z["value_dim"] * z["d"]


def deltanet_mixer_params(z: dict) -> int:
    conv_dim = 2 * z["key_dim"] + z["value_dim"]
    return deltanet_matrix_params(z) + z["taps"] * conv_dim + 2 * z["l_heads"] + z["dv"]


def attention_matrix_params(z: dict) -> int:
    return 2 * z["d"] * z["q_dim"] + 2 * z["d"] * z["kv_dim"]


def attention_mixer_params(z: dict) -> int:
    return attention_matrix_params(z) + z["q_dim"] + z["kv_dim"]


def linear_layer_params(z: dict) -> int:
    return deltanet_mixer_params(z) + mlp_params(z) + 2 * z["d"]


def full_layer_params(z: dict) -> int:
    return attention_mixer_params(z) + mlp_params(z) + 2 * z["d"]


def total_params(cfg: dict) -> int:
    z = sizes(cfg)
    return (z["n_linear"] * linear_layer_params(z) + z["n_full"] * full_layer_params(z)
            + 2 * z["vocab"] * z["d"] + z["d"])


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot: per linear layer S [heads, d_v, d_k] in
    float32 and the conv windows [taps - 1, q | k | v] in the serving type."""
    z = sizes(cfg)
    state = z["value_dim"] * int(cfg["linear_key_head_dim"]) * 4
    return z["n_linear"] * (state + (z["taps"] - 1) * (2 * z["key_dim"] + z["value_dim"]) * BF16)


def kv_bytes_per_token(cfg: dict) -> int:
    z = sizes(cfg)
    return z["n_full"] * 2 * z["kv_dim"] * BF16


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token attending ``context`` cached positions."""
    z = sizes(cfg)
    delta_rule = 8.0 * z["value_dim"] * int(cfg["linear_key_head_dim"])
    linear = 2.0 * (deltanet_matrix_params(z) + mlp_params(z)) + delta_rule
    full = 2.0 * (attention_matrix_params(z) + mlp_params(z)) + 4.0 * context * z["q_dim"]
    return z["n_linear"] * linear + z["n_full"] * full + 2.0 * z["d"] * z["vocab"]


def step_fixed_bytes(cfg: dict) -> float:
    """Bytes every decode step must read whatever its batch: every layer and
    the head once (the embedding table is NOT read whole: one row a resident,
    counted per token)."""
    z = sizes(cfg)
    return float(BF16) * (z["n_linear"] * linear_layer_params(z) + z["n_full"] * full_layer_params(z)
                          + z["vocab"] * z["d"] + z["d"])


def token_bytes(cfg: dict, context: int) -> float:
    """Bytes one resident adds to a step: its recurrent state read and
    written, its ``context`` cached K and V read, one embedding row."""
    return (2.0 * state_bytes_per_slot(cfg) + kv_bytes_per_token(cfg) * float(context)
            + float(BF16) * sizes(cfg)["d"])


def step_bytes(cfg: dict, contexts) -> float:
    """One decode step for the residents whose cached lengths are ``contexts``."""
    return step_fixed_bytes(cfg) + sum(token_bytes(cfg, c) for c in contexts)


def deltanet_weight_bytes(cfg: dict) -> float:
    """What the linear layers' mixers read of their weights inside their own
    events in one decode step: the input projections, taps, per-head vectors
    and the norm over ``d_v``. ``W_o`` is left out: its product is among the
    mixer's events but its weights are not read there (asynchronous slices
    under other layers' events: ``kernels/deltanet_mixer.py``)."""
    z = sizes(cfg)
    return z["n_linear"] * float(BF16) * (deltanet_mixer_params(z) - z["value_dim"] * z["d"])


def prefill_flops(cfg: dict, prompt: int) -> float:
    """One prompt of ``prompt`` tokens: the projections and MLP of every
    position, causal attention over half the square, the delta rule in its
    sequential count (a chunked form does more and is not credited for it),
    and one position through the head."""
    z = sizes(cfg)
    delta_rule = 8.0 * z["value_dim"] * int(cfg["linear_key_head_dim"])
    linear = 2.0 * (deltanet_matrix_params(z) + mlp_params(z)) + delta_rule
    full = 2.0 * (attention_matrix_params(z) + mlp_params(z)) + 2.0 * prompt * z["q_dim"]
    return prompt * (z["n_linear"] * linear + z["n_full"] * full) + 2.0 * z["d"] * z["vocab"]
