"""Parameters, operations and bytes of the Granite-4.0-H block stack, from a
configuration file's keys (``configs/granite-4-h-micro.json`` or the
published ``config.json``): what the ALGORITHM needs from the shapes,
whatever implements it. A multiply-accumulate is 2 FLOPs; norms, activations
and the conv's 4 taps are left out as sub-percent; the SSM recurrence is
counted (6 FLOPs a state element a token: decay, input, read).

A layer's parameters: the mixer (Mamba-2: ``in_proj`` onto ``z | xBC | dt``,
the conv's taps and bias, ``A_log``, ``D``, ``dt_bias``, the gated norm,
``out_proj``; attention: ``q`` and ``o`` square, ``k`` and ``v`` onto the KV
heads), the gated MLP's ``W_in`` (``g | u``) and ``W_out``, and the two
pre-norms. The head is the embedding's own matrix: no parameter of its own,
but a decode step reads all of it.
"""

from __future__ import annotations

MAMBA, ATTENTION = "mamba", "attention"
BF16, F32 = 2, 4


def sizes(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    m_heads, m_head_dim = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    inner = m_heads * m_head_dim
    state = int(cfg["mamba_d_state"])
    return {
        "n_m": kinds.count(MAMBA), "n_a": kinds.count(ATTENTION),
        "d": d, "vocab": int(cfg["vocab_size"]), "width": int(cfg["shared_intermediate_size"]),
        "q_dim": d, "kv_dim": int(cfg["num_key_value_heads"]) * (d // heads),
        "inner": inner, "m_heads": m_heads, "m_head_dim": m_head_dim, "state": state,
        "conv_dim": inner + 2 * int(cfg["mamba_n_groups"]) * state,
        "taps": int(cfg["mamba_d_conv"]),
    }


def mamba_matrix_params(z: dict) -> int:
    """The mixer's projections: what a token multiplies."""
    return z["d"] * (z["inner"] + z["conv_dim"] + z["m_heads"]) + z["inner"] * z["d"]


def mamba_mixer_params(z: dict) -> int:
    return (mamba_matrix_params(z) + z["taps"] * z["conv_dim"] + z["conv_dim"]
            + 3 * z["m_heads"] + z["inner"])


def attention_params(z: dict) -> int:
    return 2 * z["d"] * z["q_dim"] + 2 * z["d"] * z["kv_dim"]


def mlp_params(z: dict) -> int:
    return 3 * z["d"] * z["width"]


def mamba_layer_params(z: dict) -> int:
    return mamba_mixer_params(z) + mlp_params(z) + 2 * z["d"]


def attention_layer_params(z: dict) -> int:
    return attention_params(z) + mlp_params(z) + 2 * z["d"]


def published_params(cfg: dict) -> int:
    """Every parameter of the model the file describes (the tied embedding
    once, the final norm)."""
    z = sizes(cfg)
    return (z["n_m"] * mamba_layer_params(z) + z["n_a"] * attention_layer_params(z)
            + z["vocab"] * z["d"] + z["d"])


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot: per mamba layer h [heads, head_dim,
    state] in float32 and the conv window [taps - 1, conv_dim] in the serving
    type."""
    z = sizes(cfg)
    return z["n_m"] * (z["m_heads"] * z["m_head_dim"] * z["state"] * F32
                       + (z["taps"] - 1) * z["conv_dim"] * BF16)


def kv_bytes_per_token(cfg: dict) -> int:
    z = sizes(cfg)
    return z["n_a"] * 2 * z["kv_dim"] * BF16


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token attending ``context`` cached positions."""
    z = sizes(cfg)
    ssm = 6.0 * z["m_heads"] * z["m_head_dim"] * z["state"]
    mamba = 2.0 * (mamba_matrix_params(z) + mlp_params(z)) + ssm
    attention = 2.0 * (attention_params(z) + mlp_params(z)) + 4.0 * context * z["q_dim"]
    return z["n_m"] * mamba + z["n_a"] * attention + 2.0 * z["d"] * z["vocab"]


def step_fixed_bytes(cfg: dict) -> float:
    """Bytes every decode step reads whatever its batch: every parameter
    once (the tied head reads the whole embedding, whose rows for the
    residents' tokens are among them)."""
    return float(BF16) * published_params(cfg)


def step_state_bytes(cfg: dict, max_slots: int) -> float:
    """The state a decode step reads and writes: EVERY slot's, the idle
    ones' included (the step keeps an inactive row by selecting it back)."""
    return 2.0 * max_slots * state_bytes_per_slot(cfg)


def step_bytes(cfg: dict, max_slots: int, contexts) -> float:
    """One decode step for the residents whose cached lengths are ``contexts``."""
    return (step_fixed_bytes(cfg) + step_state_bytes(cfg, max_slots)
            + kv_bytes_per_token(cfg) * float(sum(contexts)))


def prefill_flops(cfg: dict, prompt: int) -> float:
    """One prompt of ``prompt`` tokens: the projections and MLP of every
    position, causal attention over half the square, the SSM in its
    sequential count (a chunked form does more and is not credited for it),
    and one position through the head."""
    z = sizes(cfg)
    ssm = 6.0 * z["m_heads"] * z["m_head_dim"] * z["state"]
    mamba = 2.0 * (mamba_matrix_params(z) + mlp_params(z)) + ssm
    attention = 2.0 * (attention_params(z) + mlp_params(z)) + 2.0 * prompt * z["q_dim"]
    return prompt * (z["n_m"] * mamba + z["n_a"] * attention) + 2.0 * z["d"] * z["vocab"]
