"""Parameters, operations and bytes of the Nemotron-H block stack, from a
configuration file's keys (``configs/nemotron3-super.json`` or the published
``config.json``). A multiply-accumulate is 2 FLOPs; norms, activations, the
conv's 4 taps and the router's top-k are left out as sub-percent; the SSM
recurrence is counted (6 FLOPs a state element a token: decay, input, read).

``held`` is how many routed experts of a layer live here (the file's
``n_routed_experts``); the router's width is the published count. One token
is routed to ``num_experts_per_tok`` experts of which ``held / published``
are here on average: this chip's share of the routed work.
"""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    pattern = cfg["hybrid_override_pattern"]
    published = int(cfg.get("published", {}).get("n_routed_experts", cfg["n_routed_experts"]))
    inner = int(cfg["mamba_num_heads"]) * int(cfg["mamba_head_dim"])
    return {
        "n_m": pattern.count("M"), "n_e": pattern.count("E"), "n_a": pattern.count("*"),
        "d": int(cfg["hidden_size"]), "vocab": int(cfg["vocab_size"]),
        "inner": inner, "m_heads": int(cfg["mamba_num_heads"]),
        "conv_dim": inner + 2 * int(cfg["n_groups"]) * int(cfg["ssm_state_size"]),
        "conv_kernel": int(cfg["conv_kernel"]), "state": int(cfg["ssm_state_size"]),
        "q_dim": int(cfg["num_attention_heads"]) * int(cfg["head_dim"]),
        "kv_dim": int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
        "experts": published, "held": int(cfg["n_routed_experts"]),
        "top_k": int(cfg["num_experts_per_tok"]), "latent": int(cfg["moe_latent_size"]),
        "expert_width": int(cfg["moe_intermediate_size"]),
        "shared_width": int(cfg["moe_shared_expert_intermediate_size"]),
    }


def mamba_layer_params(z: dict) -> int:
    return (z["d"] * (z["inner"] + z["conv_dim"] + z["m_heads"])       # in_proj [z | xBC | dt]
            + z["conv_dim"] * z["conv_kernel"] + z["conv_dim"]          # conv taps and bias
            + 3 * z["m_heads"] + z["inner"]                             # dt_bias, A_log, D, grouped norm
            + z["inner"] * z["d"] + z["d"])                             # out_proj, the layer's pre-norm


def attention_layer_params(z: dict) -> int:
    return 2 * z["d"] * z["q_dim"] + 2 * z["d"] * z["kv_dim"] + z["d"]


def expert_params(z: dict) -> int:
    return 2 * z["latent"] * z["expert_width"]


def moe_dense_params(z: dict) -> int:
    """What every chip of the layer holds: router and its bias, the two
    latent projections, the shared expert, the pre-norm."""
    return (z["d"] * z["experts"] + z["experts"] + 2 * z["d"] * z["latent"]
            + 2 * z["d"] * z["shared_width"] + z["d"])


def total_params(cfg: dict) -> int:
    """Parameters the file's model holds: ``n_routed_experts`` experts a
    layer (all of them for the published keys, the held ones for the cut)."""
    z = sizes(cfg)
    return (z["n_m"] * mamba_layer_params(z) + z["n_a"] * attention_layer_params(z)
            + z["n_e"] * (z["held"] * expert_params(z) + moe_dense_params(z))
            + 2 * z["vocab"] * z["d"] + z["d"])


def state_bytes_per_slot(cfg: dict) -> int:
    """Recurrent state of one slot: per M layer h [heads, head_dim, state]
    float32 and the conv window [kernel - 1, conv_dim] in the serving type."""
    z = sizes(cfg)
    return z["n_m"] * (z["inner"] * z["state"] * 4 + (z["conv_kernel"] - 1) * z["conv_dim"] * 2)


def kv_bytes_per_token(cfg: dict) -> int:
    z = sizes(cfg)
    return z["n_a"] * 2 * z["kv_dim"] * 2


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token attending ``context`` cached positions, this chip's
    share: M, *, router, latent projections, shared expert, head whole; of
    the routed experts the ``top_k * held / experts`` a token sends here."""
    z = sizes(cfg)
    mamba = (2.0 * (mamba_layer_params(z) - z["inner"] - z["d"] - z["conv_dim"] - 3 * z["m_heads"])
             + 6.0 * z["inner"] * z["state"])
    attention = 2.0 * (attention_layer_params(z) - z["d"]) + 4.0 * context * z["q_dim"]
    routed_here = z["top_k"] * z["held"] / z["experts"]
    moe = 2.0 * (moe_dense_params(z) - z["experts"] - z["d"]) + 2.0 * routed_here * expert_params(z)
    return z["n_m"] * mamba + z["n_a"] * attention + z["n_e"] * moe + 2.0 * z["d"] * z["vocab"]


def step_fixed_bytes(cfg: dict, experts_hit: float) -> float:
    """Bytes every decode step must read whatever its batch: the dense
    parameters once (the embedding table is NOT read whole: one row a
    resident, counted per token) and each held expert that got a row once
    (``experts_hit``: the mean over E layers, measured in the same window,
    never "all held")."""
    z = sizes(cfg)
    dense = (z["n_m"] * mamba_layer_params(z) + z["n_a"] * attention_layer_params(z)
             + z["n_e"] * moe_dense_params(z) + z["vocab"] * z["d"] + z["d"])
    return 2.0 * (dense + z["n_e"] * experts_hit * expert_params(z))


def token_bytes(cfg: dict, context: int) -> float:
    """Bytes one resident adds to a step: its recurrent state read and
    written, its ``context`` cached K and V read, one embedding row."""
    return (2.0 * state_bytes_per_slot(cfg) + kv_bytes_per_token(cfg) * float(context)
            + 2.0 * sizes(cfg)["d"])


def step_bytes(cfg: dict, contexts, experts_hit: float) -> float:
    """One decode step for the residents whose cached lengths are ``contexts``."""
    return step_fixed_bytes(cfg, experts_hit) + sum(token_bytes(cfg, c) for c in contexts)
