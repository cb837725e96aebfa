"""Plain reference for the Nemotron-H block stack (``model_type: nemotron_h``;
NVIDIA-Nemotron-3-Super-120B-A12B ``config.json``; the Nemotron-H report,
arXiv:2504.03624; Mamba-2, arXiv:2405.21060): one mixer per layer under one
pre-RMSNorm and one residual, ``M`` Mamba-2 / ``*`` grouped-query attention
with no position term / ``E`` LatentMoE, final RMSNorm, an untied head.

Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one full
forward over prompt + served tokens: the SSM is the SEQUENTIAL recurrence
(``lax.scan`` over positions, no chunks), attention is full and causal over a
dense mask, the experts are a loop over the held experts with a dense 0/gate
weight per token. No cache, no paging, no state slots, no sorting of rows.
Imports nothing of the program; weights are the benchmark's own seed-made
arrays, upcast a layer (an expert) at a time.

The configuration's cut is given to the reference as to the program
(model-configs guide, section 4): the router scores all
``published.n_routed_experts`` experts and keeps ``num_experts_per_tok``; only
experts ``deployment.experts_held = [first, count]`` add to the result, and
what the others would add is left out; the vocabulary is the slice the file
states. Departures from the published model are the file's ``assumed``.

``check`` decides the cell's ``correct`` as ``reference/gpt2.py`` does: over
every served (greedy) token of the sampled requests, the gap by which the
served token's logit lies below the reference's best logit at that position.
"""

from __future__ import annotations

import functools

ROWS = 8  # requests per reference block


def _quantize(x, mode):
    if mode is None:
        return x
    from benchlib.lowprec import quantize

    return quantize(x, mode)


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return out


def sizes(cfg: dict) -> dict:
    """The numbers the math reads, from a configuration file's keys."""
    published = cfg.get("published", {})
    deployment = cfg.get("deployment", {})
    experts = int(published.get("n_routed_experts", cfg["n_routed_experts"]))
    held = tuple(int(v) for v in deployment.get("experts_held", (0, experts)))
    return {
        "pattern": cfg["hybrid_override_pattern"], "d": int(cfg["hidden_size"]),
        "heads": int(cfg["num_attention_heads"]), "kv_heads": int(cfg["num_key_value_heads"]),
        "head_dim": int(cfg["head_dim"]), "m_heads": int(cfg["mamba_num_heads"]),
        "m_head_dim": int(cfg["mamba_head_dim"]), "groups": int(cfg["n_groups"]),
        "state": int(cfg["ssm_state_size"]), "conv": int(cfg["conv_kernel"]),
        "experts": experts, "held": held, "top_k": int(cfg["num_experts_per_tok"]),
        "scaling": float(cfg["routed_scaling_factor"]),
        "norm_topk": bool(cfg["norm_topk_prob"]),
        "eps": float(cfg.get("norm_eps", cfg.get("layer_norm_epsilon", 1e-5))),
    }


def _f32(x):
    import jax.numpy as jnp

    return x.astype(jnp.float32)


def _mm(x, w, mode):
    """``x @ w`` in float32; under a control ``mode`` both sides are rounded
    through the lower precision first, one scale a tensor."""
    return _quantize(x, mode) @ _quantize(_f32(w), mode)


def _rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * _f32(scale)


def _silu(x):
    import jax

    return x * jax.nn.sigmoid(x)


def mamba_mixer(u, p, z, mode=None):
    """u: [B, T, D] (normed) -> [B, T, D]. ``z``: ``sizes(cfg)``."""
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    heads, hd, groups, n, k = z["m_heads"], z["m_head_dim"], z["groups"], z["state"], z["conv"]
    inner, gn = heads * hd, groups * n
    proj = _mm(u, p["in_proj"]["kernel"], mode)
    gate, xbc, dt = proj[..., :inner], proj[..., inner:inner + inner + 2 * gn], proj[..., 2 * inner + 2 * gn:]
    w = _f32(p["conv"]["kernel"])                                   # [K, C]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * w[j] for j in range(k)) + _f32(p["conv"]["bias"])
    xbc = _silu(conv)
    xs = xbc[..., :inner].reshape(b, t, heads, hd)
    bm = xbc[..., inner:inner + gn].reshape(b, t, groups, n)
    cm = xbc[..., inner + gn:].reshape(b, t, groups, n)
    per = heads // groups
    bm, cm = jnp.repeat(bm, per, axis=2), jnp.repeat(cm, per, axis=2)   # head h uses group h // per
    delta = jax.nn.softplus(dt + _f32(p["dt_bias"]))                # [B, T, H]
    a = -jnp.exp(_f32(p["A_log"]))

    def one(h, inp):
        x_t, b_t, c_t, d_t = inp                                    # [B,H,P], [B,H,N], [B,H,N], [B,H]
        h = jnp.exp(d_t * a)[..., None, None] * h + (d_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)

    h0 = jnp.zeros((b, heads, hd, n), jnp.float32)
    seq = lambda v: jnp.moveaxis(v, 1, 0)
    _, y = jax.lax.scan(one, h0, (seq(xs), seq(bm), seq(cm), seq(delta)))
    y = jnp.moveaxis(y, 0, 1) + _f32(p["D"])[None, None, :, None] * xs
    y = y.reshape(b, t, inner) * _silu(gate)                        # gate, then the grouped norm
    y = y.reshape(b, t, groups, inner // groups)
    y = y / jnp.sqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + z["eps"])
    y = y.reshape(b, t, inner) * _f32(p["norm"]["scale"])
    return _mm(y, p["out_proj"]["kernel"], mode)


def attention_mixer(u, p, z, mode=None):
    import jax
    import jax.numpy as jnp

    b, t, _ = u.shape
    heads, kv, dh = z["heads"], z["kv_heads"], z["head_dim"]
    q = _mm(u, p["query"]["kernel"], mode).reshape(b, t, heads, dh)
    k = _mm(u, p["key"]["kernel"], mode).reshape(b, t, kv, dh)
    v = _mm(u, p["value"]["kernel"], mode).reshape(b, t, kv, dh)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(dh))
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, heads * dh)
    return _mm(att, p["out"]["kernel"], mode)


def route(u, p, z):
    """Dense [.., experts] gate weights: ``g_e`` for the chosen experts
    (normalised over all chosen, scaled), 0 elsewhere. Float32 in every mode."""
    import jax
    import jax.numpy as jnp

    s = jax.nn.sigmoid(u @ _f32(p["router"]["kernel"]))
    _, idx = jax.lax.top_k(s + _f32(p["router"]["bias"]), z["top_k"])
    chosen = jnp.sum(jax.nn.one_hot(idx, z["experts"], dtype=jnp.float32), axis=-2)
    g = s * chosen
    if z["norm_topk"]:
        g = g / (jnp.sum(g, axis=-1, keepdims=True) + 1e-20)
    return g * z["scaling"]


def moe_mixer(u, p, z, mode=None, held=None, shared=True):
    """The layer's result from the experts ``held = (first, count)`` (the
    configuration's share by default) plus, once, the shared expert."""
    import jax
    import jax.numpy as jnp

    first, count = held if held is not None else z["held"]
    gates = route(u, p, z)[..., first:first + count]                # [B, T, count]
    lat = _mm(u, p["down"]["kernel"], mode)
    lat_q = _quantize(lat, mode)

    def one(acc, inp):
        w1, w2, g = inp                                             # one expert, upcast here
        h = jnp.square(jax.nn.relu(lat_q @ _quantize(_f32(w1), mode)))
        return acc + g[..., None] * (_quantize(h, mode) @ _quantize(_f32(w2), mode)), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                             (p["experts"]["w1"], p["experts"]["w2"], jnp.moveaxis(gates, -1, 0)))
    out = _mm(routed, p["up"]["kernel"], mode)
    if shared:
        hs = jnp.square(jax.nn.relu(_mm(u, p["shared"]["w1"]["kernel"], mode)))
        out = out + _mm(hs, p["shared"]["w2"]["kernel"], mode)
    return out


MIXERS = {"M": ("mamba", mamba_mixer), "*": ("attn", attention_mixer), "E": ("moe", moe_mixer)}


def _frozen(z: dict):
    return tuple(sorted(z.items()))


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
        return highest(lambda x, p: x + mixer(_rms_norm(x, p["norm"]["scale"], z["eps"]),
                                              p[name], z, mode))

    def head(x, norm_f, head_p, positions):
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)       # [B, K, D]
        return _mm(_rms_norm(x, norm_f["scale"], z["eps"]), head_p["kernel"], mode)

    embed = highest(lambda table, tokens: _f32(table)[tokens])
    return embed, {kind: layer(kind) for kind in MIXERS}, highest(head)


def logits_at(cfg: dict, flat: dict, tokens, positions, mode=None):
    """Logits [B, K, V] at ``positions`` [B, K] of ``tokens`` [B, T], float32."""
    params = unflatten(flat)["params"]
    z = sizes(cfg)
    embed, layers, head = _programs(_frozen(z), mode)
    x = embed(params["embed"]["embedding"], tokens)
    for i, kind in enumerate(z["pattern"]):
        x = layers[kind](x, params[f"layer{i}"])
    return head(x, params["norm_f"], params["head"], positions)


def served_gaps(cfg: dict, flat: dict, sample, pad_to: int, max_out: int, control=None):
    """Per request, per served token: the reference's best logit minus its
    logit of the served token. With ``control`` the token judged at each
    position is the one the lower precision puts first, over the same
    prompts and served tokens."""
    import jax.numpy as jnp
    import numpy as np

    out = []
    for start in range(0, len(sample), ROWS):
        rows = sample[start:start + ROWS]
        tokens = np.zeros((ROWS, pad_to), np.int32)
        positions = np.zeros((ROWS, max_out), np.int32)
        judged = np.zeros((ROWS, max_out), np.int32)
        for i, r in enumerate(rows):
            seq = list(r["prompt"]) + list(r["tokens"])
            tokens[i, :len(seq)] = seq
            for k, tok in enumerate(r["tokens"]):
                positions[i, k] = len(r["prompt"]) + k - 1
                judged[i, k] = tok
        ref = logits_at(cfg, flat, jnp.asarray(tokens), jnp.asarray(positions))
        if control is not None:
            low = logits_at(cfg, flat, jnp.asarray(tokens), jnp.asarray(positions), control)
            judged = np.asarray(jnp.argmax(low, axis=-1))
        ref = np.asarray(ref)
        best = ref.max(axis=-1)
        got = np.take_along_axis(ref, judged[:, :, None], axis=-1)[:, :, 0]
        for i, r in enumerate(rows):
            out.append((best[i, :len(r["tokens"])] - got[i, :len(r["tokens"])]).tolist())
    return out


def shapes_for(mix: dict) -> tuple[int, int]:
    longest = int(mix["prompt_tokens"][1]) + int(mix["output_tokens"][1])
    return -(-longest // 128) * 128, int(mix["output_tokens"][1])


def check(cfg: dict, flat: dict, sample, limits: dict, mix: dict, control=None) -> dict:
    """The gaps of every served token of the sample, reduced to the numbers
    the configuration gives a limit for (the others are printed beside them,
    uncompared): their mean, their 90th percentile, their widest, and the
    share of tokens that are not the reference's own first choice."""
    pad_to, max_out = shapes_for(mix)
    gaps = sorted(g for row in served_gaps(cfg, flat, sample, pad_to, max_out, control)
                  for g in row)
    n = len(gaps)
    numbers = {
        "logit_gap_mean": sum(gaps) / n if n else float("inf"),
        "logit_gap_p90": gaps[min(n - 1, int(0.9 * n))] if n else float("inf"),
        "logit_gap_max": gaps[-1] if n else float("inf"),
        "not_first_choice_share": sum(1 for g in gaps if g > 0) / n if n else float("inf"),
    }
    out = {name: {"value": float(value), "limit": limits.get(name)}
           for name, value in numbers.items()}
    out["checked_tokens"] = {"value": n, "limit": 1, "sense": "min"}
    return out
