"""Plain reference for GPT-2's block (Radford et al. 2019; the block of
``openai-community/gpt2-large``): learned positions, pre-LayerNorm, causal
multi-head attention, tanh-GELU MLP, final LayerNorm, an output head.
Straight ``jax.numpy`` in float32 at ``highest`` matmul precision, one full
causal forward over prompt + served tokens: no cache, no paging, no batching
tricks. Imports nothing of the program; the weights are the benchmark's own
seed-made arrays (``benchlib/weights.py``), upcast layer by layer.

Departures from the published model, as the configuration file states them:
the head is untied (its own ``head/kernel`` and ``head/bias``) and the
LayerNorm epsilon is the configuration's ``layer_norm_epsilon``.

``check`` decides the cell's ``correct``: over every served (greedy) token
of the sampled requests, the gap by which the served token's logit lies
below the reference's best logit at that position.
"""

from __future__ import annotations

import functools

ROWS = 8  # requests per reference block


from benchlib.lowprec import quantize as _quantize  # noqa: E402


def _dense(x, p, mode):
    import jax.numpy as jnp

    w = p["kernel"].astype(jnp.float32)
    if mode is not None:
        x, w = _quantize(x, mode), _quantize(w, mode)
    return x @ w + p["bias"].astype(jnp.float32)


def _layer_norm(x, p, eps):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return ((x - mean) / jnp.sqrt(var + eps) * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32))


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x ** 3)))


@functools.lru_cache(maxsize=None)
def _programs(n_head: int, eps: float, mode):
    import jax
    import jax.numpy as jnp

    def embed(wte, wpe, tokens):
        t = tokens.shape[1]
        return wte.astype(jnp.float32)[tokens] + wpe.astype(jnp.float32)[jnp.arange(t)][None]

    def block(x, p):
        b, t, d = x.shape
        h = _layer_norm(x, p["ln1"], eps)
        split = lambda y: y.reshape(b, t, n_head, d // n_head).transpose(0, 2, 1, 3)
        q = split(_dense(h, p["attn"]["query"], mode))
        k = split(_dense(h, p["attn"]["key"], mode))
        v = split(_dense(h, p["attn"]["value"], mode))
        scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d // n_head) ** -0.5
        causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        att = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
        x = x + _dense(att.transpose(0, 2, 1, 3).reshape(b, t, d), p["attn"]["out"], mode)
        h = _gelu_tanh(_dense(_layer_norm(x, p["ln2"], eps), p["mlp_in"], mode))
        return x + _dense(h, p["mlp_out"], mode)

    def head(x, ln_f, head_p, positions):
        x = jnp.take_along_axis(x, positions[:, :, None], axis=1)      # [B, K, D]
        return _dense(_layer_norm(x, ln_f, eps), head_p, mode)          # [B, K, V]

    def highest(fn):
        def run(*a):
            with jax.default_matmul_precision("highest"):
                return fn(*a)
        return jax.jit(run)

    return highest(embed), highest(block), highest(head)


def logits_at(cfg: dict, flat: dict, tokens, positions, mode=None):
    """Logits [B, K, V] at ``positions`` [B, K] of ``tokens`` [B, T], float32."""
    from benchlib.weights import unflatten

    params = unflatten(flat)["params"]
    embed, block, head = _programs(int(cfg["n_head"]), float(cfg["layer_norm_epsilon"]), mode)
    x = embed(params["embed"]["embedding"], params["pos_embed"]["embedding"], tokens)
    for layer in range(int(cfg["n_layer"])):
        x = block(x, params[f"block{layer}"])
    return head(x, params["ln_f"], params["head"], positions)


def served_gaps(cfg: dict, flat: dict, sample, pad_to: int, max_out: int, control=None):
    """Per request, per served token: the reference's best logit minus its
    logit of the served token. With ``control`` (``"fp8"``/``"int8"``) the
    token judged at each position is the one the lower precision puts first,
    over the same prompts and served tokens."""
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
