"""Servable language models for the generation engine.

The registry's image models map name -> flax module + input geometry; this
module does the same for causal LMs built on ``parallel.sp_transformer.
SPTransformerLM`` — the architecture the lm_flash_train bench leg already
trains. Registering here makes an LM a first-class
registry citizen: the generation worker builds it by name, weights publish/
hot-swap through the existing SDFS blob path (``models/<name>``), and
``weights.variables_template`` validates blobs against the same abstract
init every other model uses.

``lm_small`` is deliberately tiny (2 layers, 128 hidden): it initializes
from seed in well under a second on the CPU test mesh, so generation has a
servable model with no new checkpoints (ISSUE 7 satellite). Production-
scale entries should follow the bench geometry — heads sized so head_dim
is 128, the MXU lane width (see ops/pallas_kernels.flash_attention).
"""

from __future__ import annotations

import jax.numpy as jnp


def lm_wide(dtype=jnp.float32):
    """The gang-serving proof model (ISSUE 17): head_dim 128 (the MXU lane
    width the bench geometry calls for), sized so its resident weights
    overflow the single-chip HBM budget in the test harness — it only serves
    sharded, across a chip gang the PlacementAdvisor picks from HBM headroom.
    Geometry: 4 heads x 128 head_dim = 512 hidden, 2 layers, vocab 2048
    (~6M params: seed-init stays sub-second on the CPU test mesh)."""
    from dmlc_tpu.parallel.sp_transformer import SPTransformerLM

    return SPTransformerLM(
        vocab=LM_WIDE_VOCAB,
        num_layers=2,
        num_heads=4,
        hidden=512,
        mlp_dim=1024,
        max_len=LM_WIDE_MAX_LEN,
        schedule="dense",
        dtype=dtype,
    )


LM_WIDE_VOCAB = 2048
LM_WIDE_MAX_LEN = 128
LM_WIDE_NUM_HEADS = 4


def lm_small(dtype=jnp.float32):
    """A seed-initialized small causal LM (dense attention schedule: the
    single-device regime; the generation engine supplies its own paged
    decode attention, so the schedule only governs training/prefill)."""
    from dmlc_tpu.parallel.sp_transformer import SPTransformerLM

    return SPTransformerLM(
        vocab=LM_SMALL_VOCAB,
        num_layers=2,
        num_heads=2,
        hidden=128,
        mlp_dim=256,
        max_len=LM_SMALL_MAX_LEN,
        schedule="dense",
        dtype=dtype,
    )


LM_SMALL_VOCAB = 1024
LM_SMALL_MAX_LEN = 256


# ---------------------------------------------------------------------------
# The GPT-2 family behind the generation engine's seam
# ---------------------------------------------------------------------------
#
# ``generate/engine.py`` owns batching, pages, slots and sampling; a model
# family owns its math as two pure functions over explicit state, *prefill
# over a padded prompt* and *one decode step*. This is ``SPTransformerLM``'s
# math parameter-for-parameter (same trees, flax LayerNorm/Dense/gelu
# semantics, dense_attention's f32 score discipline), so decode logits match
# the full-sequence ``lm.apply`` within float tolerance.


def _layer_norm(x, p):
    # flax.linen.LayerNorm semantics: population moments over the last
    # axis, epsilon 1e-6, learned scale + bias.
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + 1e-6) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _split_heads(x, num_heads: int):
    # [..., D] -> [..., H, Dh]
    return x.reshape(*x.shape[:-1], num_heads, x.shape[-1] // num_heads)


class TransformerFamily:
    """``SPTransformerLM`` (learned positions, MHA, LayerNorm, GELU) as the
    engine sees it: every layer has K/V, and there is no state beside the
    pages."""

    def __init__(self, module, dtype) -> None:
        self.dtype = dtype
        self.vocab = int(module.vocab)
        self.max_len = int(module.max_len)
        self.num_layers = int(module.num_layers)
        self.num_heads = int(module.num_heads)
        self.kv_layers = self.num_layers
        self.kv_heads = self.num_heads
        self.head_dim = int(module.hidden) // self.num_heads

    def state_shapes(self, max_slots: int) -> dict:
        return {}

    def work_attrs(self, aux: dict, rows: int) -> dict:
        return {}

    def prefill(self, params, tokens, length, slot, kv, state):
        """tokens [1, S] padded at the end -> (logits at ``length - 1`` [V]
        float32, state, aux). Exact because padding sits at the END under a
        causal mask: no real position can attend to it."""
        import jax

        from dmlc_tpu.parallel.ring_attention import dense_attention

        del slot
        s_pad = tokens.shape[1]
        x = params["embed"]["embedding"][tokens] + params["pos_embed"]["embedding"][
            jnp.arange(s_pad)
        ][None, :]
        x = x.astype(self.dtype)
        for layer in range(self.num_layers):
            blk = params[f"block{layer}"]
            h = _layer_norm(x, blk["ln1"])
            q = _split_heads(_dense(h, blk["attn"]["query"]), self.num_heads)
            k = _split_heads(_dense(h, blk["attn"]["key"]), self.num_heads)
            v = _split_heads(_dense(h, blk["attn"]["value"]), self.num_heads)
            kv.write_prefill(layer, k[0], v[0])
            qh = q.transpose(0, 2, 1, 3)  # [1, H, S, Dh]
            att = dense_attention(
                qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3), causal=True
            ).transpose(0, 2, 1, 3)
            x = x + _dense(att.reshape(1, s_pad, -1), blk["attn"]["out"])
            h2 = _layer_norm(x, blk["ln2"])
            h2 = jax.nn.gelu(_dense(h2, blk["mlp_in"]))
            x = x + _dense(h2, blk["mlp_out"])
        x = _layer_norm(x, params["ln_f"])
        logits = _dense(x, params["head"]).astype(jnp.float32)  # [1, S, V]
        return jnp.take(logits[0], length - 1, axis=0), state, {}

    def decode(self, params, tokens, lengths, active, kv, state):
        """tokens [B] -> (logits [B, V] float32, state, aux)."""
        import jax

        del active
        pos = jnp.minimum(lengths, self.max_len - 1)
        x = params["embed"]["embedding"][tokens] + params["pos_embed"]["embedding"][pos]
        x = x.astype(self.dtype)
        for layer in range(self.num_layers):
            blk = params[f"block{layer}"]
            h = _layer_norm(x, blk["ln1"])
            q = _split_heads(_dense(h, blk["attn"]["query"]), self.num_heads)
            k = _split_heads(_dense(h, blk["attn"]["key"]), self.num_heads)
            v = _split_heads(_dense(h, blk["attn"]["value"]), self.num_heads)
            att = kv.write_attend(layer, q, k, v)
            x = x + _dense(att.reshape(att.shape[0], -1), blk["attn"]["out"])
            h2 = _layer_norm(x, blk["ln2"])
            h2 = jax.nn.gelu(_dense(h2, blk["mlp_in"]))
            x = x + _dense(h2, blk["mlp_out"])
        x = _layer_norm(x, params["ln_f"])
        return _dense(x, params["head"]).astype(jnp.float32), state, {}
