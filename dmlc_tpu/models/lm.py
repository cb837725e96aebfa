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
