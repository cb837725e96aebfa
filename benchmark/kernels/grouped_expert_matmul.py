"""A prefill's grouped expert products on the device trace, whatever computes
them: ``jax.lax.ragged_dot``'s Mosaic call (``%ragged-dot-none = f32[11264,2688]
... custom-call(``) or the Pallas kernel ``ops/grouped_matmul`` that replaced
it (``%_grouped_expert_matmul.1 = f32[11264,2688] ... custom-call(``, the name
its ``pallas_call`` was given). ``parallel/moe.held_experts_ffn`` runs two of
them an expert layer in its grouped form, which only a prefill takes in the
cells (``answers``, ``replies``, ``longform``: a 64-slot decode step takes the
dense form and runs neither), so an event is one layer's first or second
product over every pair of one prompt.

The pattern is anchored at the start of the event's name: not the looped
prefill's outer ``while`` (one event whose label holds every carried shape),
not the fused decode-attention kernels (``_paged_decode_attention``,
``_paged_latent_decode_attention``) and not an operation that consumes a
product.

Checked by hand on the trace excerpts of traced runs on a TPU v5 lite
(PERF.md, findings): in ``nemotron3-super.answers`` every prefill run holds
ten such events on either side, two a layer in its five expert layers
(0.83-0.93 ms each through the kernel, 3.2-3.6 ms through ``ragged_dot``),
and in ``lfm2-8b-a1b.replies`` twenty (``ragged_dot``, 0.93-1.80 ms); the
compiled prefill programs of ``replies`` and ``kanana-2-30b-a3b.longform``
hold 20 and 46 kernel calls a prompt and their steps none. No other event of
a prefill run copies or slices an expert's matrix (the heaviest others are
the mixers' input projection and the norms' reductions, about 2 ms each a
run in ``answers``): the held weights are read inside these events, so a
share of the HBM roofline over them is sound.

``layer_bytes`` is one expert layer's held weights: every matrix of every
expert this chip holds, once, at two bytes a value: 128 x 2 x 1,024 x 2,688
(``nemotron3-super``), 32 x 3 x 2,048 x 1,792 (``lfm2-8b-a1b``) and 16 x 3 x
2,048 x 768 (``kanana-2-30b-a3b``) values, from the configuration's keys
through the family's ``benchlib/*_counts`` module; ``None`` for a
configuration without held experts.
"""
from benchlib import deepseek_v3_counts, hybrid_counts, lfm2_moe_counts

#: The grouped products' events on the device trace's "XLA Ops" line.
EVENTS = (r"^%?(?:ragged-dot[\w.-]*|_grouped_expert_matmul[\w.]*) = f32\[\d+,\d+\]\S*"
          r" custom-call\(")
BF16 = 2


def layer_bytes(cfg: dict) -> int | None:
    if "hybrid_override_pattern" in cfg:
        z = hybrid_counts.sizes(cfg)
        return BF16 * z["held"] * hybrid_counts.expert_params(z)
    if "num_experts" in cfg and "layer_types" in cfg:
        z = lfm2_moe_counts.sizes(cfg)
        return BF16 * z["experts"] * lfm2_moe_counts.expert_params(z)
    if "kv_lora_rank" in cfg:
        z = deepseek_v3_counts.sizes(cfg)
        return BF16 * z["held"] * deepseek_v3_counts.expert_params(z)
    return None
