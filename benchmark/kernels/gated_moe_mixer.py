"""The gated expert layer's events of a DECODE step on the device trace (the
expert layers of ``models/lfm2_moe``, ``jax.named_scope("moe")``:
``parallel/moe.held_experts_ffn`` in its dense form with the family's gated
expert), told apart by the shapes only that layer produces or consumes at 64
slots, top 4 of 32 experts (an event's name carries its operands' shapes
too): the first product ``x (W_1 | W_3)`` over every expert, ``[32,64,3584]``
(whose event reads the layer's ``W_1 | W_3``), the second product, which
takes that array and the ``[64,32]`` gate matrix as operands and reads
``W_2`` (the activation ``silu(h[:1792]) * h[1792:]`` is fused into it:
``[32,64,1792]`` inside the fusion), the router's product (64 rows of 32
scores AGAINST the ``[2048,32]`` router kernel), its top-k (the ``s32[64,32]``
indices sorted with the scores, ``[64,4]`` chosen) and the scatter of the
gates into ``[64,33]``. A bare ``[64,32]`` is NOT named: the per-head RMSNorm
of the 32 query heads and the rotary tables (32 pairs) have that shape too. A
prefill's expert events carry 1,024 rows and 4,096 pairs (``ragged-dot``) and
are not these; a weight's shape alone is named by neither program.

The pattern begins ``^(?!%?while\b)``: the looped prefill's outer ``while``
is one event whose label holds every carried shape (PERF.md finding PR 32.4).

Checked by hand on one trace (PERF.md finding PR 33): every matched group is
the expert layer's, the two products are 98% of the matched time, and
``moe_hbm_roofline.replies`` (``readers/gated_moe_hbm_roofline.py``: each hit
expert's three matrices and the router once a step, over these events' time
and 819 GB/s) reads under 100%: the events hold the reads of ``W_1 | W_3``
and ``W_2`` themselves (operands of the two fusions, no asynchronous slices
under other layers' events), and the count leaves out the feed-forward's
pre-norm, whose reduction is fused into the operator's output product."""

EVENTS = (r"^(?!%?while\b).*(?:\[32,64,(?:3584|1792|2048)\]|\[64,33\]|\[64,4(?:,1)?\]"
          r"|s32\[64,32\]|\[64,32\].*\[2048,32\])")
