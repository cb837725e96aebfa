"""The routed expert layers' events of a DECODE step on the device trace (the
expert layers of ``models/deepseek_v3``, ``jax.named_scope("moe")``:
``parallel/moe.held_experts_ffn`` in its dense form over the 16 experts held
here, gated SiLU experts of 768), told apart by the shapes only that layer
produces or consumes at 64 slots, top 6: the first product ``x (W_1 | W_3)``
over every held expert, ``[16,64,1536]`` (whose event reads the layer's
``W_1 | W_3``), the activation's ``[16,64,768]``, the second product
``[16,64,2048]`` (reads ``W_2``), the gates' scatter into ``[64,17]`` (16 held
experts and one column for the pairs held elsewhere), the router's product
(64 rows of 128 scores AGAINST the ``[2048,128]`` router kernel), its top-k
(the ``s32[64,128]`` indices sorted with the scores, ``[64,6]`` chosen). A
bare ``[64,128]`` is NOT named, only beside the router's kernel.
The shared experts (``jax.named_scope("shared")``) are not routed and
match none of these. A prefill's expert events carry 2,048 rows and 12,288 pairs
(``ragged-dot``) and are not these either.

The pattern begins ``^(?!%?while\\b)``: the looped prefill's outer ``while``
is one event whose label holds every carried shape (PERF.md finding PR 32.4).

``readers/deepseek_v3_moe_hbm_roofline.py`` counts each hit held expert's three
matrices and the router once a layer a step
(``benchlib/deepseek_v3_counts.moe_step_bytes``) over these events' time. The
step compiled for a described v5e (PERF.md finding PR 39) shows what the
events read themselves: ``W_2`` of all 23 expert layers and ``W_1 | W_3`` of
20; layer 1's ``W_1 | W_3`` comes by a cross-program prefetch and two layers'
by asynchronous slices under other events, and the routers' kernels (0.5 MB)
by asynchronous copies. So the share may read up to about a tenth high, never
low. PERF.md finding PR 37.5 read the events by hand on one step: 5.40 of its
22.25 device ms."""

EVENTS = (r"^(?!%?while\b).*(?:\[16,64,(?:1536|768|2048)\]|\[64,17\]|\[64,6(?:,1)?\]"
          r"|s32\[64,128\]|\[64,128\].*\[2048,128\])")
