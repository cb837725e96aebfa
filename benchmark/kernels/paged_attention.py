"""The fused paged decode-attention kernel
(``ops/ragged_decode.paged_decode_attention``, PR 30) on the device trace.

One call is one layer's decode attention over every slot: the kernel DMAs the
pages each slot holds out of the K and V pools in HBM, a chunk at a time, and
computes scores, online softmax and the weighted sum on them. Its events hold
all of that work (the HBM reads are the kernel's own DMAs: checked by hand on
one trace, PERF.md, PR 30), so a share of the HBM roofline over them is sound:
``readers/paged_attention_hbm_roofline.py`` counts the K and V bytes of the
positions the residents hold, not the pages moved.

The kernel's result is rank 2 (``[slots, heads * head_dim]``; grouped-query:
``[slots * heads, head_dim]``), which is what tells it from the page-gather
kernel's rank-3 result (``kernels/page_gather.py``): no other Mosaic call of a
decode step has a rank-2 result.
"""

#: The kernel's events on the device trace's "XLA Ops" line: a Mosaic custom
#: call whose result is the attended rows, rank 2.
EVENTS = r"= \w+\[\d+,\d+\]\S* custom-call\(.*tpu_custom_call"
