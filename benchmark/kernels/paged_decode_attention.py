"""The fused paged decode-attention kernel
(``ops/ragged_decode.paged_decode_attention``, PR 30) on the device trace, BY
NAME, in every cell that runs it.

One call is one layer's decode attention over every slot: the kernel DMAs the
pages each slot holds out of the K and V pools in HBM, a chunk at a time, and
computes scores, online softmax and the weighted sum on them. Its events hold
all of that work (the HBM reads are the kernel's own DMAs: checked by hand on
one trace, PERF.md, PR 30), so a share of the HBM roofline over them is sound:
the ``*paged_attention_hbm_roofline`` readers count the K and V bytes of the
positions the residents hold, not the pages moved.

The kernel's events carry the name ``pl.pallas_call`` was given,
``_paged_decode_attention``, which no other operation has. The rank of its
result does not tell it apart in every program: ``models/lfm2_moe``'s prefill
runs its experts through ``jax.lax.ragged_dot``, a Mosaic call whose result is
rank 2 as well (``%ragged-dot-none = f32[4096,3584] custom-call(...``), and
read through a pattern on the rank, the cell's first traced run gave 853.9 us
a call, the mean over 3 attention calls a step (280 us each) and 20 grouped
products a prefill (PERF.md finding PR 33). The latent form
(``_paged_latent_decode_attention``, ``kernels/mla_decode_attention.py``) is
not taken: the name is anchored at the start. Checked by hand on one trace:
three events a step, 0.280 ms each at 59 residents of about 770 positions
(PERF.md finding PR 33)."""

#: The kernel's events on the device trace's "XLA Ops" line: the Mosaic custom
#: call that carries the kernel's own name.
EVENTS = r"^%?_paged_decode_attention[\w.]* = \w+\[\d+,\d+\]\S* custom-call\("
