"""The fused paged decode-attention kernel
(``ops/ragged_decode.paged_decode_attention``) on the device trace, BY NAME,
for a program whose prefill holds other Mosaic calls with a rank-2 result.

``kernels/paged_attention.py`` tells the kernel from every other Mosaic call
by the rank of its result, which holds for a model whose programs run no
other Mosaic kernel. ``models/lfm2_moe``'s prefill runs its experts through
``jax.lax.ragged_dot``, a Mosaic call a product on the chip whose result is
rank 2 as well (``%ragged-dot-none = f32[4096,3584] custom-call(...``): read
through that pattern the cell's first traced run gave 853.9 us a call, the
mean over 3 attention calls a step (280 us each) and 20 grouped products a
prefill (PERF.md finding PR 33). The kernel's events carry the name
``pl.pallas_call`` was given, ``_paged_decode_attention``, which no other
operation has; as in ``kernels/paged_attention.py`` its events hold all of
its work (the HBM reads are the kernel's own DMAs), so a share of the HBM
roofline over them is sound. Checked by hand on one trace: three events a
step, 0.280 ms each at 59 residents of about 770 positions (PERF.md finding
PR 33)."""

#: The kernel's events on the device trace's "XLA Ops" line: the Mosaic custom
#: call that carries the kernel's own name.
EVENTS = r"^%?_paged_decode_attention[\w.]* = \w+\[\d+,\d+\]\S* custom-call\("
