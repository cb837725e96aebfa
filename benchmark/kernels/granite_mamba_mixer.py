"""The Mamba-2 mixer's events of a DECODE step of Granite-4.0-H on the device
trace (the ``mamba`` layers of ``models/granite_hybrid``, run by
``models/mamba2``, ``jax.named_scope("mamba")``), told apart by the shapes
only this mixer produces or consumes at 64 slots (an event's name carries its
operands' shapes too): the stacked state ``[64,36,64,64,128]`` and windows
``[64,36,3,4352]``, one layer's SSM state ``[64,64,64,128]`` (grouped
``[64,1,64,64,128]``), the input projection (8,512 wide: ``z | xBC | dt``),
the conv window and its channels (4,352 wide), the 4,096-wide gate and
inner activations (also the operand of ``out_proj``'s product) and the
per-head rows ``[64,64,64]`` and ``[64,64]``. Attention's ``q | k | v``
(3,072), its output and the residual stream (2,048) and the MLP (16,384 and
8,192) match nothing here. A prefill's write of one slot's rows into the
stacked arrays matches too (0.4 ms a prefill run). Checked by hand on one
trace (PERF.md): 16.2 of a step's 26.8 device ms, 13.8 of them the 36
fused state updates ``select_dynamic-update-slice_fusion
f32[64,36,64,64,128]``; ``in_proj``'s and ``out_proj``'s weights come in by
asynchronous slices (``slice-done bf16[2048,2128]``, ``[1024,2048]``), which
match nothing here.

The pattern begins ``^(?!%?while\\b)``: the looped prefill's outer ``while``
is one event whose label holds the carried state's shapes, these among them,
and without the prefix a step is charged one prefill's device time on top of
the mixer's (PERF.md's findings on the looped prefill)."""

EVENTS = (r"^(?!%?while\b).*(?:\[64,36,64,64,128\]|\[64,36,3,4352\]|\[64,64,64,128\]|\[64,1,64,64,128\]"
          r"|\[64,8512\]|\[64,\d,4352\]|\[64,4352\]|\[64,4096\]|\[64,64,64\]|\[64,64\]\{)")
