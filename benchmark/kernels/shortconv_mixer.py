"""The gated short convolution's events of a DECODE step on the device trace
(the ``conv`` layers of ``models/lfm2_moe``,
``jax.named_scope("shortconv")``), told apart by the shapes only that
operator produces or consumes at 64 slots: the input projection ``[64,6144]``
(``B | C | u``, fused with the operator's pre-norm), the window
``[64,2,2048]`` and its update, the new row ``[64,1,2048]`` and the three
rows under the taps ``[64,3,2048]``; the output projection takes the new row
as an operand, so its product (with the conv's sum and the gate fused in) is
among the events. Attention's ``[64,3072]`` and the feed-forwards match
nothing here. The engine's write of one admitted slot's rows into the
``[64,2,2048]`` arrays, inside a prefill run, matches too (microseconds).

The pattern begins ``^(?!%?while\b)``: the looped prefill's outer ``while``
is one event whose label holds the carried state's shapes, these windows
among them (PERF.md finding PR 32.4).

No roofline share is taken over these events: XLA brings the projections'
weights in by asynchronous slices that run under other layers' events
(``slice-start`` / ``ConcatBitcast``: checked by hand on one trace, PERF.md
finding PR 33), so the events' time does not hold the reads;
``shortconv_ms_per_step.replies`` reports the time and
``step_hbm_roofline.replies`` bounds the whole step."""

EVENTS = r"^(?!%?while\b).*(?:\[64,6144\]|\[64,[123],2048\])"
