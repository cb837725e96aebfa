"""The gated delta-rule mixer's events of a DECODE step on the device trace
(the ``linear_attention`` layers of ``models/olmo_hybrid``,
``jax.named_scope("deltanet")``), told apart by the shapes only that mixer
produces or consumes at 32 slots (an event's name carries its operands'
shapes too): the matrix state ``[32,30,192,96]``, the input projection
(17,280 wide: ``q | k | v | gate``) and ``b | a`` (60), the conv windows and
their channels (11,520 wide), the per-head rows ``[32,30,96]``, ``[32,30,192]``
and ``[32,30]``, and the 5,760-wide gated read, which is also the operand of
the output projection's product, so that product is among the events (its
weights are not: XLA brings them in by asynchronous slices that run under
other layers' events, so ``benchlib/olmo_hybrid_counts.deltanet_weight_bytes``
leaves them out). The full-attention layers' projections stay 3,840 wide
each and match nothing here. A prefill's write of one slot's rows into the
``[32,30,192,96]`` and ``[32,3,11520]`` arrays matches too: 0.04 ms a step.
Checked by hand on one trace: 8.5 of a step's 18.9 device ms, every matched
group the mixer's (PERF.md, PR 31)."""

EVENTS = (r"\[32,30,192,96\]|\[32,17280\]|\[32,\d,11520\]|\[32,11520\]|\[32,5760\]|\[32,2880\]"
          r"|\[32,30,192\]|\[32,30,96\]|\[32,60\]|\[32,30\]")
