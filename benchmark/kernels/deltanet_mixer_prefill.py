"""The gated delta-rule mixer's events of a PREFILL on the device trace (the
``linear_attention`` layers of ``models/olmo_hybrid``, a prompt padded to
1,536 positions = 24 chunks of 64): the input projection ``[1536,17280]``,
the conv's channels ``[1536,11520]`` (1,539 rows with the window in front)
and the window kept ``[3,11520]``, the per-head rows ``[1536,30,96]`` /
``[1536,30,192]`` / ``[1536,30]``, the chunked arrays ``[24,30,64,...]``
(decay-weighted triangles, the triangular solve, its right-hand sides), the
scan over chunks against the carried state (``[30,64,...]``, ``[30,192,96]``)
and the 5,760-wide gated read with the output projection that consumes it.
Attention's ``[1536,30,128]`` and ``[30,1,1536,1536]`` match nothing here.
The ``while`` event that wraps a layer's scan is left out: its body's events
are on the same line and would be counted twice. Checked by hand on one
trace: 67 of a prefill's 113 device ms, every matched group the mixer's
(PERF.md, PR 31)."""

EVENTS = (r"^(?!%?while\b).*(?:\[1536,17280\]|\[153[69],11520\]|\[3,11520\]|\[1536,5760\]|\[1536,2880\]"
          r"|\[1536,60\]|\[1536,30\]|\[1536,30,(?:96|192)\]|\[24,30,|\[24,64,30|\[30,64,\d+\]|\[30,192,96\])")
