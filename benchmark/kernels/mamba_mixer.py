"""The Mamba-2 mixer's events of a DECODE step on the device trace (the
``M`` layers of ``models/nemotron_h``, ``jax.named_scope("mamba")``), told
apart by the shapes only it produces at 64 slots: the SSM state
``[64,128,64,128]`` (or grouped ``[64,8,16,64,128]``), the input projection
(18,560 wide), the conv window (10,240 wide) and the 8,192-wide inner
activations. Checked by hand on one trace (PERF.md, PR 27)."""

EVENTS = (r"\[64,128,64,128\]|\[64,8,16,64,128\]|\[64,18560\]|\[64,\d,10240\]|\[64,10240\]"
          r"|\[64,8192\]|\[64,128,64\]")
