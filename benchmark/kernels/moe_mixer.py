"""The LatentMoE mixer's events of a DECODE step on the device trace (the
``E`` layers of ``models/nemotron_h``, ``jax.named_scope("moe")``), told
apart by the shapes only it produces at 64 slots x 22 chosen experts = 1,408
token-expert rows: the two grouped matmuls over the held experts
(``ragged-dot``), the rows gathered for them and scattered back, the latent
projections (1,024 wide), the shared expert (5,376 wide) and the router's
512 scores. Every alternative carries the 64 rows of a decode batch or its
1,408 pairs: a prefill's expert events carry 512 rows and 11,264 pairs and
are not these, and a weight's shape is named by neither program alone.
Checked by hand on one trace (PERF.md, PR 27)."""

EVENTS = (r"ragged-dot[\w.-]* = \w+\[1408,|\[1408[,\]]|\[64,22[,\]]|\[64,512\]|\[64,5376\]|\[64,1024\]")
