"""The absorbed latent decode-attention kernel
(``ops/ragged_decode.paged_latent_decode_attention``) on the device trace, BY
NAME (PERF.md Open question 17: a pattern on a Mosaic call's rank also takes
a prefill's grouped products).

The kernel's events carry the name of the jitted function around its
``pl.pallas_call``, ``_paged_latent_decode_attention``, which no other
operation has: not the K/V form (``_paged_decode_attention``, which
``kernels/paged_decode_attention.py``'s pattern anchors at the start of the
name, so neither pattern takes the other's events), not a prefill's
``ragged-dot``, and not the looped prefill's outer ``while`` (Open question
16: this pattern is anchored at the kernel's own name). Its events hold all
of its work: the pool stays in HBM and every read of it is one of the
kernel's own DMAs, each held page once, so shares of the HBM roofline and of
the peak over them are sound.

``bytes`` and ``flops`` count what the ALGORITHM needs for ``positions``
cached positions attended (every layer): 1,152 B and 69,632 FLOP a position
a layer at the published widths, whatever a row is stored as (640 lanes) and
however many terms the kernel's float32 products take (three).
"""
from benchlib import deepseek_v3_counts

#: The kernel's events on the device trace's "XLA Ops" line: the Mosaic custom
#: call that carries the kernel's own name.
EVENTS = r"^%?_paged_latent_decode_attention[\w.]* = \w+\[\d+,\d+\]\S* custom-call\("


def bytes(cfg: dict, positions: float) -> float:  # noqa: A001 (the README's word)
    return deepseek_v3_counts.latent_bytes_per_token(cfg) * float(positions)


def flops(cfg: dict, positions: float) -> float:
    return deepseek_v3_counts.kernel_flops_per_position(cfg) * float(positions)
