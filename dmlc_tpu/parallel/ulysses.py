"""Ulysses attention: all-to-all sequence/context parallelism over ``sp``.

The second of the two standard sequence-parallel schedules (the first,
ring attention, is parallel/ring_attention.py — the reference has neither,
SURVEY.md §5 "long-context: entirely absent"). Where the ring rotates K/V
blocks device-to-device and keeps the sequence sharded throughout, Ulysses
(DeepSpeed-Ulysses style) *re-shards* around the attention op: inputs arrive
sequence-sharded ``[B, H, S/n, Dh]``, one ``all_to_all`` per tensor swaps the
sharded axis from sequence to heads ``[B, H/n, S, Dh]``, each device runs
ordinary dense attention for its head slice over the FULL sequence, and one
``all_to_all`` on the output swaps back.

Trade-offs vs the ring (why both exist):

- communication: Ulysses moves each of q/k/v/o exactly once through an
  all-to-all (O(S·Dh·H/n) per device, bandwidth-optimal, latency-batched);
  the ring issues n-1 dependent ppermute steps — Ulysses wins when the
  all-to-all fits ICI comfortably and n is large, the ring wins when
  compute per block is big enough to hide every hop.
- constraint: Ulysses needs ``H % n == 0`` (heads are the resharded axis);
  ring attention has no head constraint.
- memory: each device materializes its head slice's full [S, S] scores
  unless the local attention is itself blockwise; the ring never holds more
  than an [S/n, S/n] tile. For the extreme sequence lengths the ring is the
  memory-safe choice; Ulysses is the throughput choice for moderate S.

Both are `shard_map` programs over the same mesh axis, so callers can pick
per-call. The collectives ride ICI when ``sp`` is laid out within a pod.
"""

from __future__ import annotations

from functools import partial

import jax
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dmlc_tpu.parallel.ring_attention import dense_attention


def _ulysses_local(q, k, v, *, axis_name: str, causal: bool, scale: float, local_attn=None):
    """Per-device body. q/k/v: [B, H, S/n, Dh] -> same shape/sharding.

    all_to_all(split_axis=1, concat_axis=2) turns the local sequence shard
    into the full sequence for H/n heads; attention is then embarrassingly
    parallel over heads, and the inverse all_to_all restores sequence
    sharding. Differentiable end-to-end (all_to_all transposes to itself
    with the axes swapped). ``local_attn`` swaps the per-device attention
    (default dense; the Pallas flash kernel composes here for O(S) memory
    on the reassembled sequence).
    """
    attn = local_attn or dense_attention
    a2a = partial(lax.all_to_all, axis_name=axis_name, tiled=True)
    # [B, H, S/n, Dh] -> [B, H/n, S, Dh]: heads scatter, sequence gathers.
    qh, kh, vh = (a2a(t, split_axis=1, concat_axis=2) for t in (q, k, v))
    out = attn(qh, kh, vh, causal=causal, scale=scale)
    # [B, H/n, S, Dh] -> [B, H, S/n, Dh].
    return a2a(out, split_axis=2, concat_axis=1)


def ulysses_attention(
    q,
    k,
    v,
    mesh: Mesh,
    *,
    axis_name: str = "sp",
    causal: bool = False,
    scale: float | None = None,
    use_flash: bool = False,
):
    """Sequence-parallel attention via head/sequence all-to-all resharding.

    q/k/v: [B, H, S, Dh] with S sharded over ``axis_name`` in ``mesh``;
    returns [B, H, S, Dh] with the same sharding. Requires the head count to
    be divisible by the ``sp`` extent (checked eagerly — the failure inside
    all_to_all is far less readable). ``use_flash`` runs the per-device
    attention with the blockwise Pallas kernel (ops/pallas_kernels.py)
    instead of dense — sp handles sequences past one chip, flash keeps the
    reassembled full-sequence attention O(S) in memory."""
    n = mesh.shape[axis_name]
    if q.shape[1] % n:
        raise ValueError(
            f"ulysses needs heads % sp == 0: {q.shape[1]} heads over sp={n}"
        )
    if scale is None:
        scale = q.shape[-1] ** -0.5
    local_attn = None
    check = True
    if use_flash:
        from dmlc_tpu.ops.pallas_kernels import flash_attention, interpret_mode

        local_attn = flash_attention
        # check_vma off ONLY for the flash variant in INTERPRET mode
        # (off-TPU): interpret-mode pallas_call's discharge mixes varying
        # and unvarying operands inside dynamic_slice, which the vma checker
        # rejects (jax suggests exactly this workaround). Compiled TPU runs
        # and the dense variant keep full checking.
        check = not interpret_mode()
    spec = P(None, None, axis_name, None)
    fn = partial(
        _ulysses_local, axis_name=axis_name, causal=causal, scale=scale, local_attn=local_attn
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, check_vma=check
    )(q, k, v)
