"""Ragged paged-KV decode attention: the per-step op of the generation engine.

Autoregressive decode attends ONE new query token per slot against that
slot's cached K/V, whose length differs per slot ("ragged" — per "Ragged
Paged Attention", PAPERS.md). The cache itself is PAGED (generate/kvcache.py):
fixed-size pages drawn from a shared pool, stitched into a per-slot sequence
by an int32 page table — so slots join/leave the running batch without
copying or fragmenting HBM.

Two paths behind the repo's kernel-fallback pattern (ops/pallas_kernels.py),
both over the pool as it lives in device memory, ``[rows, page_size, KV * Dh]``
with every layer's pages in the one row axis (generate/kvcache.py):

- ``gather_kv_pages`` XLA path — ``jnp.take`` over the row axis; what the
  engine runs off-TPU and the parity reference everywhere.
- ``gather_kv_pages`` Pallas path — a page-gather kernel that never stages
  the pool: pool and result are left where the compiler keeps them
  (``pl.ANY``: the pool in HBM), the row ids are prefetched to SMEM, and the
  body issues one DMA per page, pool to result, with a window of them in
  flight — the gather is pure data movement with no
  gather-scatter HLO, no staged copy of a layer's pool, and a cost of the
  pages it moves whatever the pool's size. Interpreter mode off-TPU keeps
  tests hermetic (same seam as the flash kernels).

``ragged_decode_attention`` is the mask-based attention itself: scores are
computed against the full padded [B, S_max] cache view and positions at or
past each slot's kv length are masked to -inf, exactly mirroring
``parallel/ring_attention.dense_attention``'s f32 score/softmax discipline
so paged decode logits match the full-sequence forward bit-for-tolerance
(tests/test_generate.py pins this).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dmlc_tpu.ops.pallas_kernels import interpret_mode

#: Page DMAs the gather kernel keeps in flight (one shared semaphore).
_DMA_WINDOW = 32


def _gather_pages_pallas(pool, rows):
    """[R, P, W] pool gathered by a flat row-id vector -> [len, P, W].

    Every copy moves one page, so they share a DMA semaphore: a wait takes
    one page's worth of it, whichever copy finished.
    """
    n_out = rows.shape[0]
    _, page_size, width = pool.shape
    window = min(_DMA_WINDOW, n_out)

    def gather_kernel(rows_ref, pool_ref, out_ref, sem):
        def page_copy(j):
            return pltpu.make_async_copy(pool_ref.at[rows_ref[j]], out_ref.at[j], sem)

        def issue(j, carry):
            @pl.when(j >= window)
            def _():
                page_copy(j - window).wait()

            page_copy(j).start()
            return carry

        def drain(j, carry):
            page_copy(j).wait()
            return carry

        jax.lax.fori_loop(0, n_out, issue, 0)
        jax.lax.fori_loop(n_out - window, n_out, drain, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, page_size, width), pool.dtype),
        interpret=interpret_mode(),
    )(rows, pool)


def gather_kv_pages(pool, page_table, kv_heads: int, *, first_row=0, use_pallas: bool = False):
    """Assemble the per-slot contiguous cache view from the shared pool.

    ``pool``: [rows, page_size, KV * Dh] (K or V, every layer's pages);
    ``page_table``: int32 [B, max_pages] — row b's sequence is the
    concatenation of its pages in table order (unused entries point at the
    reserved scratch page 0 and are masked out by the attention lengths);
    ``first_row``: the pool row of this layer's page 0.
    Returns [B, max_pages * page_size, KV, Dh].
    """
    b, max_pages = page_table.shape
    _, page_size, width = pool.shape
    rows = page_table.reshape(b * max_pages).astype(jnp.int32) + first_row
    if use_pallas:
        out = _gather_pages_pallas(pool, rows)
    else:
        out = jnp.take(pool, rows, axis=0)
    return out.reshape(b, max_pages * page_size, kv_heads, width // kv_heads)


def ragged_decode_attention(q, k, v, kv_lengths, *, scale: float | None = None):
    """One decode step of attention over ragged per-slot lengths.

    ``q``: [B, H, Dh] (the single new position per slot); ``k``/``v``:
    [B, S_max, KV, Dh] padded cache views (KV = H, or a divisor of H for
    grouped-query attention); ``kv_lengths``: int32 [B] — slot
    b attends positions [0, kv_lengths[b]). Scores and softmax run in f32
    (dense_attention's discipline); output is cast back to q's dtype.
    Callers guarantee kv_lengths >= 1 for every row (inactive slots carry a
    scratch-page row of length 1), so no row is fully masked.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s_max = k.shape[1]
    heads, kv_heads = q.shape[1], k.shape[2]
    if heads != kv_heads:
        return _grouped_decode_attention(q, k, v, kv_lengths, scale)
    scores = jnp.einsum(
        "bhd,bshd->bhs",
        q.astype(jnp.float32) * scale,
        k.astype(jnp.float32),
    )
    mask = jnp.arange(s_max)[None, None, :] < kv_lengths[:, None, None]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def _grouped_decode_attention(q, k, v, kv_lengths, scale):
    """Grouped-query form: ``q`` [B, H, Dh] against ``k``/``v`` [B, S, KV, Dh]
    with H a multiple of KV; query head ``i`` reads KV head ``i // (H / KV)``.
    The query heads are folded onto their KV head, so K and V are read once
    and never copied H / KV times."""
    b, heads, dh = q.shape
    kv_heads = k.shape[2]
    qg = (q.astype(jnp.float32) * scale).reshape(b, kv_heads, heads // kv_heads, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(jnp.float32))
    mask = jnp.arange(k.shape[1])[None, None, None, :] < kv_lengths[:, None, None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, v.astype(jnp.float32))
    return out.reshape(b, heads, dh).astype(q.dtype)
