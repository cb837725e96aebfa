"""Ragged paged-KV decode attention: the per-step op of the generation engine.

Autoregressive decode attends ONE new query token per slot against that
slot's cached K/V, whose length differs per slot ("ragged" — per "Ragged
Paged Attention", PAPERS.md). The cache itself is PAGED (generate/kvcache.py):
fixed-size pages drawn from a shared pool, stitched into a per-slot sequence
by an int32 page table — so slots join/leave the running batch without
copying or fragmenting HBM.

Two paths behind the repo's kernel-fallback pattern (ops/pallas_kernels.py):

- ``gather_kv_pages`` XLA path — ``jnp.take`` over the page axis; what the
  engine runs off-TPU and the parity reference everywhere.
- ``gather_kv_pages`` Pallas path — a page-gather kernel using scalar
  prefetch (``PrefetchScalarGridSpec``): the page table is prefetched to
  SMEM and drives the BlockSpec index map, so each grid cell DMAs exactly
  one page from the pool into its contiguous output slot — the gather is
  pure data movement with no gather-scatter HLO. Interpreter mode off-TPU
  keeps tests hermetic (same seam as the flash kernels).

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


def _gather_pages_pallas(pages, flat_table):
    """[N, P, D] pages gathered by a flat page-id vector -> [len, P, D].

    One grid cell per output page: the prefetched table entry picks which
    pool page the cell's input block maps to, the output block is the
    cell's own slot — the kernel body is a straight block copy.
    """
    n_out = flat_table.shape[0]
    _, page_size, width = pages.shape

    def copy_kernel(table_ref, page_ref, out_ref):
        del table_ref  # consumed by the index maps, not the body
        out_ref[...] = page_ref[...]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_out,),
        in_specs=[
            pl.BlockSpec((1, page_size, width), lambda j, table: (table[j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, page_size, width), lambda j, table: (j, 0, 0)),
    )
    return pl.pallas_call(
        copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_out, page_size, width), pages.dtype),
        interpret=interpret_mode(),
    )(flat_table, pages)


def gather_kv_pages(pages, page_table, *, use_pallas: bool = False):
    """Assemble the per-slot contiguous cache view from the shared pool.

    ``pages``: [num_pages, page_size, H, Dh] (one layer's K or V pool);
    ``page_table``: int32 [B, max_pages] — row b's sequence is the
    concatenation of its pages in table order (unused entries point at the
    reserved scratch page 0 and are masked out by the attention lengths).
    Returns [B, max_pages * page_size, H, Dh].
    """
    b, max_pages = page_table.shape
    _, page_size, heads, head_dim = pages.shape
    if use_pallas:
        flat = page_table.reshape(b * max_pages).astype(jnp.int32)
        wide = pages.reshape(pages.shape[0], page_size, heads * head_dim)
        out = _gather_pages_pallas(wide, flat)
        return out.reshape(b, max_pages * page_size, heads, head_dim)
    out = jnp.take(pages, page_table.reshape(-1), axis=0)
    return out.reshape(b, max_pages * page_size, heads, head_dim)


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


def paged_decode_attention(
    q, k_pages, v_pages, page_table, kv_lengths,
    *, scale: float | None = None, use_pallas: bool = False,
):
    """Gather + ragged attention in one call: the engine's per-layer step."""
    k = gather_kv_pages(k_pages, page_table, use_pallas=use_pallas)
    v = gather_kv_pages(v_pages, page_table, use_pallas=use_pallas)
    return ragged_decode_attention(q, k, v, kv_lengths, scale=scale)
