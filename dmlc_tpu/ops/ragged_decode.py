"""Ragged paged-KV decode attention: the per-step op of the generation engine.

Autoregressive decode attends ONE new query token per slot against that
slot's cached K/V, whose length differs per slot ("ragged" — per "Ragged
Paged Attention", PAPERS.md). The cache itself is PAGED (generate/kvcache.py):
fixed-size pages drawn from a shared pool, stitched into a per-slot sequence
by an int32 page table — so slots join/leave the running batch without
copying or fragmenting HBM. Everything here reads the pool as it lives in
device memory, ``[rows, page_size, KV * Dh]`` with every layer's pages in the
one row axis.

- ``paged_decode_attention`` — what the engine runs on the TPU: one Pallas
  kernel a layer, straight from the K and V pools. The pools stay in HBM
  (``pl.ANY``); page table, lengths and the layer's first row are prefetched
  to SMEM; per slot only the pages that hold its positions are DMA'd, a chunk
  at a time into double-buffered VMEM with the next chunk in flight; online
  softmax over the chunks in float32. No padded view of the cache exists and
  no page is unfolded to heads. Interpreter mode off-TPU keeps tests hermetic
  (same seam as the flash kernels).
- ``paged_latent_decode_attention`` — the same pipeline for a family that
  caches ONE latent row a position, shared by all its heads
  (``models/deepseek_v3``; generate/kvcache.py, "Layout"): one pool, each
  held page DMA'd once, the heads' queries against the chunk as stored (a
  true ``[H, row] x [row, chunk]`` product), the weighted sum over the first
  ``value_lanes`` lanes of the same buffer. Its reference, and what the
  engine runs off-TPU: ``gather_latent_pages`` + ``latent_decode_attention``.
- ``gather_kv_pages`` + ``ragged_decode_attention`` — the reference the kernel
  is pinned against, and what the engine runs off-TPU: ``jnp.take`` over the
  row axis assembles the padded ``[B, S_max]`` view, scores are computed
  against all of it and positions at or past each slot's kv length are masked
  to -inf, exactly mirroring ``parallel/ring_attention.dense_attention``'s f32
  score/softmax discipline so paged decode logits match the full-sequence
  forward bit-for-tolerance (tests/test_generate.py pins this). The
  contiguous-cache engine calls ``ragged_decode_attention`` alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dmlc_tpu.ops.pallas_kernels import interpret_mode

#: Cached positions the fused kernel attends at a time: pages of this many
#: tokens are in flight while the pages before them are computed (K and V of
#: 16 gpt2-large pages = 32 DMAs, the window PR 28 measured as saturating).
_CHUNK_TOKENS = 256


def _bf16_terms(x):
    """``x`` as a sum of bfloat16 arrays, exactly: itself where it is stored
    so, else three terms (8 + 8 + 8 mantissa bits hold a float32's 24). The
    matrix unit multiplies bfloat16 pairs exactly and sums in float32, so a
    product of such terms is float32 arithmetic in another summation order."""
    if x.dtype == jnp.bfloat16:
        return [x]
    terms, rest = [], x.astype(jnp.float32)
    for _ in range(3):
        term = rest.astype(jnp.bfloat16)
        terms.append(term)
        rest = rest - term.astype(jnp.float32)
    return terms


def _fold(stacked, rows: int):
    """A product whose left operand was bfloat16 terms stacked on rows (three
    of them, or the one of an operand that is stored in bfloat16)."""
    if stacked.shape[0] == rows:
        return stacked
    return stacked[:rows] + stacked[rows:2 * rows] + stacked[2 * rows:]


def _page_pipeline(table_ref, len_ref, first_ref, pools, sem, *, slots: int, max_pages: int,
                   page_size: int, chunk_pages: int):
    """The DMA pipeline both kernels share: a slot's pages leave the pool(s) a
    chunk at a time into one of two VMEM buffers. ``pools``: (pool ref in HBM,
    its ``[2, chunk, width]`` buffer) for each pool a page is read from: K and
    V, or the one latent pool. Returns ``pages_of(b)`` (pages that hold slot
    b's positions) and ``advance(b, c, chunks, buf)``, which puts the NEXT
    chunk in flight into the other buffer (this slot's, or the next slot's
    first) and waits for chunk c of slot b in ``buf``; ``start`` begins the
    very first."""

    def pages_of(b):
        return (len_ref[b] + page_size - 1) // page_size

    def page_copies(b, c, buf, act):
        """``act`` (start, or wait for) the copies of the pages of chunk c of
        slot b into buffer ``buf``. Every copy moves one page of one row
        width, so a buffer's copies share its semaphore: a wait takes one
        page's worth of it, whichever copy finished."""
        count = jnp.minimum(pages_of(b) - c * chunk_pages, chunk_pages)

        def body(j, carry):
            row = first_ref[0] + table_ref[b * max_pages + c * chunk_pages + j]
            for pool_ref, buf_ref in pools:
                act(pltpu.make_async_copy(
                    pool_ref.at[row], buf_ref.at[buf, pl.ds(j * page_size, page_size)],
                    sem.at[buf]))
            return carry

        jax.lax.fori_loop(0, count, body, 0)

    def start(b, c, buf):
        page_copies(b, c, buf, lambda copy: copy.start())

    def advance(b, c, chunks, buf):
        more = c + 1 < chunks

        @pl.when(more | (b + 1 < slots))
        def _():
            start(jnp.where(more, b, b + 1), jnp.where(more, c + 1, 0), 1 - buf)

        page_copies(b, c, buf, lambda copy: copy.wait())

    return pages_of, start, advance


def paged_decode_attention(q, k_pool, v_pool, page_table, kv_lengths, *, first_row, kv_heads: int):
    """One decode step of attention straight from the K and V pools.

    ``q``: [B, H, Dh]; ``k_pool``/``v_pool``: [rows, page_size, KV * Dh] as
    they live in device memory (every layer's pages in the row axis);
    ``page_table``: int32 [B, max_pages]; ``kv_lengths``: int32 [B], every
    one >= 1; ``first_row``: the pool row of this layer's page 0. Slot b
    attends positions [0, kv_lengths[b]) of the pages its table row names,
    and only the ``ceil(kv_lengths[b] / page_size)`` pages that hold them
    leave the pool: a chunk of pages at a time into one of two VMEM buffers,
    the next chunk (this slot's, or the next slot's first) in flight while
    this one is computed. -> [B, H, Dh] in q's dtype.

    A page stays ``[page_size, KV * Dh]``, lane-dense, and is never unfolded
    to heads. The slot's query is spread to ``[H, KV * Dh]``, row h nonzero
    in the lanes of its KV head only, so ``scores[H, T] = q_spread @ k.T``
    and ``acc[H, KV * Dh] += p @ v`` are two plain matmuls with K and V as
    stored; row h of ``acc`` is read back from its own lanes. Which lanes
    those are follows from (H, KV, Dh): one row of H * Dh lanes when
    H == KV, rows of Dh lanes for grouped-query. Scores, softmax (online over
    the chunks) and the weighted sum are float32 (``_bf16_terms``).

    ``first_row`` is an operand, so a model's layers share one traced and
    lowered kernel (a step of 36 layers would else trace it 36 times).
    """
    _, page_size, _ = k_pool.shape
    chunk_pages = max(1, min(_CHUNK_TOKENS // page_size, page_table.shape[1]))
    return _paged_decode_attention(q, k_pool, v_pool, page_table, kv_lengths, first_row,
                                   kv_heads=kv_heads, chunk_pages=chunk_pages,
                                   interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("kv_heads", "chunk_pages", "interpret"))
def _paged_decode_attention(q, k_pool, v_pool, page_table, kv_lengths, first_row, *,
                            kv_heads: int, chunk_pages: int, interpret: bool):
    slots, heads, head_dim = q.shape
    _, page_size, width = k_pool.shape
    max_pages = page_table.shape[1]
    group = heads // kv_heads
    chunk = chunk_pages * page_size
    rows = -(-heads // 16) * 16  # query rows, padded to whole bfloat16 tiles
    qs = q.astype(jnp.float32) * head_dim ** -0.5
    qs = qs.reshape(slots, width) if group == 1 else qs.reshape(slots * heads, head_dim)

    def kernel(table_ref, len_ref, first_ref, q_ref, k_ref, v_ref, out_ref, k_buf, v_buf, sem):
        pages_of, start, advance = _page_pipeline(
            table_ref, len_ref, first_ref, ((k_ref, k_buf), (v_ref, v_buf)), sem, slots=slots,
            max_pages=max_pages, page_size=page_size, chunk_pages=chunk_pages)

        row_head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0) // group
        own_lanes = row_head == jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1) // head_dim

        def spread_query(b):
            """Slot b's scaled query as bfloat16 terms stacked on rows: [3 * rows, width]."""
            if group == 1:
                spread = jnp.broadcast_to(q_ref[pl.ds(b, 1), :], (rows, width))
            else:
                mine = q_ref[pl.ds(pl.multiple_of(b * heads, heads), heads), :]
                spread = jnp.concatenate([mine] * kv_heads, axis=1)
                if rows > heads:
                    spread = jnp.concatenate(
                        [spread, jnp.zeros((rows - heads, width), jnp.float32)], axis=0)
            return jnp.concatenate(_bf16_terms(jnp.where(own_lanes, spread, 0.0)), axis=0)

        def fold(stacked):
            return _fold(stacked, rows)

        def slot(b, buf):
            length = len_ref[b]
            chunks = (pages_of(b) + chunk_pages - 1) // chunk_pages
            q_terms = spread_query(b)

            def attend(c, carry):
                buf, m, l, acc = carry
                advance(b, c, chunks, buf)
                base = c * chunk

                # Rows past the length hold whatever the buffer or the page's
                # tail held: p is 0 there, and 0 * NaN is not.
                @pl.when(base + chunk > length)
                def _():
                    held = base + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < length
                    v_buf[buf] = jnp.where(held, v_buf[buf], jnp.zeros((), v_buf.dtype))

                scores = sum(
                    fold(jax.lax.dot_general(q_terms, k, (((1,), (1,)), ((), ())),
                                             preferred_element_type=jnp.float32))
                    for k in _bf16_terms(k_buf[buf]))
                held = base + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < length
                scores = jnp.where(held, scores, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(scores - m_new)
                p_terms = jnp.concatenate(_bf16_terms(p), axis=0)
                weighted = sum(
                    fold(jnp.dot(p_terms, v, preferred_element_type=jnp.float32))
                    for v in _bf16_terms(v_buf[buf]))
                return (1 - buf, m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                        alpha * acc + weighted)

            buf, _, l, acc = jax.lax.fori_loop(0, chunks, attend, (
                buf, jnp.full((rows, 1), -jnp.inf, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32), jnp.zeros((rows, width), jnp.float32)))
            attended = acc / l
            if group == 1:
                out_ref[pl.ds(b, 1), :] = jnp.sum(
                    jnp.where(own_lanes, attended, 0.0), axis=0, keepdims=True)
            else:
                for g in range(kv_heads):
                    out_ref[pl.ds(pl.multiple_of(b * heads, heads) + g * group, group), :] = (
                        attended[g * group:(g + 1) * group, g * head_dim:(g + 1) * head_dim])
            return buf

        start(0, 0, 0)
        jax.lax.fori_loop(0, slots, slot, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, chunk, width), k_pool.dtype),
                        pltpu.VMEM((2, chunk, width), v_pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    # A rank-2 result: [B, H * Dh] or, grouped, [B * H, Dh].
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qs.shape, jnp.float32),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), kv_lengths.astype(jnp.int32),
      jnp.asarray(first_row, jnp.int32).reshape(1), qs, k_pool, v_pool)
    return out.reshape(q.shape).astype(q.dtype)


def paged_latent_decode_attention(q, pool, page_table, kv_lengths, *, first_row,
                                  value_lanes: int, scale: float):
    """One decode step of attention over ONE latent row a cached position,
    shared by all heads, straight from its pool (absorbed latent attention:
    the heads' queries arrive already multiplied into the latent's lanes).

    ``q``: [B, H, row]; ``pool``: [rows, page_size, row] as it lives in device
    memory; ``page_table``, ``kv_lengths`` (every one >= 1), ``first_row`` as
    ``paged_decode_attention`` takes them, and the same pipeline: only a
    slot's own pages leave the pool, EACH ONCE (keys and values are the same
    rows), a chunk at a time into one of two VMEM buffers with the next in
    flight. Per chunk ``scores[H, T] = (q @ rows.T) * scale`` over all ``row``
    lanes, a true product with the chunk as stored, and ``acc[H, value_lanes]
    += p @ rows[:, :value_lanes]``, the weighted sum over the first
    ``value_lanes`` lanes of the same buffer. Scores, softmax (online over
    the chunks) and the weighted sum are float32 (``_bf16_terms``); the scale
    multiplies the float32 SCORES, so a query stored in bfloat16 is its own
    one term and its product with a bfloat16 pool is exact in one pass.
    -> [B, H, value_lanes] in q's dtype."""
    _, page_size, _ = pool.shape
    chunk_pages = max(1, min(_CHUNK_TOKENS // page_size, page_table.shape[1]))
    return _paged_latent_decode_attention(
        q, pool, page_table, kv_lengths, first_row, value_lanes=value_lanes, scale=float(scale),
        chunk_pages=chunk_pages, interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("value_lanes", "scale", "chunk_pages", "interpret"))
def _paged_latent_decode_attention(q, pool, page_table, kv_lengths, first_row, *,
                                   value_lanes: int, scale: float, chunk_pages: int,
                                   interpret: bool):
    slots, heads, width = q.shape
    _, page_size, _ = pool.shape
    max_pages = page_table.shape[1]
    chunk = chunk_pages * page_size
    rows = -(-heads // 16) * 16  # query rows, padded to whole bfloat16 tiles

    def kernel(table_ref, len_ref, first_ref, q_ref, pool_ref, out_ref, row_buf, sem):
        pages_of, start, advance = _page_pipeline(
            table_ref, len_ref, first_ref, ((pool_ref, row_buf),), sem, slots=slots,
            max_pages=max_pages, page_size=page_size, chunk_pages=chunk_pages)

        def slot(b, buf):
            length = len_ref[b]
            chunks = (pages_of(b) + chunk_pages - 1) // chunk_pages
            mine = q_ref[pl.ds(pl.multiple_of(b * heads, heads), heads), :]
            if rows > heads:
                mine = jnp.concatenate(
                    [mine, jnp.zeros((rows - heads, width), mine.dtype)], axis=0)
            q_terms = jnp.concatenate(_bf16_terms(mine), axis=0)       # [1 or 3 * rows, width]

            def attend(c, carry):
                buf, m, l, acc = carry
                advance(b, c, chunks, buf)
                base = c * chunk

                # Rows past the length hold whatever the buffer or the page's
                # tail held: p is 0 there, and 0 * NaN is not.
                @pl.when(base + chunk > length)
                def _():
                    held = base + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0) < length
                    row_buf[buf] = jnp.where(held, row_buf[buf], jnp.zeros((), row_buf.dtype))

                latent = row_buf[buf]                                  # [chunk, width]
                scores = scale * sum(
                    _fold(jax.lax.dot_general(q_terms, k, (((1,), (1,)), ((), ())),
                                              preferred_element_type=jnp.float32), rows)
                    for k in _bf16_terms(latent))
                held = base + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1) < length
                scores = jnp.where(held, scores, -jnp.inf)
                m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(scores - m_new)
                p_terms = jnp.concatenate(_bf16_terms(p), axis=0)
                weighted = sum(
                    _fold(jnp.dot(p_terms, v, preferred_element_type=jnp.float32), rows)
                    for v in _bf16_terms(latent[:, :value_lanes]))
                return (1 - buf, m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True),
                        alpha * acc + weighted)

            buf, _, l, acc = jax.lax.fori_loop(0, chunks, attend, (
                buf, jnp.full((rows, 1), -jnp.inf, jnp.float32),
                jnp.zeros((rows, 1), jnp.float32), jnp.zeros((rows, value_lanes), jnp.float32)))
            out_ref[pl.ds(pl.multiple_of(b * heads, heads), heads), :] = (acc / l)[:heads]
            return buf

        start(0, 0, 0)
        jax.lax.fori_loop(0, slots, slot, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM), pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        scratch_shapes=[pltpu.VMEM((2, chunk, width), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((slots * heads, value_lanes), jnp.float32),
        interpret=interpret,
    )(page_table.reshape(-1).astype(jnp.int32), kv_lengths.astype(jnp.int32),
      jnp.asarray(first_row, jnp.int32).reshape(1), q.reshape(slots * heads, width), pool)
    return out.reshape(slots, heads, value_lanes).astype(q.dtype)


def gather_latent_pages(pool, page_table, *, first_row=0):
    """The per-slot contiguous view of a latent pool ``[rows, page_size, row]``:
    row b's sequence is its pages in table order -> [B, max_pages * page_size,
    row] (``gather_kv_pages`` without heads to unfold)."""
    b, max_pages = page_table.shape
    _, page_size, width = pool.shape
    rows = page_table.reshape(b * max_pages).astype(jnp.int32) + first_row
    return jnp.take(pool, rows, axis=0).reshape(b, max_pages * page_size, width)


def latent_decode_attention(q, rows, kv_lengths, *, value_lanes: int, scale: float):
    """Dense latent attention, the latent kernel's pin: ``q`` [B, H, row]
    against ``rows`` [B, S_max, row] (one row a position for all heads), slot
    b over positions [0, kv_lengths[b]); scores over all lanes times
    ``scale``, softmax in float32, the weighted sum over the rows' first
    ``value_lanes`` lanes -> [B, H, value_lanes] in q's dtype."""
    rows = rows.astype(jnp.float32)
    scores = jnp.einsum("bhr,bsr->bhs", q.astype(jnp.float32) * scale, rows)
    mask = jnp.arange(rows.shape[1])[None, None, :] < kv_lengths[:, None, None]
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsv->bhv", probs, rows[..., :value_lanes]).astype(q.dtype)


def gather_kv_pages(pool, page_table, kv_heads: int, *, first_row=0):
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
