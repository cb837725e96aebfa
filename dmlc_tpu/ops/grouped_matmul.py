"""Grouped matmul for a prefill's expert layer: each group of rows times its
own expert's matrix, with every held expert's weights read from HBM once.

``parallel/moe.held_experts_ffn`` sorts a prefill's token-expert pairs by
expert, so the rows of one expert are contiguous, at arbitrary offsets, and
the pairs of experts that live on other chips (and the padding) sort last,
in no group. ``grouped_matmul(lhs, rhs, group_sizes)`` is then
``jax.lax.ragged_dot(lhs, rhs, group_sizes, preferred_element_type=f32)`` on
every row inside a group. What differs is how the weights move:

- ``ragged_dot``'s kernel moves a weight block of about 128 KB a grid step and
  fetches it again for every row tile a group touches, so at a dozen rows an
  expert it runs at its grid-step rate, not at the memory's.
- Here the grid walks VISITS: one (group, row tile) pair for every row tile of
  ``tm`` rows a non-empty group touches, in group order, so a group's visits
  are consecutive and the tiles never go backwards. A group's whole
  ``[k, tn]`` matrix block (``tn = n`` unless two of them overfill the VMEM
  budget) is DMA'd once, into one of two VMEM buffers, and each of its visits
  multiplies one row tile by it. The next non-empty group's copy starts at a
  group's first visit, so the DMA engine always has the next expert in
  flight. An empty group is never visited and its weights are never fetched;
  a row tile that holds no group's row (the pairs held elsewhere, the padding)
  is never visited either.
- A visit writes only the rows of its own group into its output tile (the
  tile stays resident while consecutive visits share it), so rows outside
  every group are left unwritten: the caller masks them.

Operands keep their type and products accumulate in float32. Block sizes come
from the static shapes alone (``blocks``). Off-TPU the kernel runs in the
Pallas interpreter (``pallas_kernels.interpret_mode``), so the CPU tests and
the jitted programs there go through this same code.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dmlc_tpu.ops.pallas_kernels import interpret_mode

#: Rows a visit multiplies: one pass of the matrix unit's 128 rows. A visit
#: costs about the same below that (the unit streams a weight block's
#: columns either way), and a larger tile doubles it for a group of a dozen
#: rows (on one TPU v5e at nemotron3-super's shapes, 256 rows read 80% of the
#: HBM roofline where 128 read 84-86%).
_ROW_TILE = 128
#: VMEM for the two weight buffers: a whole expert's matrix, twice, up to this;
#: above it the matrices are split into blocks of ``n`` (v5e has 128 MiB of
#: VMEM; 14.7 MB is the largest matrix a served cell holds).
_WEIGHT_VMEM_BYTES = 48 << 20
#: VMEM the compiler may use beyond the blocks (its own scratch, the
#: product's temporaries).
_VMEM_SLACK_BYTES = 8 << 20
_LANES = 128


class Blocks(NamedTuple):
    """How ``grouped_matmul`` cuts ``lhs [m, k] x rhs [count, k, n]``."""

    tm: int                 # rows a visit multiplies
    tn: int                 # columns of one weight block (``n`` when whole)
    n_blocks: int           # blocks of ``n``; the grid walks every visit once per block
    max_visits: int         # static bound on visits: row tiles + groups - 1
    vmem_limit_bytes: int   # what the kernel asks of the compiler
    vmem_block_bytes: int   # what its blocks take, the compiler's own scratch aside


def blocks(m: int, k: int, n: int, count: int, lhs_bytes: int = 2,
           rhs_bytes: int = 2) -> Blocks:
    """Block sizes from the static shapes: ``lhs [m, k]`` of ``lhs_bytes`` a
    value, ``rhs [count, k, n]`` of ``rhs_bytes``. A row tile of 128 rows
    (all of them when fewer); the weight block a whole ``[k, n]`` matrix when
    two fit ``_WEIGHT_VMEM_BYTES``, else ``n`` cut into the fewest blocks of
    whole 128-lane columns that fit (``k`` is never cut: a ``[k, 128]`` block
    fits for any ``k`` below 96,000). The VMEM limit: two weight buffers, two
    row tiles, two output tiles and the product's float32 tile, plus slack."""
    tm = m if m <= _ROW_TILE else _ROW_TILE
    n_blocks = 1
    tn = n
    while 2 * k * tn * rhs_bytes > _WEIGHT_VMEM_BYTES:
        n_blocks += 1
        tn = -(-n // n_blocks)
        tn = -(-tn // _LANES) * _LANES
        if tn == _LANES and 2 * k * tn * rhs_bytes > _WEIGHT_VMEM_BYTES:
            raise ValueError(f"no weight block of [{k}, 128] fits {_WEIGHT_VMEM_BYTES} bytes")
    n_blocks = -(-n // tn)
    used = 2 * k * tn * rhs_bytes + 2 * tm * k * lhs_bytes + 3 * tm * tn * 4
    limit = -(-(used + _VMEM_SLACK_BYTES) // (1 << 20)) << 20
    return Blocks(tm=tm, tn=tn, n_blocks=n_blocks,
                  max_visits=-(-m // tm) + count - 1,
                  vmem_limit_bytes=limit, vmem_block_bytes=used)


def visits(group_sizes, tm: int, max_visits: int):
    """The grid's walk, from the groups' sizes: arrays of ``max_visits``
    (entries past the walk's end repeat nothing and are never read) and the
    walk's length.

    - ``group[v]``, ``tile[v]``: visit v multiplies row tile ``tile[v]`` by
      group ``group[v]``'s matrix; the visits of a group are consecutive;
    - ``slot[v]``: the weight buffer the group's matrix is in (non-empty
      groups alternate between two);
    - ``fetch[v]``: at a group's first visit, the next non-empty group, whose
      copy starts then; -1 elsewhere and after the last group;
    - ``offsets``: ``[count + 1]`` first rows of the groups, ``m`` or less last.
    """
    sizes = group_sizes.astype(jnp.int32)
    count = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    live = sizes > 0
    first_tile = starts // tm
    tiles = jnp.where(live, (ends - 1) // tm - first_tile + 1, 0)
    walk_end = jnp.cumsum(tiles)
    walk_start = walk_end - tiles
    walked = walk_end[-1]
    rank = jnp.cumsum(live, dtype=jnp.int32) - 1
    # Each lookup is a [visits, groups] compare and sum that fuses into one
    # operation: no search loop, sort or gather of a few scalars each.
    groups = jnp.arange(count, dtype=jnp.int32)

    def group_of(w):
        return jnp.minimum(jnp.sum(walk_end[None, :] <= w[:, None], axis=1, dtype=jnp.int32),
                           count - 1)

    def pick(values, g):
        return jnp.sum(jnp.where(groups[None, :] == g[:, None], values[None, :], 0), axis=1,
                       dtype=jnp.int32)

    v = jnp.arange(max_visits, dtype=jnp.int32)
    group = group_of(v)
    after = pick(walk_end, group)               # the visit that follows the group's last
    tile = pick(first_tile - walk_start, group) + v
    fetch = jnp.where((v == pick(walk_start, group)) & (after < walked), group_of(after), -1)
    slot = pick(rank, group) % 2
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return group, tile, slot, fetch, offsets, walked


def grouped_matmul(lhs, rhs, group_sizes):
    """``lhs [m, k]`` (rows sorted by group), ``rhs [count, k, n]``,
    ``group_sizes [count]`` (summing to at most ``m``) -> ``[m, n]`` float32:
    row r of group g is ``lhs[r] @ rhs[g]`` accumulated in float32. Rows past
    the last group are left as the kernel found them."""
    m, k = lhs.shape
    count, _, n = rhs.shape
    return _grouped_expert_matmul(
        lhs, rhs, group_sizes,
        blocks=blocks(m, k, n, count, lhs.dtype.itemsize, rhs.dtype.itemsize),
        interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("blocks", "interpret"))
def _grouped_expert_matmul(lhs, rhs, group_sizes, *, blocks: Blocks, interpret: bool):
    m, k = lhs.shape
    _, _, n = rhs.shape
    tm, tn, n_blocks = blocks.tm, blocks.tn, blocks.n_blocks
    last_width = n - (n_blocks - 1) * tn
    group, tile, slot, fetch, offsets, walked = visits(group_sizes, tm, blocks.max_visits)

    def weight_copy(j, g, buf, rhs_ref, w_buf, sem, act):
        """``act`` (start, or wait for) the copy of block j of group g's
        matrix into buffer ``buf``; the last block of a ragged ``n`` copies
        its own width and leaves the buffer's other columns as they were
        (they only reach output columns past ``n``, which are not written)."""
        def copy(width):
            act(pltpu.make_async_copy(rhs_ref.at[g, :, pl.ds(j * tn, width)],
                                      w_buf.at[buf, :, pl.ds(0, width)], sem.at[buf]))

        if last_width == tn:
            copy(tn)
            return

        @pl.when(j < n_blocks - 1)
        def _():
            copy(tn)

        @pl.when(j == n_blocks - 1)
        def _():
            copy(last_width)

    def kernel(group_ref, tile_ref, slot_ref, fetch_ref, offsets_ref, lhs_ref, rhs_ref,
               out_ref, w_buf, sem):
        j, v = pl.program_id(0), pl.program_id(1)
        g, buf = group_ref[v], slot_ref[v]

        @pl.when(v == 0)
        def _():
            weight_copy(j, g, buf, rhs_ref, w_buf, sem, lambda c: c.start())

        @pl.when(fetch_ref[v] >= 0)
        def _():
            weight_copy(j, fetch_ref[v], 1 - buf, rhs_ref, w_buf, sem, lambda c: c.start())

        @pl.when((v == 0) | (group_ref[jnp.maximum(v - 1, 0)] != g))
        def _():
            weight_copy(j, g, buf, rhs_ref, w_buf, sem, lambda c: c.wait())

        row = tile_ref[v] * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        product = jnp.dot(lhs_ref[...], w_buf[buf], preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(mine, product, out_ref[...])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        # The walk's length is known on the device alone: the grid is as long
        # as it (at least one step, which writes nothing when no row is held).
        grid=(n_blocks, jnp.maximum(walked, 1)),
        in_specs=[pl.BlockSpec((tm, k), lambda j, v, group, tile, *_: (tile[v], 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tm, tn), lambda j, v, group, tile, *_: (tile[v], j)),
        scratch_shapes=[pltpu.VMEM((2, k, tn), rhs.dtype), pltpu.SemaphoreType.DMA((2,))],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=blocks.vmem_limit_bytes),
        interpret=interpret,
        name="_grouped_expert_matmul",
    )(group, tile, slot, fetch, offsets, lhs, rhs)
