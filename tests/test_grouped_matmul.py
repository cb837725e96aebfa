"""``ops/grouped_matmul``: the prefill's grouped expert matmul, in the Pallas
interpreter, pinned to ``jax.lax.ragged_dot`` (float32 output) on every row
inside a group; its walk over (group, row tile) visits; and the block sizes
its shape rule gives the three cells whose prefill runs it."""

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_tpu.ops import grouped_matmul as gm  # noqa: E402

#: (rows, k, n, group sizes): each case names what it covers.
CASES = {
    # empty groups first, inside and last; a group across the first row tile's
    # end and larger than a tile; 125 rows past the last group; k and n no
    # multiple of 128
    "empty_groups_and_a_tail": (300, 72, 200, [0, 5, 130, 0, 40, 0]),
    "one_group_holds_every_row": (256, 32, 48, [256]),
    "fewer_rows_than_a_tile": (40, 16, 24, [10, 30]),
    "many_small_groups": (1024, 64, 256, [0, 3, 17, 1, 0, 64, 9, 2, 0, 30, 5, 11, 0, 0, 40, 7]),
    "no_row_in_any_group": (256, 32, 48, [0, 0, 0]),
    "a_group_spans_three_tiles": (512, 24, 136, [100, 300, 12]),
}


def _operands(m, k, n, sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    lhs = jnp.asarray(rng.normal(size=(m, k)), dtype)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), dtype)
    return lhs, rhs, jnp.asarray(sizes, jnp.int32)


def _check(m, k, n, sizes, dtype):
    lhs, rhs, group_sizes = _operands(m, k, n, sizes, dtype)
    got = np.asarray(gm.grouped_matmul(lhs, rhs, group_sizes))
    want = np.asarray(jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                         preferred_element_type=jnp.float32))
    assert got.shape == want.shape == (m, n) and got.dtype == np.float32
    held = sum(sizes)
    scale = max(float(np.abs(want[:held]).max(initial=0.0)), 1.0)
    np.testing.assert_allclose(got[:held], want[:held], rtol=1e-5, atol=1e-5 * scale)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_ragged_dot_on_every_row_in_a_group(case, dtype):
    _check(*CASES[case], dtype)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
def test_matrices_cut_into_blocks_of_n_with_a_ragged_last(monkeypatch, dtype):
    """Where two whole matrices overfill the weight budget the rule cuts ``n``
    into blocks of whole 128-lane columns; 300 columns are two of 128 and a
    last of 44, whose copy leaves the rest of its buffer as it was."""
    m, k, n, sizes = 300, 64, 300, [0, 5, 130, 0, 40, 0]
    itemsize = jnp.dtype(dtype).itemsize
    monkeypatch.setattr(gm, "_WEIGHT_VMEM_BYTES", 2 * k * 128 * itemsize)
    blocks = gm.blocks(m, k, n, len(sizes), itemsize, itemsize)
    assert (blocks.tn, blocks.n_blocks) == (128, 3)
    _check(m, k, n, sizes, dtype)


def test_the_walk_visits_each_held_tile_once_and_no_empty_group():
    """Visits in group order; an empty group has none (its matrix is never
    fetched); a tile that holds no group's row has none; each group's first
    visit names the next non-empty group to fetch, and the groups alternate
    buffers."""
    sizes = [0, 5, 130, 0, 40, 0]           # rows 0-4, 5-134, 135-174
    group, tile, slot, fetch, offsets, walked = (
        np.asarray(a) for a in gm.visits(jnp.asarray(sizes), 128, 8))
    assert int(walked) == 4
    assert group[:4].tolist() == [1, 2, 2, 4]
    assert tile[:4].tolist() == [0, 0, 1, 1]
    assert fetch[:4].tolist() == [2, 4, -1, -1]
    assert slot[:4].tolist() == [0, 1, 1, 0]
    assert offsets.tolist() == [0, 0, 5, 135, 135, 175, 175]
    # one group: one visit a tile, nothing to fetch after it
    group, tile, slot, fetch, _, walked = (
        np.asarray(a) for a in gm.visits(jnp.asarray([256]), 128, 2))
    assert int(walked) == 2 and tile.tolist() == [0, 1] and fetch.tolist() == [-1, -1]


#: The grouped products of the cells whose prefill takes the grouped form:
#: (pair rows, k, n, held experts) -> the walk's static bound.
CELLS = {
    "answers.w1": ((11264, 1024, 2688, 128), 215), "answers.w2": ((11264, 2688, 1024, 128), 215),
    "replies.w13": ((4096, 2048, 3584, 32), 63), "replies.w2": ((4096, 1792, 2048, 32), 63),
    "longform.w13": ((12288, 2048, 1536, 16), 111), "longform.w2": ((12288, 768, 2048, 16), 111),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_cells_block_choices(cell):
    """Read from the shape rule, nothing run: every held expert's whole
    bfloat16 matrix is one block (5.5 MB in ``answers``, 14.7 MB the largest,
    ``replies``' ``W1 | W3``), in row tiles of 128, and the VMEM limit covers
    two of them beside two row tiles and the output tiles."""
    (m, k, n, count), bound = CELLS[cell]
    blocks = gm.blocks(m, k, n, count)
    assert (blocks.tm, blocks.tn, blocks.n_blocks, blocks.max_visits) == (128, n, 1, bound)
    weights = 2 * k * n * 2
    assert 2 * 2 ** 20 <= weights / 2 <= 15 * 10 ** 6
    assert weights < blocks.vmem_block_bytes <= blocks.vmem_limit_bytes <= 64 << 20
