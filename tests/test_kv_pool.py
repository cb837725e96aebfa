"""The KV pools as both programs share them (generate/kvcache.py layout):
``[kv_layers * num_pages, page_size, kv_heads * head_dim]``, written in place
by the step and the prefill and read where they live by the attention.

- a prefill followed by steps gives the contiguous cache's logits at any
  (heads, KV heads, head width), grouped-query and rows that fill no tile
  included, through the gathered view and through the fused kernel;
- the fused kernel (``ops/ragged_decode.paged_decode_attention``) gives what
  the gathered view and the masked attention give, reads no page a slot does
  not hold and nothing past a slot's length;
- a prefill touches the slot's page run and the scratch page, nothing else;
- the names the benchmark reads (``engine.cache.k_pages``, ``engine._k_state``)
  answer, and deleting them frees every pool buffer;
- a step and a prefill consume the pool they are handed (one generation);
- a family that caches ONE latent row a position (``models/deepseek_v3``)
  gets one pool and no second one, and its form of the fused kernel
  (``paged_latent_decode_attention``) gives what the gathered rows and the
  dense latent attention give, under the same poisons.

Counts and values only; nothing here is a speed.
"""

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from dmlc_tpu.generate.engine import GenerationEngine  # noqa: E402
from dmlc_tpu.generate.kvcache import SCRATCH_PAGE  # noqa: E402
from dmlc_tpu.models import registry  # noqa: E402
from dmlc_tpu.ops import ragged_decode  # noqa: E402

VOCAB, MAX_LEN, LAYERS, PAGE = 40, 32, 2, 4


class GroupedQueryFamily:
    """A two-layer attention-only decoder with ``heads`` query heads on
    ``kv_heads`` K/V heads: the least family that exercises the engine's
    cache seam (``kv.write_prefill`` / ``kv.write_attend``) at any widths."""

    def __init__(self, dtype, heads: int, kv_heads: int, head_dim: int) -> None:
        self.dtype = dtype
        self.vocab, self.max_len = VOCAB, MAX_LEN
        self.heads, self.kv_layers = heads, LAYERS
        self.kv_heads, self.head_dim = kv_heads, head_dim
        self.width = heads * head_dim

    def params(self, seed: int) -> dict:
        rng = np.random.default_rng(seed)
        kv_width = self.kv_heads * self.head_dim

        def draw(*shape):
            return jnp.asarray(rng.standard_normal(shape) / np.sqrt(shape[0]), self.dtype)

        layers = [{"wq": draw(self.width, self.width), "wk": draw(self.width, kv_width),
                   "wv": draw(self.width, kv_width), "wo": draw(self.width, self.width)}
                  for _ in range(LAYERS)]
        return {"embed": draw(VOCAB, self.width) * 6.0, "pos": draw(MAX_LEN, self.width) * 6.0,
                "head": draw(self.width, VOCAB), "layers": layers}

    def state_shapes(self, max_slots: int) -> dict:
        return {}

    def work_attrs(self, aux: dict, rows: int) -> dict:
        return {}

    def _qkv(self, p, x):
        rows = x.shape[0]
        return ((x @ p["wq"]).reshape(rows, self.heads, self.head_dim),
                (x @ p["wk"]).reshape(rows, self.kv_heads, self.head_dim),
                (x @ p["wv"]).reshape(rows, self.kv_heads, self.head_dim))

    def prefill(self, params, tokens, length, slot, kv, state):
        del slot
        s_pad = tokens.shape[1]
        x = params["embed"][tokens[0]] + params["pos"][:s_pad]
        causal = jnp.tril(jnp.ones((s_pad, s_pad), bool))
        group = self.heads // self.kv_heads
        for layer, p in enumerate(params["layers"]):
            q, k, v = self._qkv(p, x)
            kv.write_prefill(layer, k, v)
            scores = jnp.einsum("shd,thd->hst", q, jnp.repeat(k, group, axis=1))
            scores = jnp.where(causal[None], scores * self.head_dim ** -0.5, -jnp.inf)
            att = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, -1),
                             jnp.repeat(v, group, axis=1))
            x = x + att.reshape(s_pad, -1) @ p["wo"]
        logits = (x @ params["head"]).astype(jnp.float32)
        return jnp.take(logits, length - 1, axis=0), state, {}

    def decode(self, params, tokens, lengths, active, kv, state):
        del active
        x = params["embed"][tokens] + params["pos"][jnp.minimum(lengths, MAX_LEN - 1)]
        for layer, p in enumerate(params["layers"]):
            q, k, v = self._qkv(p, x)
            att = kv.write_attend(layer, q, k, v)
            x = x + att.reshape(att.shape[0], -1) @ p["wo"]
        return (x @ params["head"]).astype(jnp.float32), state, {}


@pytest.fixture
def family_engine():
    """``make(heads, kv_heads, head_dim, **engine_kw)`` -> an engine over a
    ``GroupedQueryFamily`` registered for this test only."""
    names: list[str] = []

    def make(heads, kv_heads, head_dim, **kw):
        name = f"gqa_{heads}_{kv_heads}_{head_dim}"
        if name not in names:
            registry.register(registry.ModelSpec(
                name, None, MAX_LEN, VOCAB, classifier=False, kind="lm",
                family=lambda dtype: GroupedQueryFamily(dtype, heads, kv_heads, head_dim)))
            names.append(name)
        dtype = kw.get("dtype", jnp.float32)
        params = GroupedQueryFamily(dtype, heads, kv_heads, head_dim).params(seed=5)
        kw = {"max_slots": 3, "page_size": PAGE, "num_pages": 24, "max_prefill": 10,
              "return_logits": True, **kw}
        return GenerationEngine(name, variables={"params": params}, **kw)

    yield make
    for name in names:
        registry._REGISTRY.pop(name, None)


def run(engine, prompts, n_steps):
    """Join every prompt, then ``n_steps`` greedy steps: the logits of all."""
    for slot, prompt in enumerate(prompts):
        engine.join(slot, prompt)
    out = []
    for _ in range(n_steps):
        for slot in range(len(prompts)):
            engine.ensure_capacity(slot)
        engine.step()
        out.append(np.array(engine.last_logits[: len(prompts)]))
    return np.stack(out)


_GEOMETRIES = [
    (4, 4, 8),     # multi-head, a 32-wide row
    (4, 2, 16),    # grouped-query
    (6, 2, 24),    # grouped-query, a 48-wide row
    (2, 1, 64),    # one KV head
    (5, 5, 40),    # 200 wide: over one tile, no multiple of 128
]
GEOMETRIES = pytest.mark.parametrize("heads,kv_heads,head_dim", _GEOMETRIES)
#: The kernel alone (no engine, so no weights of that width) also at two served
#: models' rows: 30 heads x 128 = 3,840 lanes, 30 query rows padded to 32; and
#: 32 query heads on 8 KV heads of 64, rows of 512 lanes, four query rows a head.
KERNEL_GEOMETRIES = pytest.mark.parametrize(
    "heads,kv_heads,head_dim", _GEOMETRIES + [(30, 30, 128), (32, 8, 64)])


@pytest.mark.parametrize("use_pallas", [False, True], ids=["take", "pallas"])
@GEOMETRIES
def test_paged_pool_gives_the_contiguous_caches_logits(
        family_engine, heads, kv_heads, head_dim, use_pallas):
    rng = np.random.default_rng(heads * 100 + head_dim)
    # 7 and 10 tokens: the second prompt fills its padded prefill, both cross
    # a page boundary within six steps.
    prompts = [rng.integers(0, VOCAB, n).astype(np.int32) for n in (7, 10)]
    paged = family_engine(heads, kv_heads, head_dim, use_pallas=use_pallas)
    contiguous = family_engine(heads, kv_heads, head_dim, cache="contiguous")
    assert paged.cache.k_pages.shape == (LAYERS * 24, PAGE, kv_heads * head_dim)
    got, want = run(paged, prompts, 6), run(contiguous, prompts, 6)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)
    assert np.abs(want).max() > 0.1  # the logits say something


@pytest.mark.parametrize("length", [1, PAGE, PAGE + 3, 10])
def test_prefill_writes_the_slots_run_and_scratch_only(family_engine, length):
    engine = family_engine(4, 2, 16)
    num_pages = engine.cache.allocator.num_pages
    sentinel = 7.0
    stranger = engine.reserve(5)  # pages another request holds, LIFO-adjacent
    for name in ("_k_state", "_v_state"):
        setattr(engine, name, jnp.full_like(getattr(engine, name), sentinel))
    engine.join(1, np.arange(length, dtype=np.int32) % VOCAB)
    run_pages = engine.cache.slot_pages(1)
    assert len(run_pages) == -(-(length + 1) // PAGE) and not set(run_pages) & set(stranger)
    written = -(-length // PAGE)  # pages that hold a real position
    for pool in (engine._k_state, engine._v_state):
        pool = np.asarray(pool).reshape(LAYERS, num_pages, PAGE, -1)
        untouched = [p for p in range(num_pages)
                     if p != SCRATCH_PAGE and p not in run_pages[:written]]
        assert (pool[:, untouched] == sentinel).all()
        rows = pool[:, run_pages[:written]].reshape(LAYERS, written * PAGE, -1)
        assert (rows[:, :length] != sentinel).all()


def test_pool_names_answer_and_deleting_them_frees_every_buffer(family_engine):
    engine = family_engine(4, 2, 16, dtype=jnp.bfloat16)
    engine.join(0, np.arange(5, dtype=np.int32))
    engine.step()
    assert engine.cache.k_pages.dtype == jnp.bfloat16 == engine.cache.v_pages.dtype
    assert engine.cache.k_pages.shape == engine.cache.v_pages.shape == (LAYERS * 24, PAGE, 32)
    def live_pools():  # this test's engine is the only bfloat16 one
        return [a for a in jax.live_arrays()
                if a.shape == (LAYERS * 24, PAGE, 32) and a.dtype == jnp.bfloat16]

    assert len(live_pools()) == 2  # one generation of K and of V, no stray copy
    for name in ("_k_state", "_v_state"):  # as benchlib.system.free_pools does
        pool = getattr(engine, name)
        assert not pool.is_deleted()
        pool.delete()
    assert engine.cache.k_pages.is_deleted() and engine.cache.v_pages.is_deleted()
    assert not live_pools()


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_each_program_consumes_the_pool_it_is_handed(family_engine, program):
    probe = jnp.zeros((8,))
    jax.jit(lambda x: x + 1, donate_argnums=0)(probe)
    if not probe.is_deleted():
        pytest.skip("this backend does not honour donation")
    engine = family_engine(4, 2, 16)
    engine.join(0, np.arange(6, dtype=np.int32))
    handed = (engine._k_state, engine._v_state)
    if program == "step":
        engine.step()
    else:
        engine.join(1, np.arange(3, dtype=np.int32))
    assert all(pool.is_deleted() for pool in handed)
    assert not engine._k_state.is_deleted() and not engine._v_state.is_deleted()
    assert engine.cache.k_pages is engine._k_state and engine.cache.v_pages is engine._v_state


# ---------------------------------------------------------------------------
# the fused kernel against the gathered view + the masked attention
# ---------------------------------------------------------------------------

FUSED_PAGES = 6  # a slot's table: 24 positions


def pool_case(heads, kv_heads, head_dim, lengths, *, dtype=jnp.float32, seed=0):
    """(q, k_pool, v_pool, table, lengths, num_pages): two layers of pages, a
    slot's pages scattered over its layer, unused table entries on scratch."""
    rng = np.random.default_rng(seed)
    slots, width = len(lengths), kv_heads * head_dim
    num_pages = slots * FUSED_PAGES + 1
    k_pool, v_pool = (jnp.asarray(rng.standard_normal(
        (LAYERS * num_pages, PAGE, width)), jnp.float32).astype(dtype) for _ in range(2))
    free = iter(rng.permutation(np.arange(1, num_pages)))
    table = np.full((slots, FUSED_PAGES), SCRATCH_PAGE, np.int32)
    for slot, length in enumerate(lengths):
        held = -(-length // PAGE)
        table[slot, :held] = [next(free) for _ in range(held)]
    q = jnp.asarray(rng.standard_normal((slots, heads, head_dim)), jnp.float32).astype(dtype)
    return q, k_pool, v_pool, jnp.asarray(table), jnp.asarray(lengths, jnp.int32), num_pages


def fused(q, k_pool, v_pool, table, lengths, first_row, kv_heads):
    return np.asarray(ragged_decode.paged_decode_attention(
        q, k_pool, v_pool, table, lengths, first_row=first_row, kv_heads=kv_heads), np.float32)


def gathered(q, k_pool, v_pool, table, lengths, first_row, kv_heads):
    ks, vs = (ragged_decode.gather_kv_pages(pool, table, kv_heads, first_row=first_row)
              for pool in (k_pool, v_pool))
    return np.asarray(ragged_decode.ragged_decode_attention(q, ks, vs, lengths), np.float32)


@pytest.fixture(params=[256, 2 * PAGE], ids=["one_chunk", "chunks_of_two_pages"])
def chunked(request, monkeypatch):
    monkeypatch.setattr(ragged_decode, "_CHUNK_TOKENS", request.param)


@KERNEL_GEOMETRIES
def test_fused_attention_is_the_gathered_views(chunked, heads, kv_heads, head_dim):
    # 1, exactly a page, a page + 3, a slot at its full table; the second layer.
    *case, num_pages = pool_case(heads, kv_heads, head_dim,
                                 [1, PAGE, PAGE + 3, FUSED_PAGES * PAGE])
    got = fused(*case, num_pages, kv_heads)
    want = gathered(*case, num_pages, kv_heads)
    assert got.shape == (4, heads, head_dim)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
    assert np.abs(want).max() > 0.1


@pytest.mark.parametrize("heads,kv_heads,head_dim", [(4, 4, 8), (4, 2, 16)],
                         ids=["multi_head", "grouped_query"])
def test_fused_attention_on_bfloat16_pools_keeps_float32_sums(chunked, heads, kv_heads, head_dim):
    """bfloat16 K, V and q as the cells store them: the kernel's scores,
    softmax and weighted sum are float32, so what differs from the float32
    einsums of the reference is the order of the sums (and the result's own
    rounding to bfloat16: half a unit in its last place)."""
    *case, _ = pool_case(heads, kv_heads, head_dim, [3, 2 * PAGE + 1, FUSED_PAGES * PAGE],
                         dtype=jnp.bfloat16)
    got, want = fused(*case, 0, kv_heads), gathered(*case, 0, kv_heads)
    np.testing.assert_allclose(got, want, atol=1e-6, rtol=2 ** -8)


@KERNEL_GEOMETRIES
def test_fused_attention_reads_nothing_a_slot_does_not_hold(chunked, heads, kv_heads, head_dim):
    """Every page no slot holds (scratch among them) and the tail of every
    slot's last page filled with NaN: the output is finite and unchanged."""
    q, k_pool, v_pool, table, lengths, num_pages = pool_case(
        heads, kv_heads, head_dim, [1, PAGE + 3, 3 * PAGE])
    clean = fused(q, k_pool, v_pool, table, lengths, 0, kv_heads)
    poison = np.ones((LAYERS * num_pages, PAGE), bool)
    for slot, length in enumerate(np.asarray(lengths)):
        for j in range(-(-length // PAGE)):
            poison[int(table[slot, j]), :min(PAGE, length - j * PAGE)] = False
    poison = jnp.asarray(poison)[:, :, None]
    poisoned = fused(q, jnp.where(poison, jnp.nan, k_pool), jnp.where(poison, jnp.nan, v_pool),
                     table, lengths, 0, kv_heads)
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, clean)


@pytest.mark.parametrize("heads,kv_heads,head_dim", [(4, 4, 8), (6, 2, 24)],
                         ids=["multi_head", "grouped_query"])
def test_fused_attention_with_inactive_slots_beside_full_ones(chunked, heads, kv_heads, head_dim):
    """An inactive slot is a row of scratch-page entries and length 1 (the
    engine's ``kv_lengths``): it attends the scratch page's first position,
    so its row is that position's V, and its neighbours are not disturbed."""
    full = FUSED_PAGES * PAGE
    q, k_pool, v_pool, table, lengths, num_pages = pool_case(
        heads, kv_heads, head_dim, [full, 1, full, 1])
    table = table.at[jnp.asarray([1, 3])].set(SCRATCH_PAGE)
    got = fused(q, k_pool, v_pool, table, lengths, num_pages, kv_heads)
    np.testing.assert_allclose(
        got, gathered(q, k_pool, v_pool, table, lengths, num_pages, kv_heads),
        atol=2e-6, rtol=1e-5)
    scratch_v = np.asarray(v_pool[num_pages + SCRATCH_PAGE, 0]).reshape(kv_heads, head_dim)
    np.testing.assert_allclose(got[1], np.repeat(scratch_v, heads // kv_heads, axis=0), atol=1e-6)


# ---------------------------------------------------------------------------
# the latent layout: one pool, and the kernel's latent form
# ---------------------------------------------------------------------------


def latent_engine(**kw):
    kw = {"max_slots": 3, "page_size": PAGE, "num_pages": 24, "max_prefill": 16, **kw}
    return GenerationEngine("deepseek_v3_tiny", **kw)


def test_a_latent_family_gets_one_pool_and_no_second_one():
    engine = latent_engine(dtype=jnp.bfloat16)
    engine.join(0, np.arange(5, dtype=np.int32))
    engine.step()
    assert engine.cache.v_pages is None and engine._v_state is None
    assert engine.cache.k_pages.dtype == jnp.bfloat16
    assert engine.cache.k_pages.shape == (4 * 24, PAGE, 128)     # four layers, 40 values on 128 lanes

    def live_pools():  # this test's engine is the only bfloat16 one of this shape
        return [a for a in jax.live_arrays()
                if a.shape == (4 * 24, PAGE, 128) and a.dtype == jnp.bfloat16]

    assert len(live_pools()) == 1  # one generation of ONE pool
    for name in ("_k_state", "_v_state"):  # as benchlib.system.free_pools does
        pool = getattr(engine, name, None)
        if pool is not None:
            pool.delete()
    assert engine.cache.k_pages.is_deleted() and not live_pools()


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_each_program_consumes_the_one_latent_pool(program):
    probe = jnp.zeros((8,))
    jax.jit(lambda x: x + 1, donate_argnums=0)(probe)
    if not probe.is_deleted():
        pytest.skip("this backend does not honour donation")
    engine = latent_engine()
    engine.join(0, np.arange(6, dtype=np.int32))
    handed = engine._k_state
    if program == "step":
        engine.step()
    else:
        engine.join(1, np.arange(3, dtype=np.int32))
    assert handed.is_deleted() and not engine._k_state.is_deleted()
    assert engine.cache.k_pages is engine._k_state and engine._v_state is None
    # The stock of replaced arrays is bounded at one array a run, not two.
    assert len(engine._replaced) <= 2 and engine._replaced_max == 8


@pytest.mark.parametrize("cache", ["paged", "contiguous"])
def test_a_latent_prefill_writes_whole_rows_and_pads_with_zeros(cache):
    engine = latent_engine(cache=cache)
    engine.join(1, np.arange(PAGE + 3, dtype=np.int32))
    if cache == "paged":
        first, second = engine.cache.slot_pages(1)[:2]
        rows = np.concatenate([np.asarray(engine._k_state[first]), np.asarray(engine._k_state[second])])
        untouched = np.ones(24, bool)
        untouched[[SCRATCH_PAGE, *engine.cache.slot_pages(1)]] = False
        assert not np.asarray(engine._k_state[:24])[untouched].any()
    else:
        rows = np.asarray(engine._k_state[0, 1, :2 * PAGE])
    assert np.abs(rows[:PAGE + 3, :40]).min() > 0 and not rows[:, 40:].any()


LATENT = pytest.mark.parametrize("heads,width,value_lanes", [(4, 128, 32), (32, 128, 64), (6, 256, 128)],
                                 ids=["padded_rows", "two_tiles_of_rows", "ragged_rows"])


def latent_case(heads, width, value_lanes, lengths, *, dtype=jnp.float32, seed=0):
    q, pool, _, table, lengths, num_pages = pool_case(heads, 1, width, lengths, dtype=dtype, seed=seed)
    return q, pool, table, lengths, num_pages, {"value_lanes": value_lanes, "scale": 0.21}


def fused_latent(q, pool, table, lengths, first_row, how):
    return np.asarray(ragged_decode.paged_latent_decode_attention(
        q, pool, table, lengths, first_row=first_row, **how), np.float32)


def gathered_latent(q, pool, table, lengths, first_row, how):
    rows = ragged_decode.gather_latent_pages(pool, table, first_row=first_row)
    return np.asarray(ragged_decode.latent_decode_attention(q, rows, lengths, **how), np.float32)


@LATENT
def test_latent_kernel_is_the_gathered_rows(chunked, heads, width, value_lanes):
    lengths = [1, PAGE, PAGE + 3, FUSED_PAGES * PAGE, 10]
    q, pool, table, lengths, num_pages, how = latent_case(heads, width, value_lanes, lengths)
    for first_row in (0, num_pages):
        got = fused_latent(q, pool, table, lengths, first_row, how)
        assert got.shape == (5, heads, value_lanes)
        np.testing.assert_allclose(got, gathered_latent(q, pool, table, lengths, first_row, how),
                                   atol=2e-6, rtol=1e-5)
    assert np.abs(got).max() > 0.1


def test_latent_kernel_on_a_bfloat16_pool_keeps_float32_sums(chunked):
    lengths = [FUSED_PAGES * PAGE, 7, PAGE]
    q, pool, table, lengths, num_pages, how = latent_case(4, 128, 32, lengths, dtype=jnp.bfloat16)
    q = q.astype(jnp.float32) + 1e-3          # queries the bfloat16 grid does not hold
    got = fused_latent(q, pool, table, lengths, num_pages, how)
    want = gathered_latent(q, pool, table, lengths, num_pages, how)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=1e-5)
    rounded = gathered_latent(q.astype(jnp.bfloat16).astype(jnp.float32), pool, table, lengths,
                              num_pages, how)
    assert np.abs(rounded - want).max() > 20 * np.abs(got - want).max()
    # Queries STORED in bfloat16 (what a bfloat16 engine hands the kernel) are one
    # term: the same float32 sums over them, only the result is rounded to bfloat16.
    low = q.astype(jnp.bfloat16)
    got = fused_latent(low, pool, table, lengths, num_pages, how)
    want = gathered_latent(low.astype(jnp.float32), pool, table, lengths, num_pages, how)
    assert np.abs(got - want).max() <= 2.0 ** -8 * np.abs(want).max()


@LATENT
def test_latent_kernel_reads_nothing_a_slot_does_not_hold(chunked, heads, width, value_lanes):
    lengths = [1, PAGE + 3, FUSED_PAGES * PAGE, 10]
    q, pool, table, lengths, num_pages, how = latent_case(heads, width, value_lanes, lengths)
    clean = fused_latent(q, pool, table, lengths, 0, how)
    poison = np.ones((LAYERS * num_pages, PAGE), bool)
    for slot, length in enumerate(np.asarray(lengths)):
        for j in range(-(-length // PAGE)):
            poison[int(table[slot, j]), :min(PAGE, length - j * PAGE)] = False
    poisoned = fused_latent(q, jnp.where(jnp.asarray(poison)[:, :, None], jnp.nan, pool), table,
                            lengths, 0, how)
    assert np.isfinite(poisoned).all()
    np.testing.assert_array_equal(poisoned, clean)


def test_latent_kernel_with_inactive_slots_beside_full_ones(chunked):
    full = FUSED_PAGES * PAGE
    q, pool, table, lengths, num_pages, how = latent_case(4, 128, 32, [full, 1, full, 1])
    table = table.at[jnp.asarray([1, 3])].set(SCRATCH_PAGE)
    got = fused_latent(q, pool, table, lengths, num_pages, how)
    np.testing.assert_allclose(got, gathered_latent(q, pool, table, lengths, num_pages, how),
                               atol=2e-6, rtol=1e-5)
    # One position attended: the result is that row's value lanes, for every head.
    scratch = np.asarray(pool[num_pages + SCRATCH_PAGE, 0, :32])
    np.testing.assert_allclose(got[1], np.broadcast_to(scratch, (4, 32)), atol=1e-6)
