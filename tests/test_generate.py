"""Paged-KV generation engine correctness (ISSUE 7 pins).

- paged-vs-contiguous logits equivalence, and both against the full
  flax ``lm.apply`` forward (the decode math has ONE source of truth);
- page reuse after slot exit with zero cross-slot contamination (seeded
  churn against fresh-cache references);
- free-list exhaustion raises the typed PagePoolExhausted;
- the decode loop is recompile-free: ONE jit cache entry per program
  across any join/leave mix, and the page allocator/cache are constructed
  once per engine, never per step;
- the Pallas page-gather kernel (interpret mode off-TPU) matches the XLA
  gather path.
"""

import numpy as np
import pytest

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import batched_admission  # noqa: E402
from dmlc_tpu.generate.engine import Admission, GenerationEngine  # noqa: E402
from dmlc_tpu.generate.kvcache import (  # noqa: E402
    SCRATCH_PAGE,
    PageAllocator,
    PagePoolExhausted,
)
from dmlc_tpu.models.registry import get_model  # noqa: E402

SPEC = get_model("lm_small")
VOCAB = SPEC.num_outputs


@pytest.fixture(scope="module")
def lm():
    module, variables = SPEC.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    return module, variables


def make_engine(variables, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_prefill", 16)
    kw.setdefault("return_logits", True)
    return GenerationEngine("lm_small", variables=variables, **kw)


def greedy_run(engine, slot, prompt, n_steps):
    """Join + n_steps greedy decode; returns (tokens, per-step logits)."""
    toks = [engine.join(slot, prompt)]
    logits = []
    for _ in range(n_steps):
        engine.ensure_capacity(slot)
        out = engine.step()
        toks.append(int(out[slot]))
        logits.append(np.array(engine.last_logits[slot]))
    return toks, logits


# ---------------------------------------------------------------------------
# allocator
# ---------------------------------------------------------------------------


class TestPageAllocator:
    def test_scratch_page_never_allocated(self):
        a = PageAllocator(num_pages=5, page_size=4)
        got = a.alloc(4)
        assert SCRATCH_PAGE not in got
        assert sorted(got) == [1, 2, 3, 4]

    def test_exhaustion_is_typed_and_all_or_nothing(self):
        a = PageAllocator(num_pages=4, page_size=4)
        a.alloc(2)
        with pytest.raises(PagePoolExhausted):
            a.alloc(2)  # only 1 free: must not hand out a partial grant
        assert a.pages_free == 1

    def test_free_recycles_and_guards_double_free(self):
        a = PageAllocator(num_pages=8, page_size=4)
        got = a.alloc(3)
        a.free(got)
        assert a.pages_free == 7
        with pytest.raises(ValueError):
            a.free([got[0]])
        with pytest.raises(ValueError):
            a.free([SCRATCH_PAGE])

    def test_pages_for(self):
        a = PageAllocator(num_pages=8, page_size=4)
        assert [a.pages_for(n) for n in (0, 1, 4, 5, 8)] == [0, 1, 1, 2, 2]


# ---------------------------------------------------------------------------
# paged-KV correctness pin
# ---------------------------------------------------------------------------


class TestPagedParity:
    def test_paged_matches_contiguous_and_full_forward(self, lm):
        module, variables = lm
        paged = make_engine(variables)
        contig = make_engine(variables, cache="contiguous")
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, VOCAB, size=9).astype(np.int32)
        t_p, logits_p = greedy_run(paged, 0, prompt, 5)
        t_c, logits_c = greedy_run(contig, 0, prompt, 5)
        assert t_p == t_c
        seq = list(prompt)
        for i, (lp, lc) in enumerate(zip(logits_p, logits_c)):
            np.testing.assert_allclose(lp, lc, atol=1e-4)
            # ...and both against the full-sequence flax forward.
            seq.append(t_p[i])
            full = module.apply(variables, jnp.asarray(np.array(seq)[None]))
            np.testing.assert_allclose(lp, np.asarray(full[0, -1]), atol=1e-4)

    def test_multi_slot_rows_are_independent(self, lm):
        """A slot's logits do not change when strangers share the batch."""
        _, variables = lm
        eng = make_engine(variables)
        rng = np.random.default_rng(3)
        p0 = rng.integers(0, VOCAB, size=6).astype(np.int32)
        p1 = rng.integers(0, VOCAB, size=11).astype(np.int32)
        eng.join(0, p0)
        eng.join(1, p1)
        shared = []
        for _ in range(4):
            eng.ensure_capacity(0)
            eng.ensure_capacity(1)
            out = eng.step()
            shared.append((int(out[0]), int(out[1])))
        solo = make_engine(variables)
        t0, _ = greedy_run(solo, 0, p0, 4)
        solo2 = make_engine(variables)
        t1, _ = greedy_run(solo2, 0, p1, 4)
        assert [a for a, _ in shared] == t0[1:]
        assert [b for _, b in shared] == t1[1:]

    def test_page_reuse_after_exit_no_contamination(self, lm):
        """Seeded churn: a new slot riding RECYCLED pages produces exactly
        the tokens a fresh cache produces."""
        _, variables = lm
        eng = make_engine(variables, num_pages=8)  # 7 usable pages
        rng = np.random.default_rng(11)
        pa = rng.integers(0, VOCAB, size=15).astype(np.int32)
        greedy_run(eng, 0, pa, 6)  # fills slot 0 with history
        used = eng.cache.slot_pages(0)
        assert used, "slot 0 should hold pages"
        freed = eng.release(0)
        assert sorted(freed) == sorted(used)
        pb = rng.integers(0, VOCAB, size=14).astype(np.int32)
        t_recycled, logits_recycled = greedy_run(eng, 0, pb, 6)
        # LIFO free list: the new slot really rides A's recycled pages.
        assert set(eng.cache.slot_pages(0)) & set(freed)
        fresh = make_engine(variables, num_pages=8)
        t_fresh, logits_fresh = greedy_run(fresh, 0, pb, 6)
        assert t_recycled == t_fresh
        for lr, lf in zip(logits_recycled, logits_fresh):
            np.testing.assert_allclose(lr, lf, atol=1e-5)

    def test_reserve_exhaustion_typed(self, lm):
        _, variables = lm
        eng = make_engine(variables, num_pages=4, max_prefill=16)  # 3 usable
        eng.reserve(15)  # 2 pages (8-token pages): 15+1 = 16 tokens
        with pytest.raises(PagePoolExhausted):
            eng.reserve(15)


# ---------------------------------------------------------------------------
# recompile-free decode (the J2/H1 runtime pin)
# ---------------------------------------------------------------------------


class TestRecompileFree:
    def test_one_jit_entry_across_join_leave_mix(self, lm):
        _, variables = lm
        eng = make_engine(variables)
        cache_obj = eng.cache
        allocator_obj = eng.cache.allocator
        rng = np.random.default_rng(5)
        for round_ in range(3):
            for slot in range(2):
                prompt = rng.integers(0, VOCAB, size=3 + round_ + slot)
                eng.join(slot, prompt.astype(np.int32))
            for _ in range(3):
                for slot in range(2):
                    eng.ensure_capacity(slot)
                eng.step()
            for slot in range(2):
                eng.release(slot)
        sizes = eng.jit_cache_sizes()
        assert sizes == {"step": 1, "prefill": 1}, sizes
        # The allocator/cache are engine-lifetime singletons: steps and
        # churn must never rebuild them (H1's regression class).
        assert eng.cache is cache_obj
        assert eng.cache.allocator is allocator_obj


# ---------------------------------------------------------------------------
# one run of the prefill program for every request of a loop turn
# ---------------------------------------------------------------------------


class TestBatchedAdmission:
    @pytest.mark.parametrize("k,temperature", batched_admission.CASES)
    @pytest.mark.parametrize("cache", ["paged", "contiguous"])
    def test_one_admission_of_k_is_k_serial_joins(self, lm, cache, k, temperature):
        batched_admission.assert_batch_matches_serial(
            lambda: make_engine(lm[1], cache=cache), VOCAB, k, temperature)

    def test_one_prefill_program_after_runs_of_every_size(self, lm):
        eng = make_engine(lm[1])
        eng.warmup()
        rng = np.random.default_rng(9)
        for n in (1, 3, 2, 4, 1):
            firsts = eng.admit([Admission(slot, rng.integers(0, VOCAB, size=2 + slot + n))
                                for slot in range(n)])
            assert all(isinstance(t, int) and 0 <= t < VOCAB for t in firsts)
            for slot in range(n):
                eng.ensure_capacity(slot)
            eng.step()
            for slot in range(n):
                eng.release(slot)
        assert eng.jit_cache_sizes() == {"step": 1, "prefill": 1}
        assert eng.pages_free == eng.cache.allocator.pages_total

    def test_a_refused_request_touches_nothing_and_the_others_run(self, lm):
        eng, alone = make_engine(lm[1]), make_engine(lm[1])
        good = [np.arange(5, dtype=np.int32), np.arange(3, 12, dtype=np.int32)]
        eng.join(3, good[0])
        reserved = eng.reserve(4)
        free = eng.pages_free
        got = eng.admit([
            Admission(0, good[0]),
            Admission(1, np.zeros(eng.max_prefill + 1, np.int32)),    # too long
            Admission(3, good[1]),                                    # a resident's slot
            Admission(2, [], pages=reserved),                         # empty, with a reservation
            Admission(0, good[1]),                                    # a slot this call took
            Admission(2, good[1]),
        ])
        assert [type(r) for r in got] == [int, ValueError, ValueError, ValueError, ValueError, int]
        assert "max_prefill" in str(got[1]) and "already active" in str(got[2])
        assert got[0] == alone.join(0, good[0]) and got[5] == alone.join(2, good[1])
        assert list(eng.active) == [True, False, True, True]
        # The refused ones bound no page; the reservation is still its caller's.
        assert not eng.cache.slot_pages(1)
        assert eng.pages_free == free - 1 - 2        # 5 + 1 tokens, 9 + 1 tokens
        eng.release_reservation(reserved)
        with pytest.raises(ValueError, match="non-empty"):
            eng.join(1, [])
        assert eng.admit([]) == [] and eng.prefill_attrs == {}


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------


class TestSampling:
    def test_greedy_is_deterministic(self, lm):
        _, variables = lm
        a = make_engine(variables)
        b = make_engine(variables)
        prompt = np.arange(5, dtype=np.int32)
        ta, _ = greedy_run(a, 0, prompt, 5)
        tb, _ = greedy_run(b, 0, prompt, 5)
        assert ta == tb

    def test_temperature_sampling_seeded_and_in_vocab(self, lm):
        _, variables = lm
        a = make_engine(variables, seed=123)
        b = make_engine(variables, seed=123)
        c = make_engine(variables, seed=321)
        prompt = np.arange(4, dtype=np.int32)
        runs = []
        for eng in (a, b, c):
            toks = [eng.join(0, prompt, temperature=1.5)]
            for _ in range(8):
                eng.ensure_capacity(0)
                toks.append(int(eng.step()[0]))
            assert all(0 <= t < VOCAB for t in toks)
            runs.append(toks)
        assert runs[0] == runs[1]  # same seed, same stream
        assert runs[0] != runs[2]  # different seed diverges


# ---------------------------------------------------------------------------
# the gathered view and the masked attention (the fused kernel's reference;
# the kernel itself is pinned against them in tests/test_kv_pool.py)
# ---------------------------------------------------------------------------


class TestGatheredViewAttention:
    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_gathers_agree_on_the_folded_pool(self, layer, dtype):
        """The gather reads layer ``layer`` of a pool that holds three, by a
        table that repeats pages and points at scratch; KV heads unfold from
        the row (2 x 24 = 48 wide: no multiple of a tile)."""
        from dmlc_tpu.ops.ragged_decode import gather_kv_pages

        num_pages, page_size, kv_heads, head_dim = 6, 4, 2, 24
        rng = np.random.default_rng(1)
        pool = jnp.asarray(rng.standard_normal(
            (3 * num_pages, page_size, kv_heads * head_dim)).astype(np.float32)).astype(dtype)
        table = jnp.asarray([[5, 5, SCRATCH_PAGE, 1], [SCRATCH_PAGE] * 4, [3, 1, 5, 3]], jnp.int32)
        want = np.asarray(pool, np.float32).reshape(3, num_pages, page_size, kv_heads, head_dim)[
            layer][np.asarray(table)].reshape(3, 4 * page_size, kv_heads, head_dim)
        got = gather_kv_pages(pool, table, kv_heads, first_row=layer * num_pages)
        assert got.dtype == dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), want)

    def test_ragged_mask_excludes_beyond_length(self):
        from dmlc_tpu.ops.ragged_decode import ragged_decode_attention

        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.standard_normal((2, 2, 8)).astype(np.float32))
        k = jnp.asarray(rng.standard_normal((2, 6, 2, 8)).astype(np.float32))
        v = jnp.asarray(rng.standard_normal((2, 6, 2, 8)).astype(np.float32))
        out_short = ragged_decode_attention(q, k, v, jnp.asarray([3, 6]))
        # Rewriting positions >= row 0's length must not change row 0;
        # row 1 (full length) legitimately sees them and must change.
        k2 = k.at[:, 3:].set(99.0)
        v2 = v.at[:, 3:].set(-99.0)
        out_poisoned = ragged_decode_attention(q, k2, v2, jnp.asarray([3, 6]))
        np.testing.assert_allclose(
            np.asarray(out_short[0]), np.asarray(out_poisoned[0]), atol=1e-6
        )
        # The full-length row DOES see those positions.
        assert not np.allclose(np.asarray(out_short[1]), np.asarray(out_poisoned[1]))


class TestRegistryEntry:
    def test_lm_small_registered_and_buildable(self):
        assert SPEC.kind == "lm"
        module, variables = SPEC.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
        logits = module.apply(variables, jnp.zeros((1, 4), jnp.int32))
        assert logits.shape == (1, 4, VOCAB)

    def test_weights_roundtrip_through_blob_path(self):
        from dmlc_tpu.models import weights as weights_lib

        _, variables = SPEC.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
        blob = weights_lib.weights_to_bytes("lm_small", variables)
        name, restored = weights_lib.weights_from_bytes(blob, expect_model="lm_small")
        assert name == "lm_small"
        leaves_a = jax.tree_util.tree_leaves(variables)
        leaves_b = jax.tree_util.tree_leaves(restored)
        assert all(np.allclose(a, b) for a, b in zip(leaves_a, leaves_b))
