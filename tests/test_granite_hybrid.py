"""The Granite-4.0-H family (Mamba-2 and grouped-query attention, two
branches a layer under muP multipliers, a tied head) through the generation
engine, against the benchmark's plain reference.

The reference is ONE file, ``benchmark/reference/granite_hybrid.py``
(float32, ``highest`` precision, the Mamba-2 recurrence as a sequential scan,
dense masks, the multipliers written out), loaded here by path: the same copy
of the plain math decides ``correct`` on the chip. Everything runs
``granite_hybrid_tiny`` (``M A M M A M``, one Mamba group, four query heads
on two KV heads of 16) in float32 with seeded weights.
"""

import json

import numpy as np
import plain_reference
import pytest
from plain_reference import flat_of

pytest.importorskip("jax")
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import batched_admission  # noqa: E402
from dmlc_tpu.generate.engine import GenerationEngine  # noqa: E402
from dmlc_tpu.models import granite_hybrid as gh  # noqa: E402
from dmlc_tpu.models import mamba2  # noqa: E402
from dmlc_tpu.models.blocks import gqa_causal_attention  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402

MODEL = "granite_hybrid_tiny"
CFG = gh.GRANITE_HYBRID_TINY
VOCAB = CFG.vocab_size
REPO = plain_reference.REPO
CONFIG_FILE = REPO / "benchmark" / "configs" / "granite-4-h-micro.json"

#: Engine (prefill + decode through pages and state slots) against the
#: reference's one full forward, float32 on the CPU. What separates them is
#: summation order: the chunked scan and the one-step recurrence against the
#: sequential scan, paged against dense attention, q turned by 2^-2 before a
#: 1/4 against the scores times 1/16. Measured here: 4.8e-7 on logits whose
#: largest is 5.6 (spread 1.2). The same run with bfloat16 weights and
#: activations reads 3.4e-2: 1,700 times this tolerance (the tests below ask
#: for twenty).
LOGIT_ATOL = 2e-5

REF = plain_reference.load("granite_hybrid")


def ref_cfg(cfg=CFG) -> dict:
    """The reference reads a configuration FILE's keys: build that shape."""
    out = {k: getattr(cfg, k) for k in cfg.__dataclass_fields__ if k != "max_len"}
    out["layer_types"] = list(cfg.layer_types)
    return out


@pytest.fixture(scope="module")
def variables():
    _, v = get_model(MODEL).init_params(jax.random.PRNGKey(3), dtype=jnp.float32)
    return v


def make_engine(variables, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 64)
    kw.setdefault("max_prefill", 32)
    kw.setdefault("return_logits", True)
    return GenerationEngine(MODEL, variables=variables, **kw)


def greedy_run(engine, slot, prompt, n_steps):
    toks = [engine.join(slot, prompt)]
    logits = []
    for _ in range(n_steps):
        engine.ensure_capacity(slot)
        out = engine.step()
        toks.append(int(out[slot]))
        logits.append(np.array(engine.last_logits[slot]))
    return toks, logits


def reference_logits(variables, seq, positions, mode=None):
    tokens = jnp.asarray(np.asarray(seq, np.int32)[None])
    pos = jnp.asarray(np.asarray(positions, np.int32)[None])
    return np.asarray(REF.logits_at(ref_cfg(), flat_of(variables), tokens, pos, mode)[0])


def prompt_of(n, seed=7):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).astype(np.int32)


def slot_state(engine, slot):
    return {name: np.asarray(a[slot]) for name, a in engine._r_state.items()}


# ---------------------------------------------------------------------------
# the engine against the reference's full forward
# ---------------------------------------------------------------------------


class TestAgainstReference:
    @pytest.mark.parametrize("cache", ["paged", "contiguous"])
    def test_logits_at_every_served_position(self, variables, cache):
        prompt = prompt_of(11)
        engine = make_engine(variables, cache=cache)
        toks, logits = greedy_run(engine, 1, prompt, 6)
        seq = list(prompt) + toks
        # Step i consumed token i of the served ones and predicts the next.
        want = reference_logits(variables, seq, [len(prompt) + i for i in range(6)])
        for i, got in enumerate(logits):
            np.testing.assert_allclose(got, want[i], atol=LOGIT_ATOL)
        assert np.abs(want).max() > 0.5  # the logits say something
        # The prefill's own logits picked the first served token.
        first = reference_logits(variables, seq, [len(prompt) - 1])[0]
        assert int(np.argmax(first)) == toks[0]

    def test_a_prompt_of_several_chunks_through_the_engine(self, variables):
        """30 tokens padded to 32: the prefill's scan carries the state over
        four chunks of 8 before the steps take it up."""
        prompt = prompt_of(30, seed=4)
        engine = make_engine(variables, max_slots=2)
        toks, logits = greedy_run(engine, 0, prompt, 3)
        seq = list(prompt) + toks
        want = reference_logits(variables, seq, [len(prompt) + i for i in range(3)])
        for i, got in enumerate(logits):
            np.testing.assert_allclose(got, want[i], atol=LOGIT_ATOL)

    def test_a_bfloat16_run_fails_the_tolerance(self, variables):
        prompt = prompt_of(11)
        low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), variables)
        engine = make_engine(low, dtype=jnp.bfloat16)
        toks, logits = greedy_run(engine, 0, prompt, 4)
        seq = list(prompt) + toks
        want = reference_logits(variables, seq, [len(prompt) + i for i in range(4)])
        worst = max(float(np.max(np.abs(g - w))) for g, w in zip(logits, want))
        assert worst > 20 * LOGIT_ATOL

    def test_the_reference_in_bfloat16_fails_the_tolerance(self, variables, monkeypatch):
        """The control of ``correct``: the same plain math with every matrix
        product rounded through bfloat16 leaves the tolerance too."""
        monkeypatch.syspath_prepend(str(REPO / "benchmark"))   # benchlib.lowprec
        seq = list(prompt_of(15, seed=2))
        positions = list(range(8, 15))
        full = reference_logits(variables, seq, positions)
        low = reference_logits(variables, seq, positions, mode="bf16")
        assert float(np.max(np.abs(full - low))) > 20 * LOGIT_ATOL

    def test_rows_are_independent_of_strangers(self, variables):
        prompt = prompt_of(9, seed=1)
        alone = make_engine(variables)
        _, want = greedy_run(alone, 0, prompt, 4)
        shared = make_engine(variables)
        shared.join(0, prompt_of(17, seed=2))
        shared.join(3, prompt_of(5, seed=3))
        _, got = greedy_run(shared, 2, prompt, 4)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=LOGIT_ATOL)

    @pytest.mark.parametrize("key,factor", [("embedding_multiplier", 0.5),
                                            ("residual_multiplier", 2.0),
                                            ("logits_scaling", 2.0)])
    def test_every_multiplier_reaches_the_logits(self, variables, key, factor):
        """The reference with one multiplier changed leaves the tolerance:
        the engine, which meets the reference with each as published, applies
        each of them."""
        seq = list(prompt_of(12, seed=8))
        positions = [8, 11]
        base = reference_logits(variables, seq, positions)
        changed = {**ref_cfg(), key: getattr(CFG, key) * factor}
        other = np.asarray(REF.logits_at(changed, flat_of(variables),
                                         jnp.asarray(np.asarray(seq, np.int32)[None]),
                                         jnp.asarray(np.asarray(positions, np.int32)[None]))[0])
        assert float(np.max(np.abs(base - other))) > 20 * LOGIT_ATOL


# ---------------------------------------------------------------------------
# the score scale, the recurrence, padded prefill
# ---------------------------------------------------------------------------


def test_the_q_turn_gives_the_published_score_scale():
    """At the published widths q is turned by 1/64 x sqrt(64) = 2^-3, and the
    shared causal form's own 1/sqrt(64) after it gives softmax(q . k / 64)."""
    cfg = json.loads(CONFIG_FILE.read_text())
    config = gh.GraniteHybridConfig.from_published(cfg)
    assert config.q_turn == 2.0 ** -3 and config.head_dim == 64
    rng = np.random.default_rng(0)
    s, heads, kv, dh = 12, 32, 8, 64
    q = rng.normal(size=(s, heads, dh)).astype(np.float32) * 3.0
    k = rng.normal(size=(s, kv, dh)).astype(np.float32) * 3.0
    v = rng.normal(size=(s, kv, dh)).astype(np.float32)
    got = np.asarray(gqa_causal_attention(jnp.asarray(q) * config.q_turn, jnp.asarray(k), jnp.asarray(v)))
    kk, vv = np.repeat(k, heads // kv, axis=1), np.repeat(v, heads // kv, axis=1)
    scores = np.einsum("qhd,khd->hqk", q.astype(np.float64), kk) / 64.0
    scores = np.where(np.tril(np.ones((s, s), bool))[None], scores, -np.inf)
    probs = np.exp(scores - scores.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    want = np.einsum("hqk,khd->qhd", probs, vv)
    np.testing.assert_allclose(got, want, atol=2e-5)
    # Unturned, the form's own q . k / 8 is another distribution: the turn matters.
    unturned = np.asarray(gqa_causal_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
    assert float(np.abs(unturned - want).max()) > 0.1


def _sequential_ssm(xs, dt, a, b, c):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t (x) B_t, y_t = h_t C_t, one group, float64."""
    s, heads, p_dim = xs.shape
    h = np.zeros((heads, p_dim, b.shape[-1]))
    ys = np.zeros((s, heads, p_dim))
    for t in range(s):
        h = np.exp(dt[t] * a)[:, None, None] * h + (dt[t][:, None] * xs[t])[:, :, None] * b[t, 0][None, None]
        ys[t] = np.einsum("hpn,n->hp", h, c[t, 0])
    return ys, h


class TestRecurrence:
    @pytest.mark.parametrize("length,chunk", [(24, 8), (40, 8), (64, 16), (48, 48)])
    def test_chunked_scan_equals_the_sequential_recurrence(self, length, chunk):
        """ONE group of B and C shared by every head, as published."""
        rng = np.random.default_rng(length + chunk)
        heads, p_dim, n = 8, 16, 16
        xs = rng.normal(size=(length, heads, p_dim)).astype(np.float32)
        dt = np.abs(rng.normal(size=(length, heads))).astype(np.float32) * 0.3
        a = -np.exp(rng.normal(size=heads)).astype(np.float32)
        b = rng.normal(size=(length, 1, n)).astype(np.float32)
        c = rng.normal(size=(length, 1, n)).astype(np.float32)
        want_y, want_h = _sequential_ssm(*(v.astype(np.float64) for v in (xs, dt, a, b, c)))
        y, h = mamba2.ssd_chunked(*(jnp.asarray(v) for v in (xs, dt, a, b, c)), chunk)
        np.testing.assert_allclose(np.asarray(y), want_y, atol=3e-5)
        np.testing.assert_allclose(np.asarray(h), want_h, atol=3e-5)

    def test_padded_prefill_equals_unpadded(self, variables):
        """Same prompt through engines whose prefill pads to 16, 24 and 32:
        first token, logits, and the state the slot is left with."""
        prompt = prompt_of(13, seed=5)
        runs = []
        for pad in (16, 24, 32):
            engine = make_engine(variables, max_prefill=pad, max_slots=2)
            toks, logits = greedy_run(engine, 1, prompt, 3)
            runs.append((toks, logits, slot_state(engine, 1)))
        for toks, logits, state in runs[1:]:
            assert toks == runs[0][0]
            for a, b in zip(logits, runs[0][1]):
                np.testing.assert_allclose(a, b, atol=LOGIT_ATOL)
            for name in state:
                np.testing.assert_allclose(state[name], runs[0][2][name], atol=2e-5)

    def test_conv_windows_are_the_prompts_last_three_rows(self, variables):
        prompt = prompt_of(13, seed=6)
        engine = make_engine(variables)
        engine.join(2, prompt)
        p = variables["params"]["layer0"]
        x = variables["params"]["embed"]["embedding"][prompt] * CFG.embedding_multiplier
        u = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + CFG.rms_norm_eps)
        u = u * p["input_norm"]["scale"]
        inner, conv_dim = CFG.mamba.inner, CFG.mamba.conv_dim
        want = np.asarray((u @ p["mamba"]["in_proj"]["kernel"])[-3:, inner:inner + conv_dim])
        np.testing.assert_allclose(np.asarray(engine._r_state["conv"][2, 0]), want, atol=2e-5)

    def test_one_step_decode_continues_the_prefill_state(self, variables):
        """Prefill of n tokens then a step == prefill of n + 1 tokens: the
        state a slot holds is the state after its last real position."""
        prompt = prompt_of(12, seed=9)
        a = make_engine(variables)
        first = a.join(0, prompt)
        a.ensure_capacity(0)
        a.step()
        b = make_engine(variables)
        b.join(0, np.append(prompt, first).astype(np.int32))
        sa, sb = slot_state(a, 0), slot_state(b, 0)
        for name in sa:
            np.testing.assert_allclose(sa[name], sb[name], atol=2e-5)


# ---------------------------------------------------------------------------
# state slots: the stacked layout, reuse, inactive rows, one compiled entry, spans
# ---------------------------------------------------------------------------


class TestStateSlots:
    def test_a_reused_slot_gives_a_fresh_engines_logits(self, variables):
        engine = make_engine(variables, max_slots=2)
        greedy_run(engine, 0, prompt_of(20, seed=11), 5)      # leaves state behind
        engine.release(0)
        prompt = prompt_of(7, seed=12)
        toks, logits = greedy_run(engine, 0, prompt, 4)
        fresh_toks, fresh = greedy_run(make_engine(variables, max_slots=2), 0, prompt, 4)
        assert toks == fresh_toks
        for g, w in zip(logits, fresh):
            np.testing.assert_allclose(g, w, atol=LOGIT_ATOL)

    def test_inactive_slots_keep_their_state(self, variables):
        engine = make_engine(variables)
        greedy_run(engine, 1, prompt_of(9, seed=13), 2)
        engine.release(1)                                      # its rows stay where they are
        before = slot_state(engine, 1)
        assert all(np.abs(a).max() > 0 for a in before.values())
        greedy_run(engine, 0, prompt_of(5, seed=14), 3)        # steps run all four rows
        for name, a in slot_state(engine, 1).items():
            np.testing.assert_array_equal(a, before[name])
        for a in slot_state(engine, 3).values():               # a slot never joined
            assert not a.any()

    def test_state_is_one_stacked_array_per_kind(self, variables):
        """``conv [slots, mamba layers, K-1, conv_dim]`` and ``ssm [slots,
        mamba layers, heads, head_dim, state]`` float32: four mamba layers, a
        run replaces two state arrays beside the two pools."""
        import chip_smoke

        engine = make_engine(variables)
        state = engine._r_state
        assert sorted(state) == ["conv", "ssm"]
        assert state["conv"].shape == (4, 4, 3, 160) and state["ssm"].shape == (4, 4, 8, 16, 16)
        assert state["ssm"].dtype == jnp.float32
        per_slot = 4 * (3 * 160 * 4 + 8 * 16 * 16 * 4)
        assert engine.state.bytes_per_slot == per_slot
        assert engine.state.nbytes == 4 * per_slot and engine.state_bytes_active == 0
        # only the two attention layers take pages: 2 x 64 pages, rows of 2 KV heads x 16
        assert engine.cache.k_pages.shape == (2 * 64, 8, 32)
        assert chip_smoke.replaced_arrays_donated(engine, free_slot=0) == 4
        low = make_engine(jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), variables),
                          dtype=jnp.bfloat16)
        assert low._r_state["conv"].dtype == jnp.bfloat16 and low._r_state["ssm"].dtype == jnp.float32

    def test_warmup_leaves_no_trace(self, variables):
        engine = make_engine(variables)
        before = engine.resident_bytes()
        engine.warmup()
        assert not engine.active.any() and engine.steps == 0 and engine.tokens_out == 0
        assert engine.resident_bytes() == before

    def test_one_jit_entry_across_joins_and_releases(self, variables):
        engine = make_engine(variables)
        engine.warmup()
        for i, n in enumerate((3, 17, 32, 9)):
            engine.join(i % 3, prompt_of(n, seed=i))
            engine.ensure_capacity(i % 3)
            engine.step()
            if i % 2:
                engine.release(i % 3)
            if i == 1:
                engine.release(0)
        assert engine.jit_cache_sizes() == {"step": 1, "prefill": 1}

    def test_step_reports_the_state_it_holds(self, variables):
        engine = make_engine(variables)
        engine.join(0, prompt_of(10))
        assert engine.prefill_attrs == {}
        engine.join(2, prompt_of(4, seed=2))
        engine.step()
        assert engine.step_attrs == {"state_bytes": 2 * engine.state.bytes_per_slot}


class TestBatchedAdmission:
    @pytest.mark.parametrize("k,temperature", batched_admission.CASES)
    def test_one_admission_of_k_is_k_serial_joins(self, variables, k, temperature):
        """Pages AND the stacked state: each prompt's row of the run leaves
        its slot's rows as the prompt alone left them."""
        batched_admission.assert_batch_matches_serial(
            lambda: make_engine(variables), VOCAB, k, temperature)


# ---------------------------------------------------------------------------
# the config, the registry entry, the published counts, the refusals
# ---------------------------------------------------------------------------


def test_registered_like_any_lm_and_counted():
    spec = get_model(MODEL)
    assert spec.kind == "lm" and spec.num_outputs == VOCAB and spec.input_size == CFG.max_len
    from dmlc_tpu.models.weights import check_variables, variables_template

    _, variables = spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    check_variables(MODEL, variables)
    leaves = jax.tree_util.tree_leaves(variables_template(MODEL))
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == spec.param_count()
    family = spec.decode_family(jnp.float32)
    assert (family.kv_layers, family.kv_heads, family.head_dim) == (2, 2, 16)
    assert "head" not in variables["params"]                  # tied to the embedding


def _count(config) -> int:
    leaves = jax.tree_util.tree_leaves(gh.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    return sum(int(np.prod(shape)) for shape in leaves)


def test_published_keys_count_the_whole_model():
    """The benchmark's configuration file through ``from_published``: all 40
    layers, 3,191,396,096 parameters, every published width as it stands,
    and 76,437,504 bytes of recurrent state a slot."""
    cfg = json.loads(CONFIG_FILE.read_text())
    config = gh.GraniteHybridConfig.from_published(cfg, max_len=cfg["serving_positions"])
    assert _count(config) == 3_191_396_096
    assert len(config.layer_types) == 40 and config.layers_of(gh.ATTENTION) == [5, 15, 25, 35]
    assert (config.hidden_size, config.num_attention_heads, config.head_dim) == (2048, 32, 64)
    dims = config.mamba
    assert (dims.num_heads, dims.head_dim, dims.n_groups, dims.state_size) == (64, 64, 1, 128)
    assert (dims.inner, dims.conv_dim, dims.chunk_size) == (4096, 4352, 256)
    assert (config.shared_intermediate_size, config.vocab_size) == (8192, 100352)
    family = gh.GraniteHybridFamily(config, jnp.bfloat16)
    assert (family.kv_layers, family.kv_heads, family.head_dim) == (4, 8, 64)
    shapes = family.state_shapes(64)
    assert shapes["conv"] == ((64, 36, 3, 4352), jnp.bfloat16)
    assert shapes["ssm"] == ((64, 36, 64, 64, 128), jnp.float32)
    assert 36 * (3 * 4352 * 2 + 64 * 64 * 128 * 4) == 76_437_504       # a slot's bytes of the two


@pytest.mark.parametrize("change,message", [
    ({"num_local_experts": 4}, "num_local_experts"),
    ({"position_embedding_type": "rope"}, "position_embedding_type"),
    ({"attention_bias": True}, "attention_bias"),
    ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    ({"attention_multiplier": 0.02}, "attention_multiplier"),
    ({"mamba_conv_bias": False}, "mamba_conv_bias"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"num_hidden_layers": 9}, "num_hidden_layers"),
    ({"mamba_expand": 4}, "mamba_expand"),
    ({"layer_types": ["mamba", "sliding"]}, "layer_types"),
])
def test_what_the_family_does_not_build_is_refused(change, message):
    base = {**ref_cfg(), "num_hidden_layers": len(CFG.layer_types), "mamba_expand": 2}
    gh.GraniteHybridConfig.from_published(base)               # the base itself is built
    with pytest.raises(ValueError, match=message):
        gh.GraniteHybridConfig.from_published({**base, **change})
