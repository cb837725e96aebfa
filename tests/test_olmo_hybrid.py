"""The Olmo-Hybrid family (gated delta-rule linear attention 3:1 with full
attention, two residual branches a layer) through the generation engine,
against the benchmark's plain reference.

The reference is ONE file, ``benchmark/reference/olmo_hybrid.py`` (float32,
``highest`` precision, the delta rule as a sequential scan, dense masks),
loaded here by path: the same copy of the plain math decides ``correct`` on
the chip. Everything runs ``olmo_hybrid_tiny`` (``L L L F`` twice, 4 heads,
``d_k`` 8, ``d_v`` 16) in float32 with seeded weights.
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
from dmlc_tpu.generate.worker import GenerationBackend  # noqa: E402
from dmlc_tpu.models import olmo_hybrid as oh  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402

MODEL = "olmo_hybrid_tiny"
CFG = oh.OLMO_HYBRID_TINY
VOCAB = CFG.vocab_size
REPO = plain_reference.REPO

#: Engine (prefill + decode through pages and state slots) against the
#: reference's one full forward, float32 on the CPU. What separates them is
#: summation order: the chunked delta rule with its triangular solve and the
#: one-step recurrence against the sequential scan, paged against dense
#: attention. Measured here: 2.2e-7 on logits whose largest is 0.56 (spread
#: 0.16), through one chunk or three. The same run with bfloat16 weights and
#: activations reads 4.5e-3, and the reference with every matrix product
#: rounded through bfloat16 2.0e-3: 225 and 100 times this tolerance (the
#: tests below ask for twenty).
LOGIT_ATOL = 2e-5

REF = plain_reference.load("olmo_hybrid")


def ref_cfg(cfg=CFG) -> dict:
    """The reference reads a configuration FILE's keys: build that shape."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads",
        "linear_num_key_heads", "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim", "linear_allow_neg_eigval",
        "rms_norm_eps", "vocab_size")}
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
    return [np.asarray(a[slot]) for a in jax.tree_util.tree_leaves(engine._r_state)]


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
        """150 tokens padded to 160: the prefill's scan carries the state
        over three chunks of 64 before the steps take it up."""
        prompt = prompt_of(150, seed=4)
        engine = make_engine(variables, max_prefill=160, max_slots=2)
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


# ---------------------------------------------------------------------------
# the recurrence: chunked prefill, padded prefill, one-step decode
# ---------------------------------------------------------------------------


def _sequential_delta_rule(q, k, v, beta, log_alpha):
    """S' = alpha S; S = S' + beta (v - S' k) k^T; o = S q, in float64."""
    s, heads, dk = q.shape
    state = np.zeros((heads, v.shape[-1], dk))
    out = np.zeros((s, heads, v.shape[-1]))
    for t in range(s):
        state = np.exp(log_alpha[t])[:, None, None] * state
        read = np.einsum("hvd,hd->hv", state, k[t])
        state = state + (beta[t][:, None] * (v[t] - read))[:, :, None] * k[t][:, None, :]
        out[t] = np.einsum("hvd,hd->hv", state, q[t])
    return out, state


def _rule_inputs(s, heads=4, dk=8, dv=16, seed=0):
    rng = np.random.default_rng(seed)
    unit = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(rng.normal(size=(s, heads, dk))) / np.sqrt(dk)
    k = unit(rng.normal(size=(s, heads, dk)))
    v = rng.normal(size=(s, heads, dv))
    beta = 2.0 / (1.0 + np.exp(-rng.normal(size=(s, heads))))
    log_alpha = -np.abs(rng.normal(size=(s, heads))) * 0.2
    return [a.astype(np.float32) for a in (q, k, v, beta, log_alpha)]


class TestRecurrence:
    @pytest.mark.parametrize("length", [70, 130, 200])
    def test_chunked_rule_equals_the_sequential_recurrence(self, length):
        """Lengths that are no multiple of the chunk, padded as the prefill
        pads them (beta 0, alpha 1): outputs at the real positions and the
        state left behind are the sequential recurrence's."""
        inputs = _rule_inputs(length, seed=length)
        assert 0.3 < float((inputs[3] > 1.0).mean()) < 0.7   # both signs of 1 - beta
        want_o, want_state = _sequential_delta_rule(*(a.astype(np.float64) for a in inputs))
        pad = -length % oh.CHUNK
        padded = [jnp.pad(jnp.asarray(a), [(0, pad)] + [(0, 0)] * (a.ndim - 1)) for a in inputs]
        o, state = oh.delta_rule_chunked(*padded, oh.CHUNK)
        np.testing.assert_allclose(np.asarray(o)[:length], want_o, atol=3e-5)
        np.testing.assert_allclose(np.asarray(state), want_state, atol=3e-5)

    @pytest.mark.parametrize("size", [8, 24, 64])
    def test_blocked_forward_substitution_is_the_triangular_solve(self, size):
        """Rows as the rule makes them (beta up to 2 on unit keys that lean
        the same way, the hard case), against LAPACK's solve in float64."""
        import scipy.linalg

        rng = np.random.default_rng(size)
        keys = rng.normal(size=(3, 2, size, 8)) + 2.0
        keys /= np.linalg.norm(keys, axis=-1, keepdims=True)
        beta = rng.uniform(0.0, 2.0, size=(3, 2, size, 1))
        below = np.tril(beta * (keys @ keys.swapaxes(-1, -2)), -1)
        rhs = rng.normal(size=(3, 2, size, 5))
        got = np.asarray(oh.solve_unit_lower(jnp.asarray(below, jnp.float32),
                                             jnp.asarray(rhs, jnp.float32)))
        for c in range(3):
            for h in range(2):
                want = scipy.linalg.solve_triangular(
                    np.eye(size) + below[c, h], rhs[c, h], lower=True, unit_diagonal=True)
                np.testing.assert_allclose(got[c, h], want, rtol=2e-4, atol=2e-4 * np.abs(want).max())

    def test_one_chunk_or_many_give_the_same_rule(self):
        inputs = [jnp.asarray(a) for a in _rule_inputs(96, seed=5)]
        whole = oh.delta_rule_chunked(*inputs, 96)
        for chunk in (8, 32):
            o, state = oh.delta_rule_chunked(*inputs, chunk)
            np.testing.assert_allclose(np.asarray(o), np.asarray(whole[0]), atol=3e-5)
            np.testing.assert_allclose(np.asarray(state), np.asarray(whole[1]), atol=3e-5)

    def test_beta_reaches_both_sides_of_one_in_the_served_data(self, variables):
        """``linear_allow_neg_eigval``: beta in (1, 2) makes ``1 - beta``
        negative. The tiny model's own projections put it on both sides."""
        p = variables["params"]["layer0"]["deltanet"]
        x = variables["params"]["embed"]["embedding"][prompt_of(64)]
        beta, log_alpha = oh._beta_and_log_alpha(p, CFG, x @ p["ba"]["kernel"])
        beta = np.asarray(beta)
        assert beta.min() > 0.0 and beta.max() < 2.0
        assert (beta > 1.0).any() and (beta < 1.0).any()
        assert np.asarray(log_alpha).max() < 0.0

    def test_padded_prefill_equals_unpadded(self, variables):
        """Same prompt through engines whose prefill pads to 16, 32 and 80
        (two chunks): first token, logits, and the state and conv windows
        the slot is left with."""
        prompt = prompt_of(13, seed=5)
        runs = []
        for pad in (16, 32, 80):
            engine = make_engine(variables, max_prefill=pad, max_slots=2)
            toks, logits = greedy_run(engine, 1, prompt, 3)
            runs.append((toks, logits, slot_state(engine, 1)))
        for toks, logits, state in runs[1:]:
            assert toks == runs[0][0]
            for a, b in zip(logits, runs[0][1]):
                np.testing.assert_allclose(a, b, atol=LOGIT_ATOL)
            for a, b in zip(state, runs[0][2]):
                np.testing.assert_allclose(a, b, atol=2e-5)

    def test_conv_windows_are_the_prompts_last_three_rows(self, variables):
        prompt = prompt_of(13, seed=6)
        engine = make_engine(variables)
        engine.join(2, prompt)
        p = variables["params"]["layer0"]["deltanet"]
        x = variables["params"]["embed"]["embedding"][prompt]
        want = np.asarray((x @ p["qkvg"]["kernel"])[-3:, :CFG.conv_dim])
        np.testing.assert_allclose(np.asarray(engine._r_state["conv"][0][2]), want, atol=1e-6)

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
        for x, y in zip(slot_state(a, 0), slot_state(b, 0)):
            np.testing.assert_allclose(x, y, atol=2e-5)


# ---------------------------------------------------------------------------
# state slots: reuse, inactive rows, accounting, one compiled entry, spans
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
        assert any(np.abs(a).max() > 0 for a in before)
        greedy_run(engine, 0, prompt_of(5, seed=14), 3)        # steps run all four rows
        for a, b in zip(slot_state(engine, 1), before):
            np.testing.assert_array_equal(a, b)
        for a in slot_state(engine, 3):                        # a slot never joined
            assert not a.any()

    def test_warmup_leaves_no_trace_and_state_is_counted(self, variables):
        engine = make_engine(variables)
        before = engine.resident_bytes()
        engine.warmup()
        assert not engine.active.any() and engine.steps == 0 and engine.tokens_out == 0
        assert engine.resident_bytes() == before
        # six linear layers: conv windows [3, 2*32 + 64] f32 + S [4, 16, 8] f32, per slot
        per_slot = 6 * (3 * 128 * 4 + 4 * 16 * 8 * 4)
        assert engine.state.bytes_per_slot == per_slot == engine.family.state_bytes_per_slot
        assert engine.state.nbytes == 4 * per_slot
        assert engine.state_bytes_active == 0
        # only the two full-attention layers take pages: 2 x 64 pages, rows of 4 heads x 16
        assert engine.cache.k_pages.shape == (2 * 64, 8, 64)
        shapes = engine.family.state_shapes(4)
        assert shapes["delta"][0] == ((4, 4, 16, 8), jnp.float32) and len(shapes["conv"]) == 6

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

    def test_step_and_prefill_report_state_and_cache_work(self, variables):
        engine = make_engine(variables)
        per_slot = engine.state.bytes_per_slot
        engine.join(0, prompt_of(10))
        assert engine.prefill_attrs == {
            "linear_layers": 6, "state_bytes_touched": per_slot, "prompt_tokens": 10}
        engine.join(2, prompt_of(4, seed=2))
        engine.step()
        attrs = engine.step_attrs
        assert attrs["linear_layers"] == 6
        assert attrs["state_bytes_touched"] == 2 * 2 * per_slot     # two slots, read and written
        assert attrs["kv_tokens_read"] == (10 + 1) + (4 + 1)
        assert attrs["state_bytes"] == 2 * per_slot


class TestBatchedAdmission:
    @pytest.mark.parametrize("k,temperature", batched_admission.CASES)
    def test_one_admission_of_k_is_k_serial_joins(self, variables, k, temperature):
        """Pages AND state slots: each prompt's row of the run leaves its
        slot's recurrent state as the prompt alone left it."""
        batched_admission.assert_batch_matches_serial(
            lambda: make_engine(variables), VOCAB, k, temperature)


class TestMigration:
    def test_resume_from_prefix_is_token_identical(self, variables):
        """The recurrent state is a pure function of the tokens: re-prefilling
        prompt + delivered prefix with the same seed continues a SAMPLED
        stream exactly where it left off."""
        prompt, seed, n, cut = [3, 1, 4, 1, 5, 9, 2, 6], 4321, 9, 4
        eng = make_engine(variables, max_slots=1, return_logits=False)
        ref = [eng.join(0, np.asarray(prompt, np.int32), temperature=0.8, seed=seed)]
        for _ in range(n - 1):
            eng.ensure_capacity(0)
            ref.append(int(eng.step()[0]))
        backend = GenerationBackend(MODEL, max_slots=4, page_size=8, num_pages=128,
                                    max_prefill=32, max_waiting=8)
        backend.warmup()
        backend.load_variables(variables)
        try:
            stream = backend.submit(prompt, max_new_tokens=n - cut, temperature=0.8,
                                    request_id="resume", seed=seed, resume_tokens=ref[:cut])
            assert stream.result(timeout=120) == ref[cut:]
        finally:
            backend.stop()


# ---------------------------------------------------------------------------
# the config, the registry entry, and the published counts
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
    assert (family.kv_layers, family.kv_heads, family.head_dim) == (2, 4, 16)


def _count(config) -> int:
    leaves = jax.tree_util.tree_leaves(oh.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    return sum(int(np.prod(shape)) for shape in leaves)


def test_published_keys_count_the_model_and_the_cut():
    """The benchmark's configuration file through ``from_published``: the
    published ``layer_types`` count 7.431 B parameters, the 16 that run here
    4.101 B, and every published width stands as it is."""
    cfg = json.loads((REPO / "benchmark" / "configs" / "olmo-hybrid-7b.json").read_text())
    cut = oh.OlmoHybridConfig.from_published(cfg, max_len=cfg["serving_positions"])
    assert round(_count(cut) / 1e9, 3) == 4.101
    whole = oh.OlmoHybridConfig.from_published(
        {**cfg, "layer_types": cfg["published"]["layer_types"],
         "num_hidden_layers": cfg["published"]["num_hidden_layers"]})
    assert round(_count(whole) / 1e9, 3) == 7.431
    assert whole.layer_types[:16] == cut.layer_types and len(whole.layer_types) == 32
    assert cut.layer_types == (oh.LINEAR, oh.LINEAR, oh.LINEAR, oh.FULL) * 4
    assert (cut.hidden_size, cut.num_attention_heads, cut.head_dim) == (3840, 30, 128)
    assert (cut.key_dim, cut.value_dim, cut.conv_dim) == (2880, 5760, 11520)
    assert (cut.intermediate_size, cut.vocab_size) == (11008, 100352)
    family = oh.OlmoHybridFamily(cut, jnp.bfloat16)
    assert (family.kv_layers, family.kv_heads, family.head_dim) == (4, 30, 128)
    assert family.state_bytes_per_slot == 12 * (30 * 192 * 96 * 4 + 11520 * 3 * 2)


@pytest.mark.parametrize("change,message", [
    ({"num_hidden_layers": 9}, "num_hidden_layers"),
    ({"rope_parameters": {"rope_theta": 500000.0}}, "rotary"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"layer_types": ["sliding_attention"]}, "layer_types"),
    ({"linear_num_value_heads": 8}, "value heads"),
])
def test_what_the_family_does_not_build_is_refused(change, message):
    base = {**ref_cfg(), "num_hidden_layers": 8}
    with pytest.raises(ValueError, match=message):
        oh.OlmoHybridConfig.from_published({**base, **change})
