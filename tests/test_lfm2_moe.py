"""The LFM2-MoE family (gated short convolutions 3:1 with rotary
grouped-query attention, gated experts after two dense layers) through the
generation engine, against the benchmark's plain reference.

The reference is ONE file, ``benchmark/reference/lfm2_moe.py`` (float32,
``highest`` precision, the conv as shifted products, dense masks, explicit
cos/sin, a loop over the experts), loaded here by path: the same copy of the
plain math decides ``correct`` on the chip. Everything runs ``lfm2_moe_tiny``
(two dense layers, then ``attn conv conv conv`` twice; 4 query heads on 2 KV
heads of 16, 8 experts top 2) in float32 with seeded weights.
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
from dmlc_tpu.models import blocks  # noqa: E402
from dmlc_tpu.models import lfm2_moe as lf  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk  # noqa: E402

MODEL = "lfm2_moe_tiny"
CFG = lf.LFM2_MOE_TINY
VOCAB = CFG.vocab_size
REPO = plain_reference.REPO

#: Engine (prefill + decode through pages and conv windows) against the
#: reference's one full forward, float32 on the CPU. What separates them is
#: summation order (paged against dense attention, the expert layer's two
#: forms against a loop) and the rotary tables (``exp`` of a product against a
#: power). Measured here: 7.2e-7 on logits whose largest is 3.0 (spread 1.08):
#: 2.4e-7 of scale. The same run with bfloat16 weights and activations reads
#: 0.0143, and the reference with every matrix product rounded through
#: bfloat16 0.0127: over three thousand times this tolerance (the tests below
#: ask for twenty). One position's shift moves a logit by 0.003.
LOGIT_ATOL = 4e-6

REF = plain_reference.load("lfm2_moe")


def ref_cfg(cfg=CFG) -> dict:
    """The reference reads a configuration FILE's keys: build that shape."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_dense_layers",
        "num_experts", "num_experts_per_tok", "num_attention_heads", "num_key_value_heads",
        "conv_L_cache", "rope_theta", "norm_eps", "norm_topk_prob", "routed_scaling_factor",
        "use_expert_bias", "vocab_size")}
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

    def test_slots_of_different_lengths_each_rotate_at_their_own_position(self, variables):
        """Three residents at lengths 3, 17 and 29 in ONE step: each row's
        query and new key turn at that row's own position, so each gives the
        reference's logits for its own sequence. A step that turned every row
        at one position (the first slot's, say) fails for the others."""
        prompts = {0: prompt_of(3, seed=1), 2: prompt_of(17, seed=2), 3: prompt_of(29, seed=3)}
        engine = make_engine(variables)
        served = {slot: [engine.join(slot, p)] for slot, p in prompts.items()}
        for _ in range(3):
            for slot in prompts:
                engine.ensure_capacity(slot)
            out = engine.step()
            logits = np.array(engine.last_logits)
            for slot, p in prompts.items():
                seq = list(p) + served[slot]
                want = reference_logits(variables, seq, [len(seq) - 1])[0]
                np.testing.assert_allclose(logits[slot], want, atol=LOGIT_ATOL)
                served[slot].append(int(out[slot]))
        # The position matters: the same sequence one position late is another answer.
        seq = list(prompts[2]) + served[2][:1]
        here = reference_logits(variables, seq, [len(seq) - 1])[0]
        late = reference_logits(variables, [0] + seq, [len(seq)])[0]
        assert float(np.max(np.abs(here - late))) > 100 * LOGIT_ATOL

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
# rotary positions
# ---------------------------------------------------------------------------


class TestRotary:
    @pytest.mark.parametrize("theta", [10000.0, 1000000.0])
    def test_the_rotation_is_complex_multiplication(self, theta):
        """Lanes ``i`` and ``i + d/2`` of a head are the real and imaginary
        part of one number, turned by ``exp(i pos theta^(-2i/d))``: float64
        complex arithmetic against the family's float32 tables."""
        rng = np.random.default_rng(0)
        heads, dh = 3, 16
        positions = np.array([0, 1, 7, 500, 1023, 1406])
        x = rng.normal(size=(len(positions), heads, dh))
        cos, sin = blocks.rotary_tables(jnp.asarray(positions), dh, theta)
        assert cos.dtype == jnp.float32 and cos.shape == (len(positions), dh // 2)
        got = np.asarray(blocks.apply_rotary(jnp.asarray(x, jnp.float32), cos, sin))
        z = x[..., :dh // 2] + 1j * x[..., dh // 2:]
        turn = np.exp(1j * positions[:, None] * theta ** (-2.0 * np.arange(dh // 2) / dh))
        want = z * turn[:, None, :]
        np.testing.assert_allclose(got[..., :dh // 2], want.real, atol=5e-4)
        np.testing.assert_allclose(got[..., dh // 2:], want.imag, atol=5e-4)
        # Position 0 is the identity, and a turn keeps a pair's length.
        np.testing.assert_array_equal(got[0], x[0].astype(np.float32))
        np.testing.assert_allclose(np.abs(want), np.hypot(got[..., :dh // 2], got[..., dh // 2:]),
                                   rtol=1e-5)

    def test_scores_depend_on_the_distance_alone(self):
        """``<rot(q, m), rot(k, n)>`` is a function of ``m - n``: what lets K
        go into the pages turned and the cache's attention stay position-free."""
        rng = np.random.default_rng(1)
        q, k = (jnp.asarray(rng.normal(size=(1, 1, 16)), jnp.float32) for _ in range(2))
        def score(m, n):
            cm, sm = blocks.rotary_tables(jnp.asarray([m]), 16, 1e6)
            cn, sn = blocks.rotary_tables(jnp.asarray([n]), 16, 1e6)
            return float(jnp.sum(blocks.apply_rotary(q, cm, sm) * blocks.apply_rotary(k, cn, sn)))
        assert abs(score(40, 33) - score(7, 0)) < 1e-4
        assert abs(score(40, 33) - score(40, 30)) > 1e-3

    def test_the_cached_key_is_normed_and_turned(self, variables):
        """Contiguous cache, first attention layer: row ``t`` of a slot's K is
        RMSNorm over each head's lanes of ``W_k u_t``, turned to position t."""
        prompt = prompt_of(9, seed=5)
        engine = make_engine(variables, cache="contiguous")
        engine.join(1, prompt)
        params = variables["params"]
        x = params["embed"]["embedding"][prompt]
        for i in (0, 1):                                    # the two dense conv layers
            p = params[f"layer{i}"]
            u = blocks.rms_norm(x, p["operator_norm"]["scale"], CFG.norm_eps)
            x = x + lf.shortconv_prefill(p["shortconv"], CFG, u, 9)[0]
            u = blocks.rms_norm(x, p["ffn_norm"]["scale"], CFG.norm_eps)
            x = x + blocks.gated_mlp(p["mlp"], u)
        p = params["layer2"]
        u = blocks.rms_norm(x, p["operator_norm"]["scale"], CFG.norm_eps)
        k = (u @ p["attn"]["qkv"]["kernel"])[:, 64:96].reshape(9, 2, 16)
        k = np.asarray(k / jnp.sqrt(jnp.mean(k * k, axis=-1, keepdims=True) + CFG.norm_eps)
                       * p["attn"]["k_norm"]["scale"], np.float64)
        turn = np.exp(1j * np.arange(9)[:, None] * CFG.rope_theta ** (-2.0 * np.arange(8) / 16))
        want = (k[..., :8] + 1j * k[..., 8:]) * turn[:, None, :]
        got = np.asarray(engine._k_state[0, 1, :9])
        np.testing.assert_allclose(got[..., :8], want.real, atol=1e-5)
        np.testing.assert_allclose(got[..., 8:], want.imag, atol=1e-5)


# ---------------------------------------------------------------------------
# the conv operator: padded prefill, the window, one-step decode
# ---------------------------------------------------------------------------


class TestShortConv:
    def test_padded_prefill_equals_unpadded(self, variables):
        """Same prompt through engines whose prefill pads to 16, 32 and 80:
        first token, logits, and the windows the slot is left with."""
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
                np.testing.assert_allclose(a, b, atol=1e-6)

    @pytest.mark.parametrize("length", [1, 2, 13])
    def test_the_window_is_the_last_two_real_rows_of_the_gated_product(self, variables, length):
        """Layer 0's window after a prefill padded to 32: rows ``length-2,
        length-1`` of ``z = B * u`` (zeros before position 0), whatever the
        padding rows hold."""
        prompt = prompt_of(length, seed=6)
        engine = make_engine(variables)
        engine.join(2, prompt)
        params = variables["params"]
        p = params["layer0"]
        u = blocks.rms_norm(params["embed"]["embedding"][prompt], p["operator_norm"]["scale"],
                        CFG.norm_eps)
        bcu = np.asarray(u @ p["shortconv"]["in_proj"]["kernel"])
        z = np.concatenate([np.zeros((2, 64), np.float32), bcu[:, :64] * bcu[:, 128:]])
        np.testing.assert_allclose(np.asarray(engine._r_state["conv"][0][2]), z[-2:], atol=1e-6)

    def test_the_conv_is_three_causal_taps(self):
        """``c_t = k_0 z_{t-2} + k_1 z_{t-1} + k_2 z_t`` and ``y = W_out (C *
        c)``, written out in NumPy; a decode step continues it."""
        rng = np.random.default_rng(2)
        cfg = lf.Lfm2MoeConfig.from_published({**ref_cfg(), "hidden_size": 8,
                                               "num_attention_heads": 2, "num_key_value_heads": 1})
        p = {"in_proj": {"kernel": jnp.asarray(rng.normal(size=(8, 24)), jnp.float32)},
             "conv": {"kernel": jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)},
             "out_proj": {"kernel": jnp.asarray(rng.normal(size=(8, 8)), jnp.float32)}}
        u = jnp.asarray(rng.normal(size=(6, 8)), jnp.float32)
        bcu = np.asarray(u @ p["in_proj"]["kernel"], np.float64)
        z = np.concatenate([np.zeros((2, 8)), bcu[:, :8] * bcu[:, 16:]])
        taps = np.asarray(p["conv"]["kernel"], np.float64)
        c = np.stack([taps[0] * z[t] + taps[1] * z[t + 1] + taps[2] * z[t + 2] for t in range(6)])
        want = (bcu[:, 8:16] * c) @ np.asarray(p["out_proj"]["kernel"], np.float64)
        out, window = lf.shortconv_prefill(p, cfg, u, 5)           # the sixth row is padding
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(window), z[5:7], rtol=1e-5)      # rows 3 and 4
        step, new = lf.shortconv_decode(p, cfg, u[5:6], window[None], jnp.asarray([True]))
        np.testing.assert_allclose(np.asarray(step[0]), want[5], rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(new[0]), z[6:8], rtol=1e-5)
        _, kept = lf.shortconv_decode(p, cfg, u[5:6], window[None], jnp.asarray([False]))
        np.testing.assert_array_equal(np.asarray(kept[0]), np.asarray(window))

    def test_one_step_decode_continues_the_prefill_state(self, variables):
        """Prefill of n tokens then a step == prefill of n + 1 tokens: the
        windows a slot holds are those after its last real position."""
        prompt = prompt_of(12, seed=9)
        a = make_engine(variables)
        first = a.join(0, prompt)
        a.ensure_capacity(0)
        a.step()
        b = make_engine(variables)
        b.join(0, np.append(prompt, first).astype(np.int32))
        for x, y in zip(slot_state(a, 0), slot_state(b, 0)):
            np.testing.assert_allclose(x, y, atol=1e-6)


# ---------------------------------------------------------------------------
# the gated expert layer
# ---------------------------------------------------------------------------


class TestGatedExperts:
    def _layer(self, variables, t=40):
        p = variables["params"]["layer2"]["moe"]
        u = jnp.asarray(np.random.default_rng(21).normal(size=(t, 64)).astype(np.float32))
        return p, u

    def _loop(self, p, u, rows):
        """A plain loop over the experts in NumPy float64: every row, every
        expert it chose, ``g W_2 (silu(W_1 u) * W_3 u)``."""
        s = 1.0 / (1.0 + np.exp(-(np.asarray(u, np.float64) @ np.asarray(p["router"]["kernel"], np.float64))))
        pick = np.argsort(-(s + np.asarray(p["router"]["bias"], np.float64)), axis=-1)[:, :2]
        out = np.zeros((u.shape[0], 64))
        for t in range(u.shape[0]):
            if not rows[t]:
                continue
            chosen = s[t, pick[t]]
            for e, g in zip(pick[t], chosen / (chosen.sum() + 1e-6)):
                h = np.asarray(u[t], np.float64) @ np.asarray(p["experts"]["w13"][e], np.float64)
                h = h[:48] / (1.0 + np.exp(-h[:48])) * h[48:]
                out[t] += g * (h @ np.asarray(p["experts"]["w2"][e], np.float64))
        return out

    def test_dense_and_grouped_forms_agree_with_each_other_and_a_plain_loop(self, variables):
        p, u = self._layer(variables)
        bias = np.asarray(p["router"]["bias"])
        assert np.abs(bias).max() > 0.02                      # a bias that is not zero ...
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], p["router"]["bias"], 2,
                                        eps=lf.GATE_EPS)
        plain, _ = route_sigmoid_topk(u, p["router"]["kernel"], jnp.zeros(8), 2, eps=lf.GATE_EPS)
        assert (np.sort(np.asarray(idx)) != np.sort(np.asarray(plain))).any()   # ... and picks
        rows = jnp.arange(40) % 5 != 0
        outs = [held_experts_ffn(u, p["experts"]["w13"], p["experts"]["w2"], idx, gates, (0, 8),
                                 rows, n_experts=8, activation=blocks.gated_expert, dense=dense)
                for dense in (False, True, None)]
        want = self._loop(p, u, np.asarray(rows))
        assert np.abs(want).max() > 1e-4
        for routed, counts in outs:
            np.testing.assert_allclose(np.asarray(routed), want, atol=1e-6)
            assert counts.tolist() == outs[0][1].tolist() and int(counts.sum()) == 32 * 2

    def test_the_bias_picks_and_does_not_weigh(self, variables):
        """Gates are the chosen experts' own sigmoid scores over their sum +
        1e-6: a large bias moves the choice and leaves no trace in the weight."""
        p, u = self._layer(variables)
        bias = jnp.zeros(8).at[5].set(10.0)
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], bias, 2, eps=lf.GATE_EPS)
        assert (np.asarray(idx) == 5).any(axis=-1).all()
        s = np.asarray(jax.nn.sigmoid(u @ p["router"]["kernel"]))
        chosen = np.take_along_axis(s, np.asarray(idx), axis=-1)
        np.testing.assert_allclose(np.asarray(gates), chosen / (chosen.sum(-1, keepdims=True) + 1e-6),
                                   rtol=1e-5)
        dense = np.asarray(REF.route(u, {"router": {"kernel": p["router"]["kernel"], "bias": bias}},
                                     REF.sizes(ref_cfg())))
        np.testing.assert_allclose(np.take_along_axis(dense, np.asarray(idx), axis=-1),
                                   np.asarray(gates), rtol=1e-5)

    def test_the_static_shapes_choose_the_form(self, variables):
        """One rule for every family: at most a tile of rows whose pairs hit
        most experts take the dense form. The cell's step (64 rows, top 4 of
        32: 256 pairs >= 64) is dense, its prefill (1,024 rows) grouped."""
        def form(t, k, experts):
            w13, w2 = jnp.zeros((experts, 8, 16)), jnp.zeros((experts, 8, 8))
            fn = lambda x, i, g: held_experts_ffn(x, w13, w2, i, g, (0, experts),
                                                  n_experts=experts, activation=blocks.gated_expert)[0]
            return str(jax.make_jaxpr(fn)(jnp.zeros((t, 8)), jnp.zeros((t, k), jnp.int32),
                                          jnp.zeros((t, k))))
        kernel = "_grouped_expert_matmul"
        assert kernel not in form(64, 4, 32)
        assert kernel in form(1024, 4, 32) and kernel in form(8, 4, 32)
        assert "ragged_dot" not in form(1024, 4, 32)

    def test_a_config_without_the_expert_bias_is_served_as_a_zero_bias(self, variables):
        cfg = lf.Lfm2MoeConfig.from_published({**ref_cfg(), "use_expert_bias": False})
        assert "bias" not in lf.param_shapes(cfg)["layer2"]["moe"]["router"]
        p, u = self._layer(variables)
        no_bias = {**p, "router": {"kernel": p["router"]["kernel"]}}
        zero = {**p, "router": {"kernel": p["router"]["kernel"], "bias": jnp.zeros(8)}}
        rows = jnp.ones(40, bool)
        np.testing.assert_array_equal(np.asarray(lf.gated_moe(no_bias, cfg, u, rows)[0]),
                                      np.asarray(lf.gated_moe(zero, CFG, u, rows)[0]))


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
        # eight conv layers: a window of two rows of 64 float32, per slot
        per_slot = 8 * 2 * 64 * 4
        assert engine.state.bytes_per_slot == per_slot == engine.family.state_bytes_per_slot
        assert engine.state.nbytes == 4 * per_slot
        assert engine.state_bytes_active == 0
        # only the two attention layers take pages: 2 x 64 pages, rows of 2 KV heads x 16
        assert engine.cache.k_pages.shape == (2 * 64, 8, 32)
        shapes = engine.family.state_shapes(4)
        assert shapes == {"conv": [((4, 2, 64), jnp.float32)] * 8}

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

    def test_step_and_prefill_report_expert_state_and_cache_work(self, variables):
        engine = make_engine(variables)
        per_slot = engine.state.bytes_per_slot
        engine.join(0, prompt_of(10))
        attrs = engine.prefill_attrs
        assert attrs["conv_layers"] == 8 and attrs["prompt_tokens"] == 10
        assert attrs["state_bytes_touched"] == per_slot
        assert attrs["expert_pairs"] == 10 * 2 * 8              # 10 tokens, top 2, 8 expert layers
        assert attrs["expert_pairs_absent"] == 0                 # every expert is held here
        assert 1.0 <= attrs["experts_hit"] <= 8.0 and 3 <= attrs["expert_rows_max"] <= 10
        engine.join(2, prompt_of(4, seed=2))
        engine.step()
        attrs = engine.step_attrs
        assert attrs["conv_layers"] == 8
        assert attrs["expert_pairs"] == 2 * 2 * 8                # two residents
        assert attrs["expert_pairs_absent"] == 0
        assert 2.0 <= attrs["experts_hit"] <= 4.0 and 1 <= attrs["expert_rows_max"] <= 2
        assert attrs["state_bytes_touched"] == 2 * 2 * per_slot     # two slots, read and written
        assert attrs["kv_tokens_read"] == (10 + 1) + (4 + 1)
        assert attrs["state_bytes"] == 2 * per_slot


class TestBatchedAdmission:
    @pytest.mark.parametrize("k,temperature", batched_admission.CASES)
    def test_one_admission_of_k_is_k_serial_joins(self, variables, k, temperature):
        """Pages AND conv windows: each prompt's row of the run leaves its
        slot's state as the prompt alone left it."""
        batched_admission.assert_batch_matches_serial(
            lambda: make_engine(variables), VOCAB, k, temperature)


# ---------------------------------------------------------------------------
# the config, the registry entry, and the published counts
# ---------------------------------------------------------------------------


def test_registered_like_any_lm_and_counted():
    spec = get_model(MODEL)
    assert spec.kind == "lm" and spec.num_outputs == VOCAB and spec.input_size == CFG.max_len
    from dmlc_tpu.models.weights import check_variables, variables_template

    _, variables = spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    check_variables(MODEL, variables)
    assert "head" not in variables["params"]                     # tied to the embedding
    leaves = jax.tree_util.tree_leaves(variables_template(MODEL))
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == spec.param_count()
    family = spec.decode_family(jnp.float32)
    assert (family.kv_layers, family.kv_heads, family.head_dim) == (2, 2, 16)
    assert CFG.layer_types == ("conv", "conv") + ("full_attention", "conv", "conv", "conv") * 2


def _count(config) -> int:
    leaves = jax.tree_util.tree_leaves(lf.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    return sum(int(np.prod(shape)) for shape in leaves)


def test_published_keys_count_the_model_and_the_cut(monkeypatch):
    """The benchmark's configuration file through ``from_published``: the
    published ``layer_types`` count 8,339,930,560 parameters, what the count
    file says, the 12 that run here 3,928,728,256, and every published width
    stands as it is."""
    monkeypatch.syspath_prepend(str(REPO / "benchmark"))
    from benchlib import lfm2_moe_counts

    cfg = json.loads((REPO / "benchmark" / "configs" / "lfm2-8b-a1b.json").read_text())
    cut = lf.Lfm2MoeConfig.from_published(cfg, max_len=cfg["serving_positions"])
    assert _count(cut) == lfm2_moe_counts.total_params(cfg) == 3_928_728_256
    published = {**cfg, "layer_types": cfg["published"]["layer_types"],
                 "num_hidden_layers": cfg["published"]["num_hidden_layers"]}
    whole = lf.Lfm2MoeConfig.from_published(published)
    assert _count(whole) == lfm2_moe_counts.total_params(published) == 8_339_930_560
    assert whole.layer_types[:12] == cut.layer_types and len(whole.layer_types) == 24
    assert whole.layers_of(lf.FULL) == [2, 6, 10, 14, 18, 21]
    assert cut.layers_of(lf.FULL) == [2, 6, 10] and cut.num_dense_layers == 2
    assert (cut.hidden_size, cut.num_attention_heads, cut.num_key_value_heads, cut.head_dim) == (
        2048, 32, 8, 64)
    assert (cut.intermediate_size, cut.moe_intermediate_size, cut.vocab_size) == (7168, 1792, 65536)
    assert (cut.num_experts, cut.num_experts_per_tok, cut.conv_L_cache) == (32, 4, 3)
    family = lf.Lfm2MoeFamily(cut, jnp.bfloat16)
    assert (family.kv_layers, family.kv_heads, family.head_dim) == (3, 8, 64)
    assert family.state_bytes_per_slot == 9 * 2 * 2048 * 2       # 8 KB a conv layer
    # Every boundary inside a shared kernel on a multiple of 128 lanes.
    shapes = lf.param_shapes(cut)
    assert shapes["layer0"]["shortconv"]["in_proj"]["kernel"] == (2048, 6144)
    assert shapes["layer2"]["attn"]["qkv"]["kernel"] == (2048, 2048 + 512 + 512)
    assert shapes["layer0"]["mlp"]["gate_up"]["kernel"] == (2048, 2 * 7168)
    assert shapes["layer2"]["moe"]["experts"]["w13"] == (32, 2048, 2 * 1792)
    assert all(edge % 128 == 0 for edge in (2048, 4096, 2560, 7168, 1792))


@pytest.mark.parametrize("change,message", [
    ({"num_hidden_layers": 9}, "num_hidden_layers"),
    ({"conv_bias": True}, "conv_bias"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}}, "rope_scaling"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"layer_types": ["sliding_attention"]}, "layer_types"),
    ({"num_experts_per_tok": 9}, "num_experts_per_tok"),
])
def test_what_the_family_does_not_build_is_refused(change, message):
    base = {**ref_cfg(), "num_hidden_layers": 10}
    with pytest.raises(ValueError, match=message):
        lf.Lfm2MoeConfig.from_published({**base, **change})
