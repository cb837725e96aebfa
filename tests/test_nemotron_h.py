"""The hybrid family (Mamba-2 + GQA + LatentMoE) through the generation
engine, against the benchmark's plain reference.

The reference is ONE file, ``benchmark/reference/nemotron_h.py`` (float32,
``highest`` precision, sequential scan, dense masks), loaded here by path:
the same copy of the plain math decides ``correct`` on the chip. Everything
runs ``nemotron_h_tiny`` (pattern ``ME*E``, 8 experts top 2, 2 held) in
float32 with seeded weights.
"""


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
from dmlc_tpu.models import nemotron_h as nh  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402
from dmlc_tpu.ops.ragged_decode import ragged_decode_attention  # noqa: E402
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk  # noqa: E402

MODEL = "nemotron_h_tiny"
CFG = nh.NEMOTRON_H_TINY
VOCAB = CFG.vocab_size

#: Engine (prefill + decode through pages and state slots) against the
#: reference's one full forward, float32 on the CPU. What separates them is
#: summation order: the chunked scan and the one-step recurrence against the
#: sequential scan, paged against dense attention, sorted rows against a loop
#: over experts. Measured here: under 1e-5 on logits of size about 1. The
#: same run with bfloat16 weights and activations reads 5e-3, a hundred times
#: this tolerance (the test below asks for twenty).
LOGIT_ATOL = 5e-5


REF = plain_reference.load("nemotron_h")


def ref_cfg(cfg=CFG, held=None) -> dict:
    """The reference reads a configuration FILE's keys: build that shape."""
    out = {k: getattr(cfg, k) for k in (
        "hybrid_override_pattern", "hidden_size", "num_attention_heads",
        "num_key_value_heads", "head_dim", "mamba_num_heads", "mamba_head_dim", "n_groups",
        "ssm_state_size", "conv_kernel", "num_experts_per_tok", "routed_scaling_factor",
        "norm_topk_prob", "norm_eps")}
    first, count = held if held is not None else cfg.held
    out["n_routed_experts"] = count
    out["published"] = {"n_routed_experts": cfg.n_routed_experts}
    out["deployment"] = {"experts_held": [first, count]}
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


def reference_logits(variables, seq, positions, cfg=None, mode=None):
    tokens = jnp.asarray(np.asarray(seq, np.int32)[None])
    pos = jnp.asarray(np.asarray(positions, np.int32)[None])
    return np.asarray(REF.logits_at(cfg or ref_cfg(), flat_of(variables), tokens, pos, mode)[0])


def prompt_of(n, seed=7):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).astype(np.int32)


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
        # The prefill's own logits picked the first served token.
        first = reference_logits(variables, seq, [len(prompt) - 1])[0]
        assert int(np.argmax(first)) == toks[0]

    def test_a_bfloat16_run_fails_the_tolerance(self, variables):
        prompt = prompt_of(11)
        low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), variables)
        engine = make_engine(low, dtype=jnp.bfloat16)
        toks, logits = greedy_run(engine, 0, prompt, 4)
        seq = list(prompt) + toks
        want = reference_logits(variables, seq, [len(prompt) + i for i in range(4)])
        worst = max(float(np.max(np.abs(g - w))) for g, w in zip(logits, want))
        assert worst > 20 * LOGIT_ATOL

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
# the recurrence: padded prefill, chunked scan, one-step decode
# ---------------------------------------------------------------------------


def _sequential_ssm(xs, dt, a, b, c):
    """h_t = exp(dt_t a) h_{t-1} + dt_t xs_t (x) B_t ; y_t = h_t C_t, float64."""
    s, heads, p_dim = xs.shape
    groups, n = b.shape[1], b.shape[2]
    per = heads // groups
    h = np.zeros((heads, p_dim, n))
    ys = np.zeros((s, heads, p_dim))
    for t in range(s):
        bt, ct = np.repeat(b[t], per, axis=0), np.repeat(c[t], per, axis=0)
        h = np.exp(dt[t] * a)[:, None, None] * h + (dt[t][:, None] * xs[t])[:, :, None] * bt[:, None, :]
        ys[t] = np.einsum("hpn,hn->hp", h, ct)
    return ys, h


class TestRecurrence:
    def test_chunked_scan_equals_the_sequential_recurrence(self):
        rng = np.random.default_rng(0)
        s, heads, p_dim, groups, n = 24, 8, 8, 2, 16
        xs = rng.normal(size=(s, heads, p_dim)).astype(np.float32)
        dt = np.abs(rng.normal(size=(s, heads))).astype(np.float32) * 0.3
        a = -np.exp(rng.normal(size=heads)).astype(np.float32)
        b = rng.normal(size=(s, groups, n)).astype(np.float32)
        c = rng.normal(size=(s, groups, n)).astype(np.float32)
        want_y, want_h = _sequential_ssm(*(v.astype(np.float64) for v in (xs, dt, a, b, c)))
        for chunk in (4, 8, 24):
            y, h = nh.ssd_chunked(*(jnp.asarray(v) for v in (xs, dt, a, b, c)), chunk)
            np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
            np.testing.assert_allclose(np.asarray(h), want_h, atol=2e-5)

    def test_padded_prefill_equals_unpadded(self, variables):
        """Same prompt through engines whose prefill pads to 16 and to 32:
        first token, prefill logits, and the state the slot is left with."""
        prompt = prompt_of(13, seed=5)
        runs = []
        for pad in (16, 32):
            engine = make_engine(variables, max_prefill=pad, max_slots=2)
            toks, logits = greedy_run(engine, 1, prompt, 3)
            state = jax.tree_util.tree_map(lambda a: np.asarray(a[1]), engine._r_state)
            runs.append((toks, logits, jax.tree_util.tree_leaves(state)))
        (t16, l16, s16), (t32, l32, s32) = runs
        assert t16 == t32
        for a, b in zip(l16, l32):
            np.testing.assert_allclose(a, b, atol=LOGIT_ATOL)
        for a, b in zip(s16, s32):
            np.testing.assert_allclose(a, b, atol=2e-5)

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
        for x, y in zip(jax.tree_util.tree_leaves(a._r_state), jax.tree_util.tree_leaves(b._r_state)):
            np.testing.assert_allclose(np.asarray(x[0]), np.asarray(y[0]), atol=2e-5)


# ---------------------------------------------------------------------------
# state slots: reuse, warm-up, accounting, one compiled entry
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

    def test_warmup_leaves_no_trace_and_state_is_counted(self, variables):
        engine = make_engine(variables)
        before = engine.resident_bytes()
        engine.warmup()
        assert not engine.active.any() and engine.steps == 0 and engine.tokens_out == 0
        assert engine.resident_bytes() == before
        # one M layer: conv window [3, 8*8 + 2*2*16] f32 + h [8, 8, 16] f32, per slot
        per_slot = 3 * 128 * 4 + 8 * 8 * 16 * 4
        assert engine.state.bytes_per_slot == per_slot
        weights_and_pools = before - engine.state.nbytes
        assert engine.state.nbytes == 4 * per_slot and weights_and_pools > 0
        # only the attention layer takes pages: 1 layer x 64 pages, rows of 2 KV heads x 16
        assert engine.cache.k_pages.shape == (64, 8, 32)

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

    def test_step_and_prefill_report_expert_and_state_work(self, variables):
        engine = make_engine(variables)
        engine.join(0, prompt_of(10))
        pre = engine.prefill_attrs
        # 10 rows x top 2 x 2 E layers = 40 pairs, held or absent
        assert pre["expert_pairs"] + pre["expert_pairs_absent"] == 40
        engine.join(2, prompt_of(4, seed=2))
        engine.step()
        attrs = engine.step_attrs
        assert attrs["expert_pairs"] + attrs["expert_pairs_absent"] == 2 * 2 * 2
        assert attrs["state_bytes"] == 2 * engine.state.bytes_per_slot
        assert 0 <= attrs["experts_hit"] <= 2 and attrs["expert_rows_max"] <= 2


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
# grouped-query attention through the ragged decode path
# ---------------------------------------------------------------------------


def test_gqa_ragged_decode_matches_dense_attention():
    rng = np.random.default_rng(4)
    b, s, heads, kv, dh = 3, 24, 8, 2, 16
    q = rng.normal(size=(b, heads, dh)).astype(np.float32)
    k = rng.normal(size=(b, s, kv, dh)).astype(np.float32)
    v = rng.normal(size=(b, s, kv, dh)).astype(np.float32)
    lengths = np.array([24, 5, 1], np.int32)
    got = np.asarray(ragged_decode_attention(*(jnp.asarray(a) for a in (q, k, v, lengths))))
    for row in range(b):
        n = lengths[row]
        for h in range(heads):
            kk, vv = k[row, :n, h // (heads // kv)], v[row, :n, h // (heads // kv)]
            scores = kk @ q[row, h] / np.sqrt(dh)
            w = np.exp(scores - scores.max())
            np.testing.assert_allclose(got[row, h], (w / w.sum()) @ vv, atol=1e-5)


def test_gqa_prefill_attention_matches_repeated_heads():
    rng = np.random.default_rng(5)
    s, heads, kv, dh = 12, 4, 2, 16
    q, k, v = (jnp.asarray(rng.normal(size=shape).astype(np.float32))
               for shape in ((s, heads, dh), (s, kv, dh), (s, kv, dh)))
    from dmlc_tpu.parallel.ring_attention import dense_attention

    rep = lambda a: jnp.repeat(a, heads // kv, axis=1).transpose(1, 0, 2)[None]
    want = dense_attention(q.transpose(1, 0, 2)[None], rep(k), rep(v), causal=True)[0]
    got = nh.gqa_causal_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want.transpose(1, 0, 2)), atol=1e-5)


# ---------------------------------------------------------------------------
# the expert layer: exact at any skew, and the share test
# ---------------------------------------------------------------------------


class TestExpertLayer:
    def _layer(self, variables):
        return variables["params"]["layer1"]["moe"], rng_rows(40)

    def test_the_shares_of_four_ranks_add_up_to_the_uncut_layer(self, variables):
        """Ranks 0-3 hold 2 experts each of 8. Their routed parts summed, the
        shared expert counted once, equal the uncut layer of the REFERENCE
        (all 8 experts held)."""
        full_cfg = nh.NemotronHConfig.from_published(
            {f: getattr(CFG, f) for f in CFG.__dataclass_fields__ if f != "layer_kinds"},
            experts_held=(0, 8))
        module = nh.NemotronHModule(full_cfg, jnp.float32)
        p = module.init(jax.random.PRNGKey(11))["params"]["layer1"]["moe"]
        u = rng_rows(40)
        z = REF.sizes(ref_cfg(full_cfg))
        with jax.default_matmul_precision("highest"):
            whole = np.asarray(REF.moe_mixer(u[None], p, z)[0])
        shared = p["shared"]
        shared_only = (np.square(np.maximum(np.asarray(u) @ np.asarray(shared["w1"]["kernel"]), 0))
                       @ np.asarray(shared["w2"]["kernel"]))
        total = np.zeros_like(whole)
        for rank in range(4):
            held = (2 * rank, 2)
            mine = dict(p, experts={"w1": p["experts"]["w1"][held[0]:held[0] + 2],
                                    "w2": p["experts"]["w2"][held[0]:held[0] + 2]})
            rank_cfg = nh.NemotronHConfig.from_published(
                {f: getattr(CFG, f) for f in CFG.__dataclass_fields__ if f != "layer_kinds"},
                experts_held=held)
            out, counts = nh.latent_moe(mine, rank_cfg, u, jnp.ones(40, bool))
            # this rank's routed part = its output less the shared expert every rank computes
            total += np.asarray(out) - shared_only
            assert int(counts.sum()) <= 40 * 2
        np.testing.assert_allclose(total + shared_only, whole, atol=2e-5)

    @pytest.mark.parametrize("dense", [False, True])
    def test_no_token_is_dropped_when_every_row_picks_one_expert(self, dense):
        """64 and 512 rows, all routed to held expert 1 (the skew a capacity
        would cut): every pair is computed, by either form."""
        rng = np.random.default_rng(8)
        d, f = 16, 24
        w1 = jnp.asarray(rng.normal(size=(2, d, f)).astype(np.float32)) * 0.3
        w2 = jnp.asarray(rng.normal(size=(2, f, d)).astype(np.float32)) * 0.3
        for t in (64, 512):
            x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
            idx = jnp.tile(jnp.asarray([[5, 1]], jnp.int32), (t, 1))       # 5 lives elsewhere
            gates = jnp.tile(jnp.asarray([[0.25, 0.75]], jnp.float32), (t, 1))
            routed, counts = held_experts_ffn(x, w1, w2, idx, gates, (0, 2), n_experts=8,
                                              activation=nh._relu2, dense=dense)
            want = 0.75 * np.square(np.maximum(np.asarray(x) @ np.asarray(w1[1]), 0)) @ np.asarray(w2[1])
            np.testing.assert_allclose(np.asarray(routed), want, atol=1e-4)
            assert counts.tolist() == [0, t]

    def test_dense_and_grouped_forms_agree_and_the_shapes_choose(self, variables):
        p, u = self._layer(variables)
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], p["router"]["bias"], 2, scaling=2.5)
        lat = u @ p["down"]["kernel"]
        rows = jnp.arange(40) % 5 != 0
        outs = [held_experts_ffn(lat, p["experts"]["w1"], p["experts"]["w2"], idx, gates, (0, 2),
                                 rows, n_experts=8, activation=nh._relu2, dense=dense)
                for dense in (False, True, None)]
        for routed, counts in outs[1:]:
            np.testing.assert_allclose(np.asarray(routed), np.asarray(outs[0][0]), atol=1e-6)
            assert counts.tolist() == outs[0][1].tolist()
        # 40 rows x top 2 = 80 pairs >= 2 x 8 experts, at most a tile of rows: the dense form;
        # a prefill's 512 rows, or 4 rows that hit few experts: the grouped one.
        def form(t):
            fn = lambda x, i, g: held_experts_ffn(
                x, p["experts"]["w1"], p["experts"]["w2"], i, g, (0, 2), n_experts=8,
                activation=nh._relu2)[0]
            return str(jax.make_jaxpr(fn)(lat[:1].repeat(t, 0), idx[:1].repeat(t, 0),
                                          gates[:1].repeat(t, 0)))
        assert "ragged_dot" not in form(40)
        assert "ragged_dot" in form(512) and "ragged_dot" in form(4)

    @pytest.mark.parametrize("dense", [False, True], ids=["grouped", "dense"])
    def test_relu_squared_through_the_callers_form_is_what_it_was(self, variables, dense):
        """``held_experts_ffn`` takes the expert's form from its caller since a
        second family serves gated experts through it; this family's form,
        handed in, gives bit for bit what the function gave when it wrote
        ``relu(x W1)^2`` itself (``_held_experts_ffn_before``: that text)."""
        p, u = self._layer(variables)
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], p["router"]["bias"], 2, scaling=2.5)
        lat = u @ p["down"]["kernel"]
        rows = jnp.arange(40) % 5 != 0
        args = (lat, p["experts"]["w1"], p["experts"]["w2"], idx, gates, (0, 2), rows)
        got = held_experts_ffn(*args, n_experts=8, activation=nh._relu2, dense=dense)
        want = _held_experts_ffn_before(*args, dense=dense)
        assert np.abs(np.asarray(want[0])).max() > 1e-5
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))

    def test_the_router_normalises_over_all_chosen_experts(self, variables):
        p, u = self._layer(variables)
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], p["router"]["bias"], 2,
                                        scaling=2.5, normalize=True)
        np.testing.assert_allclose(np.asarray(gates.sum(axis=-1)), 2.5, rtol=1e-5)
        dense = np.asarray(REF.route(u, p, REF.sizes(ref_cfg())))
        picked = np.take_along_axis(dense, np.asarray(idx), axis=-1)
        np.testing.assert_allclose(picked, np.asarray(gates), rtol=1e-5)


def _held_experts_ffn_before(x, w1, w2, idx, gates, held, rows, *, dense):
    """``parallel/moe.held_experts_ffn`` as it stood while relu-squared was its
    only form, kept here word for word as the pin of the test above."""
    first, count = held
    t, k = idx.shape
    local = idx - first
    mine = (local >= 0) & (local < count) & rows[:, None]
    key = jnp.where(mine, local, count)
    group_sizes = jnp.zeros(count + 1, jnp.int32).at[key.reshape(t * k)].add(1)[:count]
    if dense:
        weight = jnp.zeros((t, count + 1), jnp.float32).at[
            jnp.arange(t)[:, None], key].add(gates)[:, :count]
        f32 = jnp.float32
        h = jnp.einsum("td,edf->etf", x.astype(f32), w1.astype(f32))
        h = jnp.square(jax.nn.relu(h)).astype(x.dtype)
        y = jnp.einsum("etf,efd->etd", h.astype(f32), w2.astype(f32))
        return jnp.einsum("etd,te->td", y, weight), group_sizes
    key = key.reshape(t * k)
    order = jnp.argsort(key, stable=True)
    h = jax.lax.ragged_dot(x[order // k], w1, group_sizes, preferred_element_type=jnp.float32)
    h = jnp.square(jax.nn.relu(h)).astype(x.dtype)
    y = jax.lax.ragged_dot(h, w2, group_sizes, preferred_element_type=jnp.float32)
    in_a_group = (key[order] < count)[:, None]
    y = jnp.where(in_a_group, y * gates.reshape(t * k)[order][:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
    return y[back].reshape(t, k, -1).sum(axis=1), group_sizes


def rng_rows(n, d=CFG.hidden_size, seed=21):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(n, d)).astype(np.float32))


# ---------------------------------------------------------------------------
# the registry entry
# ---------------------------------------------------------------------------


def test_registered_like_any_lm_and_counted():
    spec = get_model(MODEL)
    assert spec.kind == "lm" and spec.num_outputs == VOCAB and spec.input_size == CFG.max_len
    from dmlc_tpu.models.weights import check_variables, variables_template

    _, variables = spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    check_variables(MODEL, variables)
    leaves = jax.tree_util.tree_leaves(variables_template(MODEL))
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == spec.param_count()
