"""The DeepSeek-V3 family (latent attention over one cached row a position, a
dense layer, then gated experts beside shared ones, some of them held)
through the generation engine, against the benchmark's plain reference.

The reference is ONE file, ``benchmark/reference/deepseek_v3.py`` (float32,
``highest`` precision, EXPANDED attention only: keys and values per head from
the latent, dense masks, explicit cos/sin, a loop over the held experts),
loaded here by path: the same copy of the plain math decides ``correct`` on
the chip. Everything runs ``deepseek_v3_tiny`` (a dense layer, then three
expert layers; 4 heads of 16 | 8 query lanes over a latent of 32 | 8; 16
experts top 3, experts 4-7 held, two shared) in float32 with seeded weights.
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
from dmlc_tpu.models import deepseek_v3 as ds  # noqa: E402
from dmlc_tpu.models.registry import get_model  # noqa: E402
from dmlc_tpu.ops import ragged_decode  # noqa: E402
from dmlc_tpu.parallel.moe import held_experts_ffn, route_sigmoid_topk  # noqa: E402

MODEL = "deepseek_v3_tiny"
CFG = ds.DEEPSEEK_V3_TINY
VOCAB = CFG.vocab_size
REPO = plain_reference.REPO

#: Engine (prefill in the expanded form, then decode in the absorbed form
#: through the latent pages) against the reference's one full forward in the
#: expanded form, float32 on the CPU. What separates them is summation order:
#: ``(q W_uk) . c`` against ``q . (W_uk c)``, paged against dense attention, the
#: expert layer's two forms against a loop, and the rotary tables (``exp`` of a
#: product against a power). Measured here: 1.8e-7 on logits whose largest is
#: 3.4 (spread 1.0). The same run with bfloat16 weights and activations reads
#: 0.05, and the reference with every matrix product rounded through bfloat16
#: 0.03: ten thousand times this tolerance (the tests below ask for twenty).
LOGIT_ATOL = 4e-6

REF = plain_reference.load("deepseek_v3")

#: The engine's three ways to the same attention.
CACHES = [pytest.param({"cache": "paged", "use_pallas": False}, id="paged-take"),
          pytest.param({"cache": "paged", "use_pallas": True}, id="paged-kernel"),
          pytest.param({"cache": "contiguous"}, id="contiguous")]


def ref_cfg(cfg=CFG) -> dict:
    """The reference reads a configuration FILE's keys: build that shape (the
    file's ``n_routed_experts`` is what is held, ``published`` the router's
    width, ``deployment.experts_held`` the cut)."""
    out = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "n_shared_experts", "num_experts_per_tok", "first_k_dense_replace",
        "routed_scaling_factor", "norm_topk_prob", "rope_theta", "rms_norm_eps", "vocab_size")}
    out["n_routed_experts"] = cfg.held[1]
    out["published"] = {"n_routed_experts": cfg.n_routed_experts}
    out["deployment"] = {"experts_held": list(cfg.held)}
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


def reference_logits(variables, seq, positions, mode=None, cfg=None):
    tokens = jnp.asarray(np.asarray(seq, np.int32)[None])
    pos = jnp.asarray(np.asarray(positions, np.int32)[None])
    return np.asarray(REF.logits_at(cfg or ref_cfg(), flat_of(variables), tokens, pos, mode)[0])


def prompt_of(n, seed=7):
    return np.random.default_rng(seed).integers(0, VOCAB, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
# the engine against the reference's full forward
# ---------------------------------------------------------------------------


class TestAgainstReference:
    @pytest.mark.parametrize("how", CACHES)
    def test_logits_at_every_served_position(self, variables, how, monkeypatch):
        """Prefill, then seven steps that cross a page boundary (position 32,
        pages of 8) and, for the kernel, a chunk boundary (chunks of two
        pages: the step at length 32 reads a third chunk's first row)."""
        monkeypatch.setattr(ragged_decode, "_CHUNK_TOKENS", 16)
        prompt = prompt_of(29)
        engine = make_engine(variables, **how)
        toks, logits = greedy_run(engine, 1, prompt, 7)
        seq = list(prompt) + toks
        # Step i consumed token i of the served ones and predicts the next.
        want = reference_logits(variables, seq, [len(prompt) + i for i in range(7)])
        for i, got in enumerate(logits):
            np.testing.assert_allclose(got, want[i], atol=LOGIT_ATOL)
        assert np.abs(want).max() > 0.5  # the logits say something
        # The prefill's own logits picked the first served token.
        first = reference_logits(variables, seq, [len(prompt) - 1])[0]
        assert int(np.argmax(first)) == toks[0]

    @pytest.mark.parametrize("how", CACHES[:2])
    def test_slots_of_ragged_lengths_each_attend_their_own_rows(self, variables, how):
        """Three residents at lengths 3, 17 and 29 in ONE step: each row's
        query and new key turn at that row's own position and attend that
        slot's own pages, so each gives the reference's logits for its own
        sequence."""
        prompts = {0: prompt_of(3, seed=1), 2: prompt_of(17, seed=2), 3: prompt_of(29, seed=3)}
        engine = make_engine(variables, **how)
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

    def test_padded_prefill_equals_unpadded(self, variables):
        """Same prompt through engines whose prefill pads to 16, 32 and 80
        (one block of queries or several: 80 is not a multiple of the
        block): first token and logits."""
        prompt = prompt_of(13, seed=4)
        runs = [greedy_run(make_engine(variables, max_prefill=pad), 0, prompt, 2)
                for pad in (16, 32, 80)]
        for toks, logits in runs[1:]:
            assert toks == runs[0][0]
            for g, w in zip(logits, runs[0][1]):
                np.testing.assert_allclose(g, w, atol=LOGIT_ATOL)


# ---------------------------------------------------------------------------
# latent attention: what is cached, and the two forms over it
# ---------------------------------------------------------------------------


class TestLatentAttention:
    def _projected(self, variables, s=12, seed=0):
        p = variables["params"]["layer1"]["attn"]
        u = jnp.asarray(np.random.default_rng(seed).standard_normal((s, CFG.hidden_size)),
                        jnp.float32)
        cos, sin = ds.rotary_tables(jnp.arange(s), CFG.qk_rope_head_dim, CFG.rope_theta)
        return p, ds.project(p, CFG, u, cos, sin)

    def test_absorbed_decode_equals_expanded_attention(self, variables):
        """The same weights, the same rows: position t's result in the
        expanded form (keys and values per head from the latent) is the
        absorbed form's (``q W_uk`` against the latent itself, ``W_uv`` after
        the weighted sum), for every t, from the rows as a cache holds them."""
        p, (q_nope, q_rope, rows) = self._projected(variables)
        want = np.asarray(ds.expanded_causal_attention(p, CFG, q_nope, q_rope, rows))
        s = rows.shape[0]
        attended = ragged_decode.latent_decode_attention(
            ds.absorbed_queries(p, q_nope, q_rope), jnp.broadcast_to(rows, (s, *rows.shape)),
            jnp.arange(1, s + 1), value_lanes=CFG.kv_lora_rank, scale=CFG.qk_head_dim ** -0.5)
        got = np.asarray(ds.absorbed_values(p, attended))
        assert got.shape == want.shape == (s, CFG.num_attention_heads, CFG.v_head_dim)
        np.testing.assert_allclose(got, want, atol=2e-6)
        assert np.abs(want).max() > 0.05

    def test_a_block_of_queries_at_a_time_is_the_whole_square(self, variables, monkeypatch):
        p, (q_nope, q_rope, rows) = self._projected(variables, s=12)
        whole = np.asarray(ds.expanded_causal_attention(p, CFG, q_nope, q_rope, rows))
        monkeypatch.setattr(ds, "PREFILL_QUERY_BLOCK", 4)
        blocks = np.asarray(ds.expanded_causal_attention(p, CFG, q_nope, q_rope, rows))
        np.testing.assert_allclose(blocks, whole, atol=1e-6)

    def test_the_cached_row_is_the_normed_latent_and_the_turned_key(self, variables):
        """Contiguous cache, layer 0: row ``t`` of a slot is ``RMSNorm(c_t) |
        k_rope_t`` turned to position t, then zeros up to the stored width;
        one row for all heads and no second array."""
        prompt = prompt_of(9, seed=5)
        engine = make_engine(variables, cache="contiguous")
        engine.join(1, prompt)
        assert engine._v_state is None and engine.latent_row == 128
        params = variables["params"]
        p = params["layer0"]
        u = ds.rms_norm(params["embed"]["embedding"][prompt], p["attn_norm"]["scale"],
                        CFG.rms_norm_eps)
        both = np.asarray(u @ p["attn"]["q_kva"]["kernel"], np.float64)
        q_lanes = CFG.num_attention_heads * CFG.qk_head_dim
        c, k = both[:, q_lanes:q_lanes + 32], both[:, q_lanes + 32:]
        c = c / np.sqrt((c * c).mean(-1, keepdims=True) + CFG.rms_norm_eps) * np.asarray(
            p["attn"]["kv_norm"]["scale"])
        turn = np.exp(1j * np.arange(9)[:, None] * CFG.rope_theta ** (-2.0 * np.arange(4) / 8))
        k = (k[:, :4] + 1j * k[:, 4:]) * turn
        got = np.asarray(engine._k_state[0, 1, :9])
        np.testing.assert_allclose(got[:, :32], c, atol=1e-5)
        np.testing.assert_allclose(got[:, 32:36], k.real, atol=1e-5)
        np.testing.assert_allclose(got[:, 36:40], k.imag, atol=1e-5)
        assert not got[:, 40:].any()

    def test_one_pool_is_allocated_carried_and_released(self, variables):
        engine = make_engine(variables)
        assert engine.cache.v_pages is None and engine._v_state is None
        assert engine.cache.k_pages.shape == (4 * 64, 8, 128)
        assert engine._replaced_max == 8                     # eight runs of ONE array
        weights = sum(a.nbytes for a in jax.tree_util.tree_leaves(variables))
        assert engine.resident_bytes() == weights + 4 * 64 * 8 * 128 * 4
        engine.join(0, prompt_of(5))
        engine.step()
        assert engine._v_state is None and engine.cache.v_pages is None
        assert engine.cache.k_pages is engine._k_state
        freed = engine.release(0)
        assert freed and engine.pages_free == 63


# ---------------------------------------------------------------------------
# the expert layer: the router, the cut, the shares
# ---------------------------------------------------------------------------


def _layer_of(experts=16, top_k=3, d=32, f=8, seed=0):
    """A router over ``experts`` experts and every expert's matrices."""
    rng = np.random.default_rng(seed)
    draw = lambda *shape, std=1.0: jnp.asarray(rng.standard_normal(shape) * std, jnp.float32)
    return {"router": {"kernel": draw(d, experts, std=0.3), "bias": draw(experts, std=0.1)},
            "experts": {"w13": draw(experts, d, 2 * f, std=0.3), "w2": draw(experts, f, d, std=0.3)},
            "shared": {"gate_up": {"kernel": draw(d, 4 * f, std=0.3)},
                       "down": {"kernel": draw(2 * f, d, std=0.3)}}}


class TestExperts:
    def test_the_bias_picks_and_does_not_weigh_and_the_gates_sum_to_the_scaling(self):
        p = _layer_of()
        u = jnp.asarray(np.random.default_rng(1).standard_normal((10, 32)), jnp.float32)
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], p["router"]["bias"], 3,
                                        scaling=2.448, normalize=True)
        scores = np.asarray(jax.nn.sigmoid(u @ p["router"]["kernel"]), np.float64)
        picked = np.argsort(-(scores + np.asarray(p["router"]["bias"])), axis=-1)[:, :3]
        assert (np.sort(np.asarray(idx), axis=-1) == np.sort(picked, axis=-1)).all()
        by_score = np.argsort(-scores, axis=-1)[:, :3]
        assert (np.sort(picked, axis=-1) != np.sort(by_score, axis=-1)).any()   # the bias matters
        chosen = np.take_along_axis(scores, np.asarray(idx), axis=-1)
        np.testing.assert_allclose(gates, 2.448 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.448, rtol=1e-5)

    @pytest.mark.parametrize("dense", [True, False], ids=["dense", "grouped"])
    def test_the_shares_of_eight_ranks_and_the_shared_experts_once_are_the_uncut_layer(self, dense):
        """128 experts top 6, 16 a rank: the routed parts of ranks 0-7, each
        ``held = (16 r, 16)`` with its own 16 experts' matrices, plus the
        shared experts counted ONCE, add up to the reference's layer with
        every expert held; one rank's part is what the reference gives for
        that cut; each token's six pairs are spread over the ranks."""
        p = _layer_of(experts=128, top_k=6)
        u = jnp.asarray(np.random.default_rng(2).standard_normal((10, 32)), jnp.float32)
        cfg = ds.DeepseekV3Config(
            vocab_size=8, hidden_size=32, intermediate_size=8, moe_intermediate_size=8,
            num_hidden_layers=2, num_attention_heads=1, kv_lora_rank=8, qk_nope_head_dim=8,
            qk_rope_head_dim=2, v_head_dim=8, n_routed_experts=128, n_shared_experts=2,
            num_experts_per_tok=6, routed_scaling_factor=2.448)
        idx, gates = route_sigmoid_topk(u, p["router"]["kernel"], p["router"]["bias"], 6,
                                        scaling=2.448)
        rows = jnp.ones(10, bool)
        parts, pairs = [], 0
        for rank in range(8):
            mine = slice(16 * rank, 16 * rank + 16)
            routed, counts = held_experts_ffn(
                u, p["experts"]["w13"][mine], p["experts"]["w2"][mine], idx, gates,
                (16 * rank, 16), rows, n_experts=128, activation=ds.gated_expert, dense=dense)
            parts.append(np.asarray(routed))
            pairs += int(counts.sum())
        assert pairs == 10 * 6
        shared = np.asarray(ds.gated_mlp(p["shared"], u))
        z = REF.sizes({**ref_cfg(cfg), "n_routed_experts": 128,
                       "deployment": {"experts_held": [0, 128]}})
        with jax.default_matmul_precision("highest"):
            whole = np.asarray(REF.expert_layer(u[None], p, z)[0])
            z["held"] = (48, 16)
            rank3 = np.asarray(REF.expert_layer(u[None], {
                **p, "experts": {k: w[48:64] for k, w in p["experts"].items()}}, z)[0])
        np.testing.assert_allclose(sum(parts) + shared, whole, atol=2e-5)
        np.testing.assert_allclose(parts[3] + shared, rank3, atol=2e-5)
        assert np.abs(whole - shared).max() > 0.1 and np.abs(parts[3]).max() > 0.01
        # The family's own layer is that one rank's part plus the shared experts.
        held = ds.DeepseekV3Config(**{**cfg.__dict__, "experts_held": (48, 16)})
        mine = {**p, "experts": {k: w[48:64] for k, w in p["experts"].items()}}
        out, counts = ds.expert_layer(mine, held, u, rows)
        np.testing.assert_allclose(out, rank3, atol=2e-5)
        assert counts.shape == (16,)

    def test_step_and_prefill_report_expert_and_cache_work(self, variables):
        engine = make_engine(variables)
        engine.join(0, prompt_of(10))
        attrs = engine.prefill_attrs
        assert attrs["latent_layers"] == 4 and attrs["prompt_tokens"] == 10
        # 10 tokens, top 3, 3 expert layers; a quarter of the experts live here.
        assert attrs["expert_pairs"] + attrs["expert_pairs_absent"] == 10 * 3 * 3
        assert 0 < attrs["expert_pairs"] < 60 and 1 <= attrs["expert_rows_max"] <= 10
        assert 0.0 < attrs["experts_hit"] <= 4.0
        assert "kv_tokens_read" not in attrs and "state_bytes" not in attrs
        engine.join(2, prompt_of(4, seed=2))
        engine.step()
        attrs = engine.step_attrs
        assert attrs["latent_layers"] == 4
        assert attrs["expert_pairs"] + attrs["expert_pairs_absent"] == 2 * 3 * 3   # two residents
        assert attrs["kv_tokens_read"] == (10 + 1) + (4 + 1)
        # The algorithm's bytes: 40 values a token a layer, whatever a row is stored as.
        assert attrs["latent_bytes_read"] == 16 * 4 * 40 * 4
        assert "state_bytes" not in attrs and "prompt_tokens" not in attrs


# ---------------------------------------------------------------------------
# serving: slots, the programs, admission
# ---------------------------------------------------------------------------


class TestServing:
    def test_a_reused_slot_gives_a_fresh_engines_logits(self, variables):
        engine = make_engine(variables)
        greedy_run(engine, 0, prompt_of(21, seed=9), 3)
        engine.release(0)
        _, got = greedy_run(engine, 0, prompt_of(6, seed=1), 3)
        _, want = greedy_run(make_engine(variables), 0, prompt_of(6, seed=1), 3)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g, w, atol=LOGIT_ATOL)

    def test_warmup_leaves_no_trace_and_one_jit_entry_across_joins(self, variables):
        engine = make_engine(variables)
        engine.warmup()
        assert engine.steps == 0 and engine.tokens_out == 0 and not engine.active.any()
        assert engine.pages_free == 63 and engine.state.nbytes == 0
        for i in range(5):
            engine.join(i % 3, prompt_of(3 + 4 * i, seed=i))
            engine.ensure_capacity(i % 3)
            engine.step()
            engine.release(i % 3)
        assert engine.jit_cache_sizes() == {"step": 1, "prefill": 1}

    @pytest.mark.parametrize("use_pallas", [False, True], ids=["take", "kernel"])
    def test_named_scopes_are_in_both_programs(self, variables, use_pallas):
        import chip_smoke

        engine = make_engine(variables, use_pallas=use_pallas)
        args = chip_smoke.abstract_program_args(engine)
        texts = {name: program.lower(*args[name]).as_text(debug_info=True)
                 for name, program in (("step", engine._step), ("prefill", engine._prefill))}
        for name, text in texts.items():
            for scope in ("mla", "moe", "shared", "mlp"):
                assert f"/{scope}/" in text, (name, scope)
        # The kernel is a function of its own name (what its events on the device
        # trace are called), once a layer, in the step alone.
        call = "call @_paged_latent_decode_attention("
        assert texts["step"].count(call) == (4 if use_pallas else 0)
        assert call not in texts["prefill"]

    @pytest.mark.parametrize("k,temperature", batched_admission.CASES)
    def test_one_admission_of_k_is_k_serial_joins(self, variables, k, temperature):
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
    assert variables["params"]["head"]["kernel"].shape == (64, VOCAB)       # untied
    leaves = jax.tree_util.tree_leaves(variables_template(MODEL))
    assert sum(int(np.prod(leaf.shape)) for leaf in leaves) == spec.param_count()
    family = spec.decode_family(jnp.float32)
    assert (family.kv_layers, family.latent_row, CFG.latent_dim) == (4, 128, 40)
    assert not hasattr(family, "kv_heads") and family.state_shapes(4) == {}
    assert variables["params"]["layer1"]["moe"]["experts"]["w13"].shape == (4, 64, 64)


def _count(config) -> int:
    leaves = jax.tree_util.tree_leaves(ds.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    return sum(int(np.prod(shape)) for shape in leaves)


def test_published_keys_count_the_model_and_the_cut(monkeypatch):
    """The benchmark's configuration file through ``from_published`` and the
    program's own ``param_shapes`` (shapes only, nothing allocated): the
    published depth with every expert held counts 30,670,815,104 parameters,
    what the count file says, the cut that runs here 3,155,018,624, and every
    published width stands as it is."""
    monkeypatch.syspath_prepend(str(REPO / "benchmark"))
    from benchlib import deepseek_v3_counts

    cfg = json.loads((REPO / "benchmark" / "configs" / "kanana-2-30b-a3b.json").read_text())
    cut = ds.DeepseekV3Config.from_published(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["deployment"]["experts_held"], max_len=cfg["serving_positions"])
    assert _count(cut) == deepseek_v3_counts.total_params(cfg) == 3_155_018_624
    published = {**cfg, **{k: cfg["published"][k] for k in ("num_hidden_layers",
                                                            "n_routed_experts")}}
    whole = ds.DeepseekV3Config.from_published(published)
    assert _count(whole) == deepseek_v3_counts.published_params(cfg) == 30_670_815_104
    assert (whole.num_hidden_layers, whole.held) == (48, (0, 128))
    assert (cut.num_hidden_layers, cut.held, cut.n_routed_experts) == (24, (0, 16), 128)
    assert (cut.hidden_size, cut.num_attention_heads, cut.kv_lora_rank) == (2048, 32, 512)
    assert (cut.qk_nope_head_dim, cut.qk_rope_head_dim, cut.v_head_dim) == (128, 64, 128)
    assert (cut.intermediate_size, cut.moe_intermediate_size, cut.vocab_size) == (6144, 768, 128256)
    assert (cut.num_experts_per_tok, cut.n_shared_experts, cut.routed_scaling_factor) == (
        6, 2, 2.448)
    assert (cut.first_k_dense_replace, cut.rope_theta, cut.rms_norm_eps) == (1, 1e6, 1e-6)
    family = ds.DeepseekV3Family(cut, jnp.bfloat16)
    # 576 values a token a layer, 1,152 B, stored on 640 lanes.
    assert (family.kv_layers, cut.latent_dim, family.latent_row) == (24, 576, 640)
    assert family.latent_bytes_per_token == 24 * 1152 == 27_648
    shapes = ds.param_shapes(cut)
    assert shapes["layer0"]["attn"]["q_kva"]["kernel"] == (2048, 32 * 192 + 576)
    assert shapes["layer0"]["attn"]["k_up"] == shapes["layer0"]["attn"]["v_up"] == (32, 512, 128)
    assert shapes["layer0"]["mlp"]["gate_up"]["kernel"] == (2048, 2 * 6144)
    assert shapes["layer1"]["moe"]["experts"]["w13"] == (16, 2048, 2 * 768)
    assert shapes["layer1"]["moe"]["shared"]["down"]["kernel"] == (2 * 768, 2048)
    assert shapes["layer1"]["moe"]["router"]["kernel"] == (2048, 128)
    assert "mlp" not in shapes["layer1"] and "moe" not in shapes["layer0"]


@pytest.mark.parametrize("change,message", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"rope_scaling": {"type": "yarn", "factor": 40, "mscale": 1.0}}, "rope_scaling"),
    ({"n_group": 8, "topk_group": 4}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"attention_bias": True}, "attention_bias"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"num_experts_per_tok": 17}, "num_experts_per_tok"),
    ({"experts_held": [12, 8]}, "experts_held"),
    ({"qk_rope_head_dim": 7}, "qk_rope_head_dim"),
])
def test_what_the_family_does_not_build_is_refused(change, message):
    base = {**ref_cfg(), "n_routed_experts": 16}
    assert ds.DeepseekV3Config.from_published(base).held == (0, 16)
    with pytest.raises(ValueError, match=message):
        ds.DeepseekV3Config.from_published({**base, **change})
