"""What PR 37 added for ``kanana-2-30b-a3b.longform``: discovery finds the
cell, its driver, reference, readers and event pattern; the count file's
totals for the published model and for the cut, and a step's bytes and
operations at a pinned set of lengths; the file keeps every published width;
the new readers on an empty and on a recorded trace; the kernel's pattern
matches its name and not a prefill's grouped product or loop wrapper; and the
whole driver on the CPU at a toy size (sound run correct, fp8 control not, a
token altered where it is produced not correct)."""

import json
import re
from types import SimpleNamespace

import pytest

from benchlib import deepseek_v3_counts as counts, manifest, peaks, trace
from test_correct import drive, load_run
from test_hybrid import recorded  # noqa: F401  (the recorded excerpt, as a fixture)

CELL = "kanana-2-30b-a3b.longform"
CONFIG = manifest.BENCH / "configs" / "kanana-2-30b-a3b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

#: kakaocorp/kanana-2-30b-a3b-instruct-2601 config.json, as the catalog's row has it.
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "kv_lora_rank": 512,
    "max_position_embeddings": 32768, "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128, "n_shared_experts": 2,
    "norm_topk_prob": True, "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
    "rope_scaling": None, "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
}

#: The per-layer entries that list this cell alone: PR 37's three (the manifest had room for
#: three: PERF.md finding PR 37.6) and PR 39's four.
LISTED = ("step_mfu.longform", "step_hbm_roofline.longform", "mla_attention_hbm_roofline.longform",
          "mla_attention_us_per_call", "latent_mb_read_per_step", "moe_ms_per_step.longform",
          "moe_hbm_roofline.longform")
#: Folded quantities (one entry, several cells) that PR 39 gave this cell.
FOLDED = ("step_ms_p50", "slots_active_mean", "ttft_ms_p50", "prefill_prompts_mean", "step_ahead_mean",
          "idle_in_step_host_pct", "tokens_resident_mean", "expert_pairs_per_step", "experts_hit_mean",
          "expert_rows_max_mean", "device_idle_pct", "hbm_peak_gb", "step_operands_ms", "step_call_ms",
          "prefill_operands_ms", "prefill_call_ms", "idle_in_step_call_pct", "idle_in_prefill_dispatch_pct",
          "prefill_run_ms_p50", "first_token_in_ms_p50", "first_token_run_ms_p50", "first_token_out_ms_p50",
          "step_call_cpu_s_mean", "prefill_call_cpu_s_mean", "release_ms")


def test_discovery_finds_the_cell_and_everything_it_names():
    m = manifest.load()
    cell = manifest.cell(m, CELL)
    cfg = manifest.config_of(m, cell)
    mix = manifest.traffic_of(cell)
    assert cell["chips"] == 1 and mix["clients"] == cfg["cluster"]["gen_max_slots"] == 64
    # ISSUE 37's mix, to the number.
    assert mix["prompt_tokens"] == [512, 2048] and mix["output_tokens"] == [768, 1023]
    # The pool reaches every output length (Open question 15's trap).
    assert mix["pool"] == 256 == mix["output_tokens"][1] - mix["output_tokens"][0] + 1
    assert (mix["poll_interval_s"], mix["warm_completions"], mix["check_requests"]) == (0.1, 64, 16)
    assert mix["prompt_tokens"][1] == cfg["cluster"]["gen_max_prefill"]
    assert mix["prompt_tokens"][1] + mix["output_tokens"][1] <= cfg["serving_positions"] == 3072
    longest = -(-(mix["prompt_tokens"][1] + mix["output_tokens"][1]) // cfg["cluster"]["gen_page_size"])
    assert mix["clients"] * longest == cfg["cluster"]["gen_num_pages"] == 12288
    assert manifest.plugin("drivers", cfg["driver"]).run
    assert manifest.plugin("reference", cfg["reference"]).check
    assert [e["name"] for e in manifest.wanted(m, CELL, trace=False)] == ["tokens_per_s", "setup_s"]
    specs, readers, kernels = manifest.metric_files(), manifest.plugins("readers"), manifest.plugins("kernels")
    entries = {e["name"]: e for e in manifest.wanted(m, CELL, trace=True)}
    for name in LISTED:
        spec = specs[name]
        assert {k: spec[k] for k in entries[name]} == entries[name]
        assert spec["workloads"] == [CELL] and spec["moves"] == "tokens_per_s"
        assert spec["reader"] in readers, spec["reader"]
        if "kernel" in spec["args"]:
            assert kernels[spec["args"]["kernel"]].EVENTS
    # Every entry the cell reports has its file, and every `.longform` file is listed.
    assert set(entries) <= set(specs)
    assert set(FOLDED) <= set(entries) and all(len(specs[name]["workloads"]) > 1 for name in FOLDED)
    # No recurrent state, and a client's gaps say nothing at a 100 ms poll (PERF.md finding PR 39).
    assert not {"state_mb_mean", "token_gap_ms_p50"} & set(entries)
    assert {name for name in specs if name.endswith(".longform")} <= set(entries)
    # Void since PR 34 (PERF.md Open question 18): not brought to a new cell.
    assert not {"prefill_host_ms.longform", "idle_in_prefill_host_pct.longform"} & set(specs)


def test_the_file_keeps_every_published_width_and_states_the_cut():
    m = manifest.load()
    entry = next(c for c in m["configs"] if c["name"] == "kanana-2-30b-a3b")
    cfg = manifest.read_json(manifest.REPO / entry["file"])
    assert sorted(entry["reduced"]) == ["n_routed_experts", "num_hidden_layers"]
    assert entry["source"] == cfg["source"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"]) == (24, 16)
    d = cfg["deployment"]
    assert d["pipeline_stages"] * d["layers_per_stage"] == 48 and d["expert_parallel"] == 8
    assert (d["stage"], d["rank"], d["experts_held"]) == (0, 0, [0, 16])
    assert d["expert_parallel"] * d["experts_held"][1] == cfg["published"]["n_routed_experts"]
    for key in ("block", "attention", "rotary", "cache", "decode", "router", "experts", "kernels",
                "init", "limits", "gen_num_pages"):
        assert key in cfg["assumed"], key
    assert {"long_contexts", "prefix_reuse", "expert_exchange", "stage_boundary", "scaled_rotary",
            "low_rank_queries"} <= set(cfg["not_built"])
    bias = next(r for r in cfg["init"] if "router/bias" in r["match"])
    assert bias["std"] > 0                                   # choosing by s + b differs from s
    assert cfg["limits"]["default"]["logit_gap_mean"] > 0


def test_the_published_keys_are_the_catalogs():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    assert row["config"] == PUBLISHED and row["source_url"] == manifest.read_json(CONFIG)["source"]


def test_parameter_totals_and_a_steps_bytes_and_operations():
    cut = manifest.read_json(CONFIG)
    assert counts.published_params(cut) == 30_670_815_104
    assert counts.total_params(cut) == 3_155_018_624
    z = counts.sizes(cut)
    assert (z["layers"], z["n_dense"], z["n_moe"], z["experts"], z["held"]) == (24, 1, 23, 128, 16)
    assert counts.attention_params(z) == 26_345_984
    assert counts.mlp_params(z) == 37_748_736 and counts.shared_params(z) == 9_437_184
    assert counts.expert_params(z) == 4_718_592 and counts.router_params(z) == 262_272
    assert counts.attention_params(z) + 4096 + counts.mlp_params(z) == 64_098_816
    whole = counts.sizes(cut, published=True)
    assert (whole["layers"], whole["held"]) == (48, 128)
    assert (counts.attention_params(z) + 4096 + counts.router_params(z) + counts.shared_params(z)
            + 128 * counts.expert_params(z)) == 640_029_312
    # The kernel: 1,152 B and 69,632 FLOP a position a layer (60 FLOP a byte in one pass).
    assert counts.latent_bytes_per_token(cut) == 24 * 1152 == 27_648
    assert counts.kernel_flops_per_position(cut) == 24 * 69_632
    kernel = manifest.plugin("kernels", "mla_decode_attention")
    assert kernel.bytes(cut, 1000) == 27_648_000 and kernel.flops(cut, 1000) == 24 * 69_632_000
    # ISSUE 37's reckoning of a step at 64 residents of 1,730 positions: 5.78 GB of weights
    # with every held expert hit, 3.06 GB of latent cache, 184 GFLOP in the kernel.
    lengths = [1730] * 64
    assert round(counts.step_fixed_bytes(cut, 16) / 1e9, 2) == 5.78
    assert round(sum(lengths) * counts.latent_bytes_per_token(cut) / 1e9, 2) == 3.06
    assert round(kernel.flops(cut, sum(lengths)) / 1e9) == 185
    assert counts.step_bytes(cut, lengths, 16) == pytest.approx(
        counts.step_fixed_bytes(cut, 16) + 64 * 1730 * 27_648)
    assert counts.step_fixed_bytes(cut, 16) - counts.step_fixed_bytes(cut, 15) == 23 * 2 * 4_718_592
    # The routed layers alone: each hit held expert and the router, 23 layers (PR 37.5: 3.11 GB at 14.27).
    assert counts.moe_step_bytes(cut, 14.0) == 2 * 23 * (14 * 4_718_592 + 262_272)
    assert round(counts.moe_step_bytes(cut, 14.27) / 1e9, 2) == 3.11
    # 2 FLOPs a parameter that multiplies, the absorbed form: W_uk and W_uv a head at a time.
    attn = 2048 * 6144 + 2048 * 576 + 2 * 32 * 128 * 512 + 4096 * 2048
    active = 24 * attn + 37_748_736 + 23 * (2048 * 128 + 9_437_184) + 2048 * 128256
    assert counts.decode_token_flops(cut, 0) == 2.0 * active
    assert (counts.decode_token_flops(cut, 1730) - counts.decode_token_flops(cut, 0)
            == 24 * 69_632 * 1730)
    assert counts.expert_pair_flops(cut) == 2.0 * 4_718_592
    assert 4.9e12 < counts.prefill_flops(cut, 2048, 2048 * 6 * 23 // 8) < 5.1e12


def test_the_program_counts_what_the_count_file_counts():
    from benchlib import system

    system.import_program()
    import jax
    import numpy as np

    from dmlc_tpu.models import deepseek_v3 as ds

    cfg = manifest.read_json(CONFIG)
    config = ds.DeepseekV3Config.from_published(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["deployment"]["experts_held"], max_len=cfg["serving_positions"])
    leaves = jax.tree_util.tree_leaves(ds.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == counts.total_params(cfg)
    family = ds.DeepseekV3Family(config, jax.numpy.bfloat16)
    assert family.latent_bytes_per_token == counts.latent_bytes_per_token(cfg)
    attrs = family.work_attrs({"kv_tokens_read": 1000}, 64)
    assert attrs["latent_bytes_read"] == 1000 * counts.latent_bytes_per_token(cfg)


def step_span(t1, hit, pairs=288.0):
    return {"name": "gen/step", "t0": t1 - 0.01, "t1": t1,
            "attrs": {"experts_hit": hit, "expert_pairs": pairs}}


def reader_ctx(tr, records, spans=(), config=None):
    cfg = manifest.read_json(CONFIG) if config is None else config
    return SimpleNamespace(config=cfg, records=records, spans=list(spans), trace=tr, chips=1,
                           peaks=peaks.peaks("TPU v5 lite"), kernels=manifest.plugins("kernels"))


NEW_READERS = [("deepseek_v3_step_mfu", {"pattern": "step"}),
               ("deepseek_v3_step_hbm_roofline", {"pattern": "step"}),
               ("mla_attention_hbm_roofline", {"kernel": "mla_decode_attention"}),
               ("deepseek_v3_moe_hbm_roofline", {"kernel": "deepseek_moe_mixer", "pattern": "step"})]


@pytest.mark.parametrize("reader,args", NEW_READERS)
def test_new_readers_find_nothing_on_an_empty_trace(reader, args):
    empty = trace.DeviceTrace(10.0, 16.0)
    records = [{"prompt": [0] * 100, "token_t": [11.0, 12.0, 13.0]}]
    ctx = reader_ctx(empty, records, [step_span(12.0, 15.0)])
    assert manifest.plugin("readers", reader).read(ctx, **args) is None


@pytest.mark.parametrize("reader,args", NEW_READERS)
def test_new_readers_find_nothing_in_a_program_without_the_family(recorded, reader, args):
    """The recorded excerpt is gpt2-large's: its step holds no latent kernel
    and no expert event, and another model's configuration has no latent to
    count: a parent that serves this cell's siblings reads nothing, not 0."""
    records = [{"prompt": [0] * 299, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}]
    hit = [step_span(recorded.t0 + 1e-3, 15.0)]
    args = {**args, **({"pattern": "jit_step"} if "pattern" in args else {})}
    read = manifest.plugin("readers", reader).read
    other = manifest.read_json(manifest.BENCH / "configs" / "gpt2-large.json")
    assert read(reader_ctx(recorded, records, hit, other), **args) is None
    if "kernel" in args:
        assert read(reader_ctx(recorded, records, hit), **args) is None      # no such event
        assert read(reader_ctx(recorded, records, hit), **{**args, "kernel": "no_such"}) is None


def test_new_step_readers_on_a_recorded_excerpt(recorded):
    """One run of ``jit_step`` (of gpt2-large: only its device time is read):
    64 residents at 1,730 cached positions decode one token each in it."""
    step = recorded.module_runs("jit_step")[0]
    records = [{"prompt": [0] * 1729, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}
               for _ in range(64)]
    ctx = reader_ctx(recorded, records, [step_span(recorded.t0 + 1e-3, 14.0, 280.0),
                                         step_span(recorded.t0 + 2e-3, 16.0, 296.0)])
    mfu = manifest.plugin("readers", "deepseek_v3_step_mfu").read(ctx, pattern="jit_step")
    flops = 64 * counts.decode_token_flops(ctx.config, 1730) + 288 * counts.expert_pair_flops(ctx.config)
    assert mfu == pytest.approx(100.0 * flops / step / 197e12)
    roof = manifest.plugin("readers", "deepseek_v3_step_hbm_roofline").read(ctx, pattern="jit_step")
    assert roof == pytest.approx(100.0 * counts.step_bytes(ctx.config, [1730] * 64, 15.0) / 819e9 / step)
    # The kernel's share over events a stand-in pattern finds in the excerpt.
    stand_in = SimpleNamespace(EVENTS="fusion", bytes=ctx.kernels["mla_decode_attention"].bytes)
    ctx.kernels = {**ctx.kernels, "stand_in": stand_in}
    seconds, events = recorded.op_seconds("fusion")
    assert events
    assert manifest.plugin("readers", "mla_attention_hbm_roofline").read(ctx, kernel="stand_in") == (
        pytest.approx(100.0 * 27_648 * 64 * 1730 / 819e9 / seconds))
    # The routed layers' share over the same events: the step spans' mean hit, once a step run.
    moe = manifest.plugin("readers", "deepseek_v3_moe_hbm_roofline").read(
        ctx, kernel="stand_in", pattern="jit_step")
    assert moe == pytest.approx(100.0 * counts.moe_step_bytes(ctx.config, 15.0) / 819e9 / seconds)


def test_the_expert_layers_events_are_the_decode_steps_alone():
    moe = re.compile(manifest.plugin("kernels", "deepseek_moe_mixer").EVENTS)
    decode = ["%fusion.7 = f32[16,64,1536]{2,1,0} fusion(bf16[64,2048]{1,0} %x, bf16[16,2048,1536]{2,1,0} %w13)",
              "%fusion.8 = bf16[64,2048]{1,0} fusion(f32[16,64,768]{2,1,0} %h, f32[64,17]{1,0} %g)",
              "%scatter.2 = f32[64,17]{1,0} scatter(f32[64,17]{1,0} %z, s32[64,6]{1,0} %i)",
              "%sort.1 = (f32[64,128]{1,0}, s32[64,128]{1,0}) sort(f32[64,128]{1,0} %s, s32[64,128]{1,0} %j)",
              "%convolution.4 = f32[64,128]{1,0} convolution(bf16[64,2048]{1,0} %x, bf16[2048,128]{1,0} %r)"]
    other = ["%ragged-dot-none = f32[12288,1536]{1,0:T(8,128)S(1)} custom-call(s32[1]{0} %n, "
             "bf16[16,2048,1536]{2,1,0} %w13), custom_call_target=\"tpu_custom_call\"",
             "%while.3 = (s32[], bf16[294912,16,640]{2,1,0}, f32[16,64,1536]{2,1,0}) while(s32[] %n)",
             "%fusion.9 = bf16[64,6720]{1,0} fusion(bf16[64,2048]{1,0} %x, bf16[2048,6720]{1,0} %wq)",
             "%fusion.10 = f32[64,128]{1,0} fusion(bf16[64,32,128]{2,1,0} %q)",
             "%fusion.11 = bf16[64,640]{1,0} fusion(bf16[64,2048]{1,0} %x)"]
    assert all(moe.search(name) for name in decode)
    assert not any(moe.search(name) for name in other)


def test_the_kernels_events_match_its_name_and_nothing_else():
    by_name = re.compile(manifest.plugin("kernels", "mla_decode_attention").EVENTS)
    sibling = re.compile(manifest.plugin("kernels", "paged_decode_attention").EVENTS)
    kernel = ("%_paged_latent_decode_attention.3 = f32[2048,512]{1,0:T(8,128)S(1)} custom-call(s32[12288]{0} "
              "%reshape.2, bf16[294912,16,640]{2,1,0} %pool), custom_call_target=\"tpu_custom_call\"")
    kv_form = ("%_paged_decode_attention.3 = f32[2048,64]{1,0:T(8,128)S(1)} custom-call(s32[6144]{0} %reshape.2, "
               "bf16[18432,16,512]{2,1,0} %k), custom_call_target=\"tpu_custom_call\"")
    grouped = ("%ragged-dot-none = f32[12288,1536]{1,0:T(8,128)S(1)} custom-call(s32[1]{0} %n, "
               "bf16[16,2048,1536]{2,1,0} %w13), custom_call_target=\"tpu_custom_call\"")
    wrapper = ("%while.3 = (s32[], bf16[294912,16,640]{2,1,0}, s32[64]{0}, s32[23,16]{1,0}) "
               "while(s32[] %n, bf16[294912,16,640]{2,1,0} %pool)")
    assert by_name.search(kernel)
    assert not any(by_name.search(name) for name in (kv_form, grouped, wrapper))
    assert sibling.search(kv_form) and not sibling.search(kernel)
    assert not by_name.search(
        "%fusion.3 = bf16[64,32,512]{2,1,0} fusion(f32[2048,512]{1,0} %_paged_latent_decode_attention.3)")


def test_deepseek_v3_sound_run_is_correct_and_its_control_is_not():
    judge = load_run().judge
    result = drive("deepseek_v3_tiny.json", "lm_tiny_traffic.json", "deepseek_v3",
                   seed=2**31 + 12, seconds=1.5, control="fp8")
    assert result["failed"] == 0
    assert judge(result["checks"]), result["checks"]
    program, control = result["checks"]["logit_gap_mean"], result["control_checks"]["logit_gap_mean"]
    assert control["value"] > program["limit"] >= program["value"], (control, program)
    assert not judge(result["control_checks"])


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch):
    """The loop calls the halves (PERF.md finding PR 34.6): slot 0's row is
    altered where the loop reads a step's tokens."""
    from benchlib import system

    system.import_program()
    from dmlc_tpu.generate.engine import GenerationEngine

    sound = GenerationEngine.collect_step

    def collect_step(self, run):
        tokens = sound(self, run).copy()
        tokens[0] = (int(tokens[0]) + 1) % self.vocab    # slot 0 streams a token the model did not pick
        return tokens

    monkeypatch.setattr(GenerationEngine, "collect_step", collect_step)
    judge = load_run().judge
    result = drive("deepseek_v3_tiny.json", "lm_tiny_traffic.json", "deepseek_v3",
                   seed=5, seconds=1.5)
    mean = result["checks"]["logit_gap_mean"]
    assert mean["value"] > mean["limit"], mean
    assert not judge(result["checks"]), result["checks"]
