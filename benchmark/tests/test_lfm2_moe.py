"""What PR 33 added for ``lfm2-8b-a1b.replies``: discovery finds the cell,
its driver, reference, readers and event patterns; the count file's totals
for the published model and for the cut; the file keeps every published
width; the new readers on an empty and on a recorded trace; the event
patterns tell the expert layer and the short convolution from attention and
from a prefill's loop wrapper; and the whole driver on the CPU at a toy size
(sound run correct, fp8 control not)."""

import json
import re
from types import SimpleNamespace

import pytest

from benchlib import lfm2_moe_counts as counts, manifest, peaks, trace
from test_correct import drive, load_run
from test_hybrid import recorded  # noqa: F401  (the recorded excerpt, as a fixture)

CELL = "lfm2-8b-a1b.replies"
CONFIG = manifest.BENCH / "configs" / "lfm2-8b-a1b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

PERIOD = ["full_attention", "conv", "conv", "conv"]
LAYER_TYPES = ["conv", "conv"] + PERIOD * 4 + ["full_attention", "conv", "conv"] * 2
#: LiquidAI/LFM2-8B-A1B config.json, the keys that say something of its shape.
PUBLISHED = {
    "model_type": "lfm2_moe", "vocab_size": 65536, "hidden_size": 2048, "intermediate_size": 7168,
    "moe_intermediate_size": 1792, "num_hidden_layers": 24, "layer_types": LAYER_TYPES,
    "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "num_attention_heads": 32,
    "num_key_value_heads": 8, "rope_theta": 1000000, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-05, "max_position_embeddings": 128000,
}


def test_discovery_finds_the_cell_and_everything_it_names():
    m = manifest.load()
    cell = manifest.cell(m, CELL)
    cfg = manifest.config_of(m, cell)
    mix = manifest.traffic_of(cell)
    assert cell["chips"] == 1 and mix["clients"] == cfg["cluster"]["gen_max_slots"] == 64
    assert mix["prompt_tokens"] == [256, 1024] and mix["output_tokens"] == [128, 383]
    assert mix["pool"] == 256 == mix["output_tokens"][1] - mix["output_tokens"][0] + 1
    assert (mix["poll_interval_s"], mix["warm_completions"], mix["check_requests"]) == (0.1, 128, 16)
    assert mix["prompt_tokens"][1] == cfg["cluster"]["gen_max_prefill"]
    assert mix["prompt_tokens"][1] + mix["output_tokens"][1] <= cfg["serving_positions"]
    longest = -(-(mix["prompt_tokens"][1] + mix["output_tokens"][1]) // cfg["cluster"]["gen_page_size"])
    assert mix["clients"] * longest == 5632 < cfg["cluster"]["gen_num_pages"] == 6144
    assert manifest.plugin("drivers", cfg["driver"]).run
    assert manifest.plugin("reference", cfg["reference"]).check
    assert [e["name"] for e in manifest.wanted(m, CELL, trace=False)] == ["tokens_per_s", "setup_s"]
    specs, readers, kernels = manifest.metric_files(), manifest.plugins("readers"), manifest.plugins("kernels")
    wanted = manifest.wanted(m, CELL, trace=True)
    # Names, not a count: folded quantities list this cell beside others (PERF.md finding PR 39).
    names = {e["name"] for e in wanted}
    assert {"step_mfu.replies", "step_hbm_roofline.replies", "moe_ms_per_step.replies",
            "moe_hbm_roofline.replies", "shortconv_ms_per_step.replies",
            "paged_attention_us_per_call", "paged_attention_hbm_roofline.replies",
            "expert_pairs_per_step", "experts_hit_mean", "expert_rows_max_mean",
            "tokens_resident_mean", "step_ms_p50", "device_idle_pct"} <= names
    assert {n for n in specs if n.endswith(".replies")} <= names
    for entry in wanted:
        spec = specs[entry["name"]]
        assert {k: spec[k] for k in entry} == entry
        assert spec["reader"] in readers, spec["reader"]
        if "kernel" in spec["args"]:
            assert kernels[spec["args"]["kernel"]].EVENTS
    shares = {e["name"] for e in wanted if "roofline" in e["name"] or "mfu" in e["name"]}
    assert shares == {"step_mfu.replies", "step_hbm_roofline.replies", "moe_hbm_roofline.replies",
                      "paged_attention_hbm_roofline.replies"}


def test_the_file_keeps_every_published_width_and_states_the_cut():
    m = manifest.load()
    entry = next(c for c in m["configs"] if c["name"] == "lfm2-8b-a1b")
    cfg = manifest.read_json(manifest.REPO / entry["file"])
    assert sorted(entry["reduced"]) == ["layer_types", "num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == LAYER_TYPES[:12] and cfg["num_hidden_layers"] == 12
    assert cfg["layer_types"] == ["conv", "conv"] + PERIOD * 2 + ["full_attention", "conv"]
    assert cfg["deployment"]["pipeline_stages"] * cfg["deployment"]["layers_per_stage"] == 24
    assert cfg["deployment"]["stage"] == 0 and cfg["deployment"]["experts_held"] == [0, 32]
    for key in ("tie_word_embeddings", "head_dim", "rotary", "conv_state", "router", "init",
                "init_expert_bias", "init_embedding", "init_final_norm", "limits"):
        assert key in cfg["assumed"], key
    assert {"long_contexts", "prefix_reuse"} <= set(cfg["not_built"])
    bias = next(r for r in cfg["init"] if "router/bias" in r["match"])
    assert bias["std"] > 0                                   # choosing by s + b differs from s


def test_the_published_keys_are_the_catalogs():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "LFM2-8B-A1B")
    assert row["config"] == PUBLISHED and row["source_url"] == manifest.read_json(CONFIG)["source"]


def test_parameter_totals_of_the_published_model_and_of_the_cut():
    assert counts.total_params(PUBLISHED) == 8_339_930_560
    cut = manifest.read_json(CONFIG)
    assert counts.total_params(cut) == 3_928_728_256
    z = counts.sizes(cut)
    assert (z["n_conv"], z["n_full"], z["n_dense"], z["n_moe"]) == (9, 3, 2, 10)
    assert counts.conv_operator_params(z) == 16_783_360
    assert counts.attention_operator_params(z) == 10_485_888
    assert counts.mlp_params(z) == 44_040_192
    assert counts.expert_params(z) == 11_010_048 and counts.router_params(z) == 65_568
    assert counts.conv_operator_params(z) + counts.mlp_params(z) + 4096 == 60_827_648
    assert counts.conv_operator_params(z) + counts.moe_params(z) + 4096 == 369_174_560
    assert counts.attention_operator_params(z) + counts.moe_params(z) + 4096 == 362_877_088
    assert counts.state_bytes_per_slot(cut) == 9 * 8192 and counts.kv_bytes_per_token(cut) == 6144
    # ISSUE 33's reckoning of a step at 64 residents of 900 positions: 7.86 GB of weights of
    # which the experts are 7.05 GB, 0.35 GB of KV, a few MB of conv state
    assert round(counts.expert_bytes(cut) / 1e9, 2) == 7.05
    assert round(counts.step_fixed_bytes(cut, 32) / 1e9, 2) == 7.86
    assert round(64 * 900 * counts.kv_bytes_per_token(cut) / 1e9, 2) == 0.35
    assert counts.step_bytes(cut, [900] * 64, 32) == pytest.approx(
        counts.step_fixed_bytes(cut, 32) + 64 * (2 * 73728 + 900 * 6144))
    assert counts.moe_step_bytes(cut, 32) == pytest.approx(counts.expert_bytes(cut) + 10 * 2 * 65_568)
    assert counts.moe_step_bytes(cut, 16) < 0.51 * counts.moe_step_bytes(cut, 32)
    # 2 FLOPs a parameter that multiplies: 4 of 32 experts a token, the head once
    active = (9 * counts.conv_matrix_params(z) + 3 * counts.attention_matrix_params(z)
              + 2 * counts.mlp_params(z) + 10 * (4 * counts.expert_params(z) + 65_536) + 65536 * 2048)
    assert counts.decode_token_flops(cut, 0) == 2.0 * active
    assert counts.decode_token_flops(cut, 900) - counts.decode_token_flops(cut, 0) == 3 * 4 * 900 * 2048
    assert 1.4e12 < counts.prefill_flops(cut, 1024) < 1.6e12


def test_the_program_counts_what_the_count_file_counts():
    from benchlib import system

    system.import_program()
    import jax
    import numpy as np

    from dmlc_tpu.models import lfm2_moe as lf

    cfg = manifest.read_json(CONFIG)
    config = lf.Lfm2MoeConfig.from_published(cfg, max_len=cfg["serving_positions"])
    leaves = jax.tree_util.tree_leaves(lf.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == counts.total_params(cfg)
    family = lf.Lfm2MoeFamily(config, jax.numpy.bfloat16)
    assert family.state_bytes_per_slot == counts.state_bytes_per_slot(cfg)
    assert family.kv_layers * 2 * family.kv_heads * family.head_dim * 2 == counts.kv_bytes_per_token(cfg)


def step_span(t1, hit):
    return {"name": "gen/step", "t0": t1 - 0.01, "t1": t1, "attrs": {"experts_hit": hit}}


def reader_ctx(tr, records, spans=(), config=None):
    cfg = manifest.read_json(CONFIG) if config is None else config
    return SimpleNamespace(config=cfg, records=records, spans=list(spans), trace=tr, chips=1,
                           peaks=peaks.peaks("TPU v5 lite"), kernels=manifest.plugins("kernels"))


NEW_READERS = [("lfm2_moe_step_mfu", {"pattern": "step"}),
               ("lfm2_moe_step_hbm_roofline", {"pattern": "step"}),
               ("gated_moe_hbm_roofline", {"kernel": "gated_moe_mixer", "pattern": "step"}),
               ("lfm2_moe_paged_attention_hbm_roofline", {"kernel": "paged_decode_attention"})]


@pytest.mark.parametrize("reader,args", NEW_READERS)
def test_new_readers_find_nothing_on_an_empty_trace(reader, args):
    empty = trace.DeviceTrace(10.0, 16.0)
    records = [{"prompt": [0] * 100, "token_t": [11.0, 12.0, 13.0]}]
    ctx = reader_ctx(empty, records, [step_span(12.0, 31.0)])
    assert manifest.plugin("readers", reader).read(ctx, **args) is None


def test_expert_readers_find_nothing_in_a_program_without_the_layer(recorded):
    """The recorded excerpt is gpt2-large's: its step holds no expert event,
    its spans carry no ``experts_hit``, and another model's configuration has
    no experts to count."""
    records = [{"prompt": [0] * 299, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}]
    reader = manifest.plugin("readers", "gated_moe_hbm_roofline")
    args = {"kernel": "gated_moe_mixer", "pattern": "jit_step"}
    hit = [step_span(recorded.t0 + 1e-3, 32.0)]
    assert reader.read(reader_ctx(recorded, records, hit), **args) is None      # no such event
    other = manifest.read_json(manifest.BENCH / "configs" / "gpt2-large.json")
    assert reader.read(reader_ctx(recorded, records, hit, other), **args) is None
    assert reader.read(reader_ctx(recorded, records, hit), kernel="no_such", pattern="jit_step") is None
    whole = manifest.plugin("readers", "lfm2_moe_step_hbm_roofline")
    assert whole.read(reader_ctx(recorded, records), pattern="jit_step") is None   # no experts_hit


def test_new_step_readers_on_a_recorded_excerpt(recorded):
    """One run of ``jit_step`` (of gpt2-large: only its device time is read):
    64 residents at 900 cached positions decode one token each in it."""
    step = recorded.module_runs("jit_step")[0]
    records = [{"prompt": [0] * 899, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}
               for _ in range(64)]
    ctx = reader_ctx(recorded, records, [step_span(recorded.t0 + 1e-3, 30.0),
                                         step_span(recorded.t0 + 2e-3, 32.0)])
    mfu = manifest.plugin("readers", "lfm2_moe_step_mfu").read(ctx, pattern="jit_step")
    assert mfu == pytest.approx(100.0 * 64 * counts.decode_token_flops(ctx.config, 900) / step / 197e12)
    roof = manifest.plugin("readers", "lfm2_moe_step_hbm_roofline").read(ctx, pattern="jit_step")
    assert roof == pytest.approx(100.0 * counts.step_bytes(ctx.config, [900] * 64, 31.0) / 819e9 / step)
    attn = manifest.plugin("readers", "lfm2_moe_paged_attention_hbm_roofline")
    seconds, events = recorded.op_seconds(ctx.kernels["paged_decode_attention"].EVENTS)
    if events:   # the excerpt predates the fused kernel: nothing to read, not 0
        assert attn.read(ctx, kernel="paged_decode_attention") == pytest.approx(
            100.0 * 6144 * 64 * 900 / 819e9 / seconds)
    else:
        assert attn.read(ctx, kernel="paged_decode_attention") is None


def test_event_patterns_tell_the_expert_layer_and_the_conv_from_the_rest():
    moe = re.compile(manifest.plugin("kernels", "gated_moe_mixer").EVENTS)
    conv = re.compile(manifest.plugin("kernels", "shortconv_mixer").EVENTS)
    experts = [
        "%convolution_bitcast_fusion.9 = f32[32,64,3584]{2,1,0} fusion(bf16[32,2048,3584]{2,1,0} %w13, bf16[64,2048]{1,0} %x)",
        "%fusion.58 = bf16[64,2048]{1,0} fusion(bf16[32,1792,2048]{2,1,0} %w2, f32[64,32]{1,0} %slice.103, f32[32,64,3584]{2,1,0} %h)",
        "%broadcast_add_fusion.8 = (f32[64,32]{1,0}, f32[64,32]{1,0}) fusion(f32[32]{0} %b, bf16[2048,32]{1,0} %router, bf16[64,2048]{1,0} %x)",
        "%sort.1 = (f32[64,32]{1,0}, s32[64,32]{1,0}) sort(f32[64,32]{1,0} %s, s32[64,32]{1,0} %iota.1)",
        "%fusion.14 = f32[64,33]{0,1} fusion(f32[64,33]{0,1} %zeros, s32[256]{0} %key, f32[256]{0} %gates)",
        "%copy.298 = f32[64,4]{1,0} copy(f32[64,4]{0,1} %reshape.596)"]
    convs = [
        "%fusion.283 = bf16[64,6144]{1,0} fusion(bf16[2048,6144]{1,0} %custom-call.69, bf16[64,2048]{1,0} %x)",
        "%fusion.301 = bf16[64,1,2048]{2,1,0} fusion(bf16[64,6144]{1,0} %fusion.283)",
        "%fusion.143 = (f32[64]{0}, bf16[64,2048]{1,0}) fusion(bf16[64,2048]{1,0} %x, bf16[2048,2048]{1,0} %w, bf16[64,1,2048]{2,1,0} %z)",
        "%broadcast_select_fusion.1 = (bf16[64,2,2048]{2,1,0}, bf16[64,2,2048]{2,1,0}) fusion(pred[64]{0} %active)"]
    theirs = [
        "%multiply_reduce_fusion.2 = f32[64,32]{1,0} fusion(f32[64,32,64]{2,1,0} %reshape.278)",     # q norm
        "%fusion.7 = f32[64,32]{1,0} fusion(s32[64]{0} %lengths)",                                   # rotary table
        "%fusion.20 = bf16[64,3072]{1,0} fusion(bf16[2048,3072]{1,0} %qkv, bf16[64,2048]{1,0} %x)",
        "%_paged_decode_attention = f32[256,64]{1,0} custom-call(...tpu_custom_call",
        "%fusion.3 = bf16[64,14336]{1,0} fusion(bf16[2048,14336]{1,0} %gate_up, bf16[64,2048]{1,0} %x)",
        "%fusion.9 = bf16[64,65536]{1,0} fusion(bf16[65536,2048]{1,0} %embedding, bf16[64,2048]{1,0} %x)",
        "%ragged-dot.1 = f32[4096,3584]{1,0} ragged-dot(bf16[4096,2048]{1,0} %rows, bf16[32,2048,3584]{2,1,0} %w13)",
        "%fusion.30 = bf16[1024,6144]{1,0} fusion(bf16[2048,6144]{1,0} %in_proj, bf16[1024,2048]{1,0} %x)",
        "%fusion.31 = (f32[1024,32]{1,0}, f32[1024,32]{1,0}) fusion(f32[32]{0} %b, bf16[2048,32]{1,0} %router)"]
    wrapper = ("%while.3 = (s32[], bf16[18432,16,512]{2,1,0}, bf16[64,2,2048]{2,1,0}, s32[10,32]{1,0}) "
               "while(s32[] %n, bf16[64,2,2048]{2,1,0} %state)")
    assert all(moe.search(name) for name in experts)
    assert all(conv.search(name) for name in convs)
    assert not any(moe.search(name) or conv.search(name) for name in theirs)
    assert not any(moe.search(name) for name in convs) and not any(conv.search(name) for name in experts)
    assert not moe.search(wrapper) and not conv.search(wrapper)
    assert conv.search(wrapper.replace("%while.3", "%fusion.3").replace("while(", "fusion("))


def test_the_attention_kernel_is_told_from_the_prefills_grouped_products_by_name():
    """Both are Mosaic calls with a rank-2 result: the pattern by rank that
    ``kernels/paged_attention.py`` held until PR 39 takes both, the one every
    cell's metrics read since then takes the kernel alone."""
    by_rank = re.compile(r"= \w+\[\d+,\d+\]\S* custom-call\(.*tpu_custom_call")
    by_name = re.compile(manifest.plugin("kernels", "paged_decode_attention").EVENTS)
    kernel = ("%_paged_decode_attention.3 = f32[2048,64]{1,0:T(8,128)S(1)} custom-call(s32[6144]{0} %reshape.2, "
              "bf16[18432,16,512]{2,1,0} %k), custom_call_target=\"tpu_custom_call\"")
    grouped = ("%ragged-dot-none = f32[4096,3584]{1,0:T(8,128)S(1)} custom-call(s32[1]{0} %n, "
               "bf16[32,2048,3584]{2,1,0} %w13), custom_call_target=\"tpu_custom_call\"")
    assert by_rank.search(kernel) and by_rank.search(grouped)
    assert by_name.search(kernel) and not by_name.search(grouped)
    assert not by_name.search("%fusion.3 = f32[2048,64]{1,0} fusion(f32[2048,64]{1,0} %_paged_decode_attention.3)")


def test_lfm2_moe_sound_run_is_correct_and_its_control_is_not():
    judge = load_run().judge
    result = drive("lfm2_moe_tiny.json", "lm_tiny_traffic.json", "lfm2_moe",
                   seed=2**31 + 12, seconds=1.5, control="fp8")
    assert result["failed"] == 0
    assert judge(result["checks"]), result["checks"]
    program, control = result["checks"]["logit_gap_mean"], result["control_checks"]["logit_gap_mean"]
    assert control["value"] > program["limit"] >= program["value"], (control, program)
    assert not judge(result["control_checks"])
