"""What PR 31 added for ``olmo-hybrid-7b.briefs``: discovery finds the cell,
its driver, reference, readers and event patterns; the count file's totals
for the published model and for the cut; the file keeps every published
width; the new readers on an empty and on a recorded trace; the event
patterns tell the delta-rule mixer from attention; and the whole driver on
the CPU at a toy size (sound run correct, fp8 control not)."""

import json
import re
from types import SimpleNamespace

import pytest

from benchlib import manifest, olmo_hybrid_counts as counts, peaks, trace
from test_correct import drive, load_run
from test_hybrid import recorded  # noqa: F401  (the recorded excerpt, as a fixture)

CELL = "olmo-hybrid-7b.briefs"
CONFIG = manifest.BENCH / "configs" / "olmo-hybrid-7b.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"

PERIOD = ["linear_attention"] * 3 + ["full_attention"]
#: allenai/Olmo-Hybrid-7B config.json, the keys that say something of its shape.
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
    "layer_types": PERIOD * 8, "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
}


def test_discovery_finds_the_cell_and_everything_it_names():
    m = manifest.load()
    cell = manifest.cell(m, CELL)
    cfg = manifest.config_of(m, cell)
    mix = manifest.traffic_of(cell)
    assert cell["chips"] == 1 and mix["clients"] == cfg["cluster"]["gen_max_slots"] == 32
    assert mix["prompt_tokens"][1] == cfg["cluster"]["gen_max_prefill"]
    assert mix["prompt_tokens"][1] + mix["output_tokens"][1] <= cfg["serving_positions"]
    longest = -(-(mix["prompt_tokens"][1] + mix["output_tokens"][1]) // cfg["cluster"]["gen_page_size"])
    assert mix["clients"] * longest < cfg["cluster"]["gen_num_pages"]
    assert manifest.plugin("drivers", cfg["driver"]).run
    assert manifest.plugin("reference", cfg["reference"]).check
    assert [e["name"] for e in manifest.wanted(m, CELL, trace=False)] == ["tokens_per_s", "setup_s"]
    specs, readers, kernels = manifest.metric_files(), manifest.plugins("readers"), manifest.plugins("kernels")
    wanted = manifest.wanted(m, CELL, trace=True)
    assert len(wanted) == 19 and all(e["name"].endswith(".briefs") for e in wanted)
    for entry in wanted:
        spec = specs[entry["name"]]
        assert {k: spec[k] for k in entry} == entry
        assert spec["reader"] in readers, spec["reader"]
        if "kernel" in spec["args"]:
            assert kernels[spec["args"]["kernel"]].EVENTS


def test_the_file_keeps_every_published_width_and_states_the_cut():
    m = manifest.load()
    entry = next(c for c in m["configs"] if c["name"] == "olmo-hybrid-7b")
    cfg = manifest.read_json(manifest.REPO / entry["file"])
    assert sorted(entry["reduced"]) == ["layer_types", "num_hidden_layers"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["layer_types"] == PERIOD * 4 and cfg["num_hidden_layers"] == 16
    assert cfg["deployment"]["pipeline_stages"] * cfg["deployment"]["layers_per_stage"] == 32
    for key in ("block", "qk_norm", "positions", "output_gate", "beta_alpha", "qk_l2",
                "state_dtype", "chunk", "init", "init_embedding", "limits"):
        assert key in cfg["assumed"], key
    assert "long_contexts" in cfg["not_built"]


def test_the_published_keys_are_the_catalogs():
    try:
        rows = [json.loads(line) for line in open(CATALOG)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in rows if r["name"] == "Olmo-Hybrid-7B")
    assert row["config"] == PUBLISHED and row["source_url"] == manifest.read_json(CONFIG)["source"]


def test_parameter_totals_of_the_published_model_and_of_the_cut():
    assert round(counts.total_params(PUBLISHED) / 1e9, 3) == 7.431
    cut = manifest.read_json(CONFIG)
    assert round(counts.total_params(cut) / 1e9, 3) == 4.101
    assert round(counts.total_params({**cut, "layer_types": PERIOD * 3}) / 1e9, 3) == 3.268
    z = counts.sizes(cut)
    assert round(counts.deltanet_mixer_params(z) / 1e6, 2) == 88.75
    assert round(counts.mlp_params(z) / 1e6, 2) == 126.81
    assert round(counts.linear_layer_params(z) / 1e6, 2) == 215.57
    assert round(counts.full_layer_params(z) / 1e6, 2) == 185.81
    assert counts.state_bytes_per_slot(cut) == 12 * (30 * 192 * 96 * 4 + 11520 * 3 * 2)
    assert counts.kv_bytes_per_token(cut) == 61440
    # ISSUE 31's reckoning of a step at 32 residents of 1,150 positions: 7.4 GB of weights
    # (the embedding is not read whole), 1.75 GB of state, 2.26 GB of KV
    assert 7.3e9 < counts.step_fixed_bytes(cut) < 7.9e9
    assert 11.3e9 < counts.step_bytes(cut, [1150] * 32) < 11.9e9
    assert round(32 * 2 * counts.state_bytes_per_slot(cut) / 1e9, 2) == 1.75
    assert 10.0e12 < counts.prefill_flops(cut, 1536) < 10.6e12
    # 2 FLOPs a parameter that multiplies, plus the delta rule and attention's reads
    assert 7.0e9 < counts.decode_token_flops(cut, 0) < 2 * counts.total_params(cut)


def test_the_program_counts_what_the_count_file_counts():
    from benchlib import system

    system.import_program()
    import jax
    import numpy as np

    from dmlc_tpu.models import olmo_hybrid as oh

    cfg = manifest.read_json(CONFIG)
    config = oh.OlmoHybridConfig.from_published(cfg, max_len=cfg["serving_positions"])
    leaves = jax.tree_util.tree_leaves(oh.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == counts.total_params(cfg)
    family = oh.OlmoHybridFamily(config, jax.numpy.bfloat16)
    assert family.state_bytes_per_slot == counts.state_bytes_per_slot(cfg)


def reader_ctx(tr, records, config=None):
    cfg = manifest.read_json(CONFIG) if config is None else config
    return SimpleNamespace(config=cfg, records=records, spans=[], trace=tr, chips=1,
                           peaks=peaks.peaks("TPU v5 lite"), kernels=manifest.plugins("kernels"))


NEW_READERS = [("olmo_hybrid_step_mfu", {"pattern": "step"}),
               ("olmo_hybrid_step_hbm_roofline", {"pattern": "step"}),
               ("deltanet_hbm_roofline", {"kernel": "deltanet_mixer", "pattern": "step"}),
               ("olmo_hybrid_paged_attention_hbm_roofline", {"kernel": "paged_attention"})]


@pytest.mark.parametrize("reader,args", NEW_READERS)
def test_new_readers_find_nothing_on_an_empty_trace(reader, args):
    empty = trace.DeviceTrace(10.0, 16.0)
    records = [{"prompt": [0] * 100, "token_t": [11.0, 12.0, 13.0]}]
    assert manifest.plugin("readers", reader).read(reader_ctx(empty, records), **args) is None


def test_deltanet_reader_finds_nothing_in_a_program_without_the_mixer(recorded):
    """The recorded excerpt is gpt2-large's: its step holds no delta-rule
    event, and another model's configuration has no linear layers to count."""
    records = [{"prompt": [0] * 299, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}]
    reader = manifest.plugin("readers", "deltanet_hbm_roofline")
    args = {"kernel": "deltanet_mixer", "pattern": "jit_step"}
    assert reader.read(reader_ctx(recorded, records), **args) is None
    other = manifest.read_json(manifest.BENCH / "configs" / "gpt2-large.json")
    assert reader.read(reader_ctx(recorded, records, other), **args) is None
    assert reader.read(reader_ctx(recorded, records), kernel="no_such", pattern="jit_step") is None


def test_new_step_readers_on_a_recorded_excerpt(recorded):
    """One run of ``jit_step`` (of gpt2-large: only its device time is read):
    32 residents at 1,150 cached positions decode one token each in it."""
    step = recorded.module_runs("jit_step")[0]
    records = [{"prompt": [0] * 1149, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}
               for _ in range(32)]
    ctx = reader_ctx(recorded, records)
    mfu = manifest.plugin("readers", "olmo_hybrid_step_mfu").read(ctx, pattern="jit_step")
    assert mfu == pytest.approx(100.0 * 32 * counts.decode_token_flops(ctx.config, 1150) / step / 197e12)
    roof = manifest.plugin("readers", "olmo_hybrid_step_hbm_roofline").read(ctx, pattern="jit_step")
    assert roof == pytest.approx(100.0 * counts.step_bytes(ctx.config, [1150] * 32) / 819e9 / step)
    attn = manifest.plugin("readers", "olmo_hybrid_paged_attention_hbm_roofline")
    seconds, events = recorded.op_seconds(ctx.kernels["paged_attention"].EVENTS)
    if events:   # the excerpt predates the fused kernel: nothing to read, not 0
        assert attn.read(ctx, kernel="paged_attention") == pytest.approx(
            100.0 * 61440 * 32 * 1150 / 819e9 / seconds)
    else:
        assert attn.read(ctx, kernel="paged_attention") is None


def test_event_patterns_tell_the_delta_rule_from_attention_and_the_mlp():
    decode = re.compile(manifest.plugin("kernels", "deltanet_mixer").EVENTS)
    prefill = re.compile(manifest.plugin("kernels", "deltanet_mixer_prefill").EVENTS)
    ours = ["%fusion.10 = (f32[32,30,192,96]{3,2,1,0}, f32[32,30,192,96]{3,2,1,0}) fusion(...)",
            "%fusion.3 = bf16[32,17280]{1,0} fusion(bf16[32,3840]{1,0} %x, bf16[3840,17280]{1,0} %w)",
            "%fusion.9 = bf16[32,3840]{1,0} fusion(f32[32,5760]{1,0} %y, bf16[5760,3840]{1,0} %w)",
            "%fusion.5 = f32[32,60]{1,0} fusion(...)"]
    theirs = ["%fusion.1 = bf16[32,22016]{1,0} fusion(bf16[32,3840]{1,0} %x, bf16[3840,22016]{1,0} %w)",
              "%fusion.2 = bf16[32,3840]{1,0} fusion(bf16[32,3840]{1,0} %x, bf16[3840,3840]{1,0} %w)",
              "%_paged_decode_attention = f32[32,3840]{1,0} custom-call(...tpu_custom_call",
              "%copy-start = (bf16[3840,17280]{1,0}, bf16[3840,17280]{1,0}, u32[]) copy-start(...)",
              "%fusion.7 = bf16[32,100352]{1,0} fusion(...)"]
    assert all(decode.search(name) for name in ours)
    assert not any(decode.search(name) or prefill.search(name) for name in theirs)
    assert prefill.search("%fusion = bf16[1536,17280]{1,0} fusion(...)")
    assert prefill.search("%triangular = f32[24,30,64,288]{3,2,1,0} fusion(...)")
    assert prefill.search("%body = f32[30,192,96]{2,1,0} fusion(f32[30,64,192]{2,1,0} %u)")
    assert not prefill.search("%while.3 = (s32[], f32[30,192,96]{2,1,0}, f32[24,30,64,192]{3,2,1,0}) while(...)")
    assert not prefill.search("%attn = f32[30,1,1536,1536]{3,2,1,0} fusion(bf16[1536,30,128]{2,1,0} %q)")
    assert not prefill.search("%mlp = bf16[1536,22016]{1,0} fusion(bf16[1536,3840]{1,0} %x)")
    assert not any(prefill.search(name) for name in ours)


def test_olmo_hybrid_sound_run_is_correct_and_its_control_is_not():
    judge = load_run().judge
    result = drive("olmo_hybrid_tiny.json", "lm_tiny_traffic.json", "olmo_hybrid",
                   seed=2**31 + 9, seconds=1.5, control="fp8")
    assert result["failed"] == 0
    assert judge(result["checks"]), result["checks"]
    program, control = result["checks"]["logit_gap_mean"], result["control_checks"]["logit_gap_mean"]
    assert control["value"] > program["limit"] >= program["value"], (control, program)
    assert not judge(result["control_checks"])
