"""What PR 27 added for ``nemotron3-super.answers``: discovery finds the
cell, its driver, reference, readers and kernel patterns; the count file's
parameter totals; the two NEW roofline readers on an empty and on a recorded
trace; and the whole driver on the CPU at a toy size (sound run correct, fp8
control not)."""

import time
from types import SimpleNamespace

import pytest

from benchlib import hybrid_counts, manifest, peaks, trace
from test_correct import drive, load_run

CELL = "nemotron3-super.answers"
RECORDED = manifest.BENCH / "tests" / "data" / "trace_excerpt.json.gz"

#: The published keys the count needs (NVIDIA-Nemotron-3-Super-120B-A12B-BF16 config.json).
PUBLISHED = {
    "hybrid_override_pattern": "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEM*EMEMEMEME",
    "hidden_size": 4096, "vocab_size": 131072, "num_attention_heads": 32,
    "num_key_value_heads": 2, "head_dim": 128, "mamba_num_heads": 128, "mamba_head_dim": 64,
    "n_groups": 8, "ssm_state_size": 128, "conv_kernel": 4, "n_routed_experts": 512,
    "num_experts_per_tok": 22, "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376,
}


def test_discovery_finds_the_cell_and_everything_it_names():
    m = manifest.load()
    cell = manifest.cell(m, CELL)
    cfg = manifest.config_of(m, cell)
    assert cell["chips"] == 1 and manifest.traffic_of(cell)["clients"] == cfg["cluster"]["gen_max_slots"]
    assert manifest.plugin("drivers", cfg["driver"]).run
    assert manifest.plugin("reference", cfg["reference"]).check
    assert [e["name"] for e in manifest.wanted(m, CELL, trace=False)] == ["tokens_per_s", "setup_s"]
    specs, readers, kernels = manifest.metric_files(), manifest.plugins("readers"), manifest.plugins("kernels")
    wanted = manifest.wanted(m, CELL, trace=True)
    # Names, not a count: folded quantities list this cell beside others (PERF.md finding PR 39).
    names = {e["name"] for e in wanted}
    assert {"step_mfu.answers", "step_hbm_roofline.answers", "moe_ms_per_step.answers",
            "mamba_ms_per_step.answers", "expert_pairs_per_step", "experts_hit_mean",
            "expert_rows_max_mean", "state_mb_mean", "step_ms_p50", "device_idle_pct"} <= names
    assert {n for n in specs if n.endswith(".answers")} <= names
    for entry in wanted:
        spec = specs[entry["name"]]
        assert {k: spec[k] for k in entry} == entry
        assert spec["reader"] in readers, spec["reader"]
        if "kernel" in spec["args"]:
            assert kernels[spec["args"]["kernel"]].EVENTS


def test_the_file_states_the_cut_beside_the_published_values():
    m = manifest.load()
    entry = next(c for c in m["configs"] if c["name"] == "nemotron3-super")
    cfg = manifest.read_json(manifest.REPO / entry["file"])
    assert sorted(entry["reduced"]) == ["hybrid_override_pattern", "n_routed_experts", "vocab_size"]
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert PUBLISHED["hybrid_override_pattern"].startswith(cfg["hybrid_override_pattern"])
    assert cfg["deployment"]["experts_held"] == [0, cfg["n_routed_experts"]]
    assert "multi_token_prediction" in cfg["not_built"]


def test_parameter_totals_of_the_published_model_and_of_the_cut():
    assert round(hybrid_counts.total_params(PUBLISHED) / 1e9, 2) == 120.67
    cut = manifest.read_json(manifest.BENCH / "configs" / "nemotron3-super.json")
    assert round(hybrid_counts.total_params(cut) / 1e9, 3) == 4.648
    z = hybrid_counts.sizes(cut)
    assert round(hybrid_counts.mamba_layer_params(z) / 1e6, 2) == 109.64
    assert round(hybrid_counts.attention_layer_params(z) / 1e6, 2) == 35.66
    assert round(hybrid_counts.expert_params(z) / 1e6, 3) == 5.505
    assert round(hybrid_counts.moe_dense_params(z) / 1e6, 2) == 54.53
    assert hybrid_counts.state_bytes_per_slot(cut) == 5 * (128 * 64 * 128 * 4 + 10240 * 3 * 2)
    assert hybrid_counts.kv_bytes_per_token(cut) == 1024
    # 12.8 B active per token in the published model: 2 FLOPs a parameter, experts at top 22 of 512
    active = hybrid_counts.decode_token_flops({**PUBLISHED}, 0) / 2 - 6 * 8192 * 128 * 40 / 2
    assert 12.0e9 < active + PUBLISHED["vocab_size"] * 4096 < 13.5e9


@pytest.fixture(scope="module")
def recorded():
    planes = trace.read_planes(str(RECORDED))
    lines = dict(next(lines for name, lines in planes if name.startswith("/device:")))
    sync_end = next(s + d for n, s, d, _ in lines[trace.MODULE_LINE] if n.startswith(trace.SYNC_NAME))
    to_perf = lambda ns: 100.0 + (ns - sync_end) * 1e-9
    both = lines[trace.OP_LINE] + [m for m in lines[trace.MODULE_LINE]
                                   if not m[0].startswith(trace.SYNC_NAME)]
    first, last = min(e[1] for e in both) - 1e3, max(e[1] + e[2] for e in both) + 1e3
    return trace.load(str(RECORDED), 100.0, to_perf(first), to_perf(last))


def reader_ctx(tr, records, spans):
    cfg = manifest.read_json(manifest.BENCH / "configs" / "nemotron3-super.json")
    return SimpleNamespace(config=cfg, records=records, spans=spans, trace=tr, chips=1,
                           peaks=peaks.peaks("TPU v5 lite"), kernels=manifest.plugins("kernels"))


def test_new_roofline_readers_find_nothing_on_an_empty_trace():
    empty = trace.DeviceTrace(10.0, 16.0)
    records = [{"prompt": [0] * 100, "token_t": [11.0, 12.0, 13.0]}]
    spans = [{"name": "gen/step", "t0": 11.5, "t1": 12.0, "attrs": {"experts_hit": 120.0}}]
    ctx = reader_ctx(empty, records, spans)
    assert manifest.plugin("readers", "hybrid_step_mfu").read(ctx, pattern="step") is None
    assert manifest.plugin("readers", "hybrid_step_hbm_roofline").read(ctx, pattern="step") is None
    assert manifest.plugin("readers", "events_ms_per_run").read(ctx, kernel="moe_mixer", pattern="step") is None
    assert manifest.plugin("readers", "events_ms_per_run").read(ctx, kernel="no_such", pattern="step") is None


def test_new_roofline_readers_on_a_recorded_excerpt(recorded):
    """The recorded excerpt holds one run of ``jit_step`` (of gpt2-large: only
    its device time is read). 64 residents at 300 cached positions decode one
    token each in it; the steps' spans say 120.5 experts were hit."""
    step = recorded.module_runs("jit_step")[0]
    records = [{"prompt": [0] * 299, "token_t": [recorded.t0 - 1.0, recorded.t0 + 1e-3]}
               for _ in range(64)]
    spans = [{"name": "gen/step", "t0": recorded.t0, "t1": recorded.t0 + 0.01,
              "attrs": {"experts_hit": 120.5}}]
    ctx = reader_ctx(recorded, records, spans)
    cfg = ctx.config
    mfu = manifest.plugin("readers", "hybrid_step_mfu").read(ctx, pattern="jit_step")
    assert mfu == pytest.approx(100.0 * 64 * hybrid_counts.decode_token_flops(cfg, 300) / step / 197e12)
    roof = manifest.plugin("readers", "hybrid_step_hbm_roofline").read(ctx, pattern="jit_step")
    needed = hybrid_counts.step_bytes(cfg, [300] * 64, 120.5)
    assert roof == pytest.approx(100.0 * needed / 819e9 / step)
    # the bytes of ISSUE 27's reckoning: 9.03 GB of weights less the experts not hit, 2 x 1.36 GB of state
    assert 11.0e9 < needed < 11.8e9
    assert 2.2e9 < hybrid_counts.decode_token_flops(cfg, 300) < 2.5e9
    # no span carries the attribute (the parent program): nothing to read, not 0
    ctx.spans = [{"name": "gen/step", "t0": recorded.t0, "t1": recorded.t0 + 0.01, "attrs": {}}]
    assert manifest.plugin("readers", "hybrid_step_hbm_roofline").read(ctx, pattern="jit_step") is None


def test_mixer_patterns_tell_the_mixers_apart():
    import re

    moe = re.compile(manifest.plugin("kernels", "moe_mixer").EVENTS)
    mamba = re.compile(manifest.plugin("kernels", "mamba_mixer").EVENTS)
    assert moe.search("%ragged-dot-none.3 = f32[1408,2688]{1,0} custom-call(...)")
    assert mamba.search("%fusion.12 = f32[64,128,64,128]{3,2,1,0} fusion(...)")
    assert not moe.search("%fusion.12 = f32[64,128,64,128]{3,2,1,0} fusion(...)")
    assert not mamba.search("%ragged-dot-none.3 = f32[1408,2688]{1,0} custom-call(...)")
    assert not moe.search("%x = f32[11264,2688]{1,0} fusion(...)")     # a prefill's rows


def test_hybrid_sound_run_is_correct_and_its_control_is_not():
    judge = load_run().judge
    result = drive("hybrid_tiny.json", "lm_tiny_traffic.json", "lm_hybrid", seed=2**31 + 5,
                   seconds=1.5, control="fp8")
    assert result["failed"] == 0
    assert judge(result["checks"]), result["checks"]
    program, control = result["checks"]["logit_gap_mean"], result["control_checks"]["logit_gap_mean"]
    assert control["value"] > program["limit"] >= program["value"], (control, program)
    assert not judge(result["control_checks"])
