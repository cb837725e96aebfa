"""What the cell ``granite-4-h-micro.passages`` brings: discovery finds the
cell, its driver, reference, readers and event pattern; the file keeps every
published key, nothing cut; the count file's totals and a step's bytes
against a reckoning by hand and against the program's own parameter tree
and state; the new readers on an empty trace, on a configuration without
mamba layers, and on a synthetic one; the kernel's pattern tells the mixer
from attention, the MLP and the prefill's loop; and the whole driver on the
CPU at a toy size (sound run correct, fp8 control not)."""

import json
import re
from types import SimpleNamespace

import pytest

from benchlib import granite_hybrid_counts as counts, manifest, peaks, trace
from test_correct import drive, load_run

CELL = "granite-4-h-micro.passages"
CONFIG = manifest.BENCH / "configs" / "granite-4-h-micro.json"

_M, _A = "mamba", "attention"
#: ibm-granite/granite-4.0-h-micro config.json, the keys that say something of its shape.
PUBLISHED = {
    "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 8192,
    "layer_types": ([_M] * 5 + [_A] + [_M] * 4) + ([_M] * 5 + [_A] + [_M] * 4) * 3,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
    "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "shared_intermediate_size": 8192, "tie_word_embeddings": True, "vocab_size": 100352,
}
NEW = ("step_mfu.passages", "step_hbm_roofline.passages", "mamba_ms_per_step.passages",
       "mamba_hbm_roofline.passages")


def test_discovery_finds_the_cell_and_everything_it_names():
    m = manifest.load()
    cell = manifest.cell(m, CELL)
    cfg = manifest.config_of(m, cell)
    mix = manifest.traffic_of(cell)
    assert cell["chips"] == 1 and mix["clients"] == cfg["cluster"]["gen_max_slots"] == 64
    # The mix, to the number.
    assert mix["prompt_tokens"] == [1024, 2048] and mix["output_tokens"] == [384, 639]
    # The pool reaches every output length.
    assert mix["pool"] == 256 == mix["output_tokens"][1] - mix["output_tokens"][0] + 1
    assert (mix["poll_interval_s"], mix["warm_completions"], mix["check_requests"]) == (0.1, 64, 16)
    assert (mix["profile_delay_s"], mix["profile_seconds"], mix["temperature"]) == (2.0, 6.0, 0.0)
    assert mix["prompt_tokens"][1] == cfg["cluster"]["gen_max_prefill"]
    assert mix["prompt_tokens"][1] + mix["output_tokens"][1] < cfg["serving_positions"] == 2688
    longest = -(-(mix["prompt_tokens"][1] + mix["output_tokens"][1]) // cfg["cluster"]["gen_page_size"])
    assert mix["clients"] * longest == cfg["cluster"]["gen_num_pages"] == 10752
    assert manifest.plugin("drivers", cfg["driver"]).run
    assert manifest.plugin("reference", cfg["reference"]).check
    assert [e["name"] for e in manifest.wanted(m, CELL, trace=False)] == ["tokens_per_s", "setup_s"]
    specs, readers, kernels = manifest.metric_files(), manifest.plugins("readers"), manifest.plugins("kernels")
    entries = {e["name"]: e for e in manifest.wanted(m, CELL, trace=True)}
    # The cell reports exactly the four entries it brings (a PR that may only add
    # cannot append it to a folded entry: PERF.md Open question 23a).
    assert sorted(entries) == sorted(NEW) and len(m["per_layer"]) == 87
    for name in NEW:
        spec = specs[name]
        assert {k: spec[k] for k in entries[name]} == entries[name]
        assert spec["workloads"] == [CELL] and spec["moves"] == "tokens_per_s"
        assert spec["reader"] in readers, spec["reader"]
        if "kernel" in spec["args"]:
            assert kernels[spec["args"]["kernel"]].EVENTS


def test_the_file_keeps_every_published_key_and_cuts_nothing():
    m = manifest.load()
    entry = next(c for c in m["configs"] if c["name"] == "granite-4-h-micro")
    cfg = manifest.read_json(manifest.REPO / entry["file"])
    assert entry["reduced"] == [] == cfg["reduced"] and entry["source"] == cfg["source"]
    for key, value in PUBLISHED.items():
        assert cfg[key] == value, key
    assert cfg["deployment"]["pipeline_stages"] == 1 and cfg["deployment"]["chips_per_layer"] == 1
    for key in ("block", "head_dim", "attention", "positions", "mlp", "mamba", "state_layout",
                "init", "init_mamba", "init_embedding", "limits"):
        assert key in cfg["assumed"], key
    for key in ("long_contexts", "prefix_reuse", "experts"):
        assert key in cfg["not_built"], key


def test_counts_against_a_reckoning_by_hand():
    cfg = manifest.read_json(CONFIG)
    z = counts.sizes(cfg)
    assert counts.published_params(cfg) == counts.published_params(PUBLISHED) == 3_191_396_096
    assert counts.mamba_mixer_params(z) == 25_847_232
    assert counts.attention_params(z) == 10_485_760 and counts.mlp_params(z) == 50_331_648
    assert counts.mamba_layer_params(z) == 76_182_976
    assert counts.attention_layer_params(z) == 60_821_504
    assert counts.state_bytes_per_slot(cfg) == 76_437_504
    assert counts.kv_bytes_per_token(cfg) == 8192
    # A step at 64 residents of about 1,800 positions: 6.38 GB of weights, 9.78 GB
    # of state read and written, 0.94 GB of KV, 17.1 GB in all.
    assert round(counts.step_fixed_bytes(cfg) / 1e9, 2) == 6.38
    assert round(counts.step_state_bytes(cfg, 64) / 1e9, 2) == 9.78
    assert round(counts.step_bytes(cfg, 64, [1800] * 64) / 1e9, 1) == 17.1
    # A padded prefill of 2,048 is about 12.6 TFLOP.
    assert 12.4e12 < counts.prefill_flops(cfg, 2048) < 12.7e12
    assert 2 * counts.published_params(cfg) < counts.decode_token_flops(cfg, 0) < 2.1 * counts.published_params(cfg)


def test_the_program_counts_what_the_count_file_counts():
    from benchlib import system

    system.import_program()
    import jax
    import numpy as np

    from dmlc_tpu.models import granite_hybrid as gh

    cfg = manifest.read_json(CONFIG)
    config = gh.GraniteHybridConfig.from_published(cfg, max_len=cfg["serving_positions"])
    leaves = jax.tree_util.tree_leaves(gh.param_shapes(config),
                                       is_leaf=lambda node: isinstance(node, tuple))
    assert sum(int(np.prod(s)) for s in leaves) == counts.published_params(cfg)
    family = gh.GraniteHybridFamily(config, jax.numpy.bfloat16)
    per_slot = sum(np.dtype(dtype).itemsize * int(np.prod(shape[1:]))
                   for shape, dtype in family.state_shapes(1).values())
    assert per_slot == counts.state_bytes_per_slot(cfg)
    assert family.kv_layers * 2 * family.kv_heads * family.head_dim * 2 == counts.kv_bytes_per_token(cfg)


def reader_ctx(tr, records, config=None):
    cfg = manifest.read_json(CONFIG) if config is None else config
    return SimpleNamespace(config=cfg, records=records, spans=[], trace=tr, chips=1,
                           peaks=peaks.peaks("TPU v5 lite"), kernels=manifest.plugins("kernels"))


NEW_READERS = [("granite_hybrid_step_mfu", {"pattern": "step"}),
               ("granite_hybrid_step_hbm_roofline", {"pattern": "step"}),
               ("granite_mamba_hbm_roofline", {"kernel": "granite_mamba_mixer", "pattern": "step"}),
               ("events_ms_per_run", {"kernel": "granite_mamba_mixer", "pattern": "step"})]


@pytest.mark.parametrize("reader,args", NEW_READERS)
def test_new_readers_find_nothing_on_an_empty_trace(reader, args):
    empty = trace.DeviceTrace(10.0, 16.0)
    records = [{"prompt": [0] * 100, "token_t": [11.0, 12.0, 13.0]}]
    assert manifest.plugin("readers", reader).read(reader_ctx(empty, records), **args) is None


def synthetic_step_trace():
    """One device, two runs of ``jit_step`` of 30 ms, each holding a mixer
    event of 12 ms and an MLP event; one prefill loop in between."""
    tr = trace.DeviceTrace(10.0, 11.0)
    dev = "/device:TPU:0"
    tr.modules[dev] = [("jit_step(1)", 10.1, 10.13), ("jit_prefill(2)", 10.2, 10.4),
                       ("jit_step(3)", 10.5, 10.53)]
    tr.devices[dev] = [
        ("fusion.1 | %fusion.1 = f32[64,36,64,64,128]{4,3,2,1,0} fusion(f32[64,36,64,64,128] %p)", 10.1, 10.112),
        ("fusion.2 | %fusion.2 = bf16[64,2048]{1,0} fusion(bf16[64,8192]{1,0} %x, bf16[8192,2048]{1,0} %w)",
         10.112, 10.13),
        ("while.3 | %while.3 = (s32[], f32[64,36,64,64,128]) while(...)", 10.2, 10.4),
        ("fusion.1 | %fusion.1 = f32[64,36,64,64,128]{4,3,2,1,0} fusion(f32[64,36,64,64,128] %p)", 10.5, 10.512),
        ("fusion.2 | %fusion.2 = bf16[64,2048]{1,0} fusion(bf16[64,8192]{1,0} %x, bf16[8192,2048]{1,0} %w)",
         10.512, 10.53),
    ]
    return tr


def test_new_readers_on_a_synthetic_trace():
    """64 residents at 1,800 cached positions decode one token in each of the two steps."""
    tr = synthetic_step_trace()
    records = [{"prompt": [0] * 1799, "token_t": [10.0, 10.14, 10.54]} for _ in range(64)]
    ctx = reader_ctx(tr, records)
    cfg = ctx.config
    read = lambda name, **args: manifest.plugin("readers", name).read(ctx, **args)
    contexts = [1800] * 64 + [1801] * 64
    assert read("granite_hybrid_step_mfu", pattern="step") == pytest.approx(
        100.0 * sum(counts.decode_token_flops(cfg, c) for c in contexts) / 0.06 / 197e12)
    assert read("granite_hybrid_step_hbm_roofline", pattern="step") == pytest.approx(
        100.0 * (2 * counts.step_fixed_bytes(cfg) + 2 * counts.step_state_bytes(cfg, 64)
                 + counts.kv_bytes_per_token(cfg) * sum(contexts)) / 819e9 / 0.06)
    # The mixer's events only (not the MLP's, not the prefill loop's), per step.
    assert read("events_ms_per_run", kernel="granite_mamba_mixer", pattern="step") == pytest.approx(12.0)
    assert read("granite_mamba_hbm_roofline", kernel="granite_mamba_mixer", pattern="step") == pytest.approx(
        100.0 * 2 * counts.step_state_bytes(cfg, 64) / 819e9 / 0.024)
    # Another family's configuration has no mamba layers to count.
    other = manifest.read_json(manifest.BENCH / "configs" / "olmo-hybrid-7b.json")
    other_ctx = reader_ctx(tr, records, other)
    for name, args in NEW_READERS[:3]:
        assert manifest.plugin("readers", name).read(other_ctx, **args) is None


def test_the_pattern_tells_the_mixer_from_attention_the_mlp_and_the_prefill():
    mixer = re.compile(manifest.plugin("kernels", "granite_mamba_mixer").EVENTS)
    ours = ["%fusion.10 = f32[64,36,64,64,128]{4,3,2,1,0} fusion(f32[64,36,64,64,128]{4,3,2,1,0} %p, ...)",
            "%fusion.3 = bf16[64,8512]{1,0} fusion(bf16[64,2048]{1,0} %x, bf16[2048,8512]{1,0} %w)",
            "%fusion.4 = bf16[64,36,3,4352]{3,2,1,0} dynamic-update-slice(...)",
            "%fusion.5 = f32[64,64,64,128]{3,2,1,0} fusion(...)",
            "%fusion.6 = f32[64,4096]{1,0} fusion(f32[64,4096]{1,0} %y, bf16[64,4096]{1,0} %z)",
            "%fusion.9 = bf16[64,2048]{1,0} fusion(bf16[64,4096]{1,0} %y, bf16[4096,2048]{1,0} %w)",
            "%fusion.7 = f32[64,64]{1,0} fusion(bf16[64,8512]{1,0} %dt)"]
    theirs = ["%fusion.1 = bf16[64,16384]{1,0} fusion(bf16[64,2048]{1,0} %x, bf16[2048,16384]{1,0} %w)",
              "%fusion.2 = bf16[64,2048]{1,0} fusion(bf16[64,8192]{1,0} %x, bf16[8192,2048]{1,0} %w)",
              "%fusion.8 = bf16[64,3072]{1,0} fusion(bf16[64,2048]{1,0} %x, bf16[2048,3072]{1,0} %w)",
              "%_paged_decode_attention = f32[64,32,64]{2,1,0} custom-call(...tpu_custom_call",
              "%fusion.11 = f32[64,100352]{1,0} fusion(bf16[64,2048]{1,0} %x, bf16[100352,2048]{1,0} %e)",
              "%while.3 = (s32[], f32[64,36,64,64,128]{4,3,2,1,0}, bf16[64,36,3,4352]{3,2,1,0}) while(...)",
              "%fusion = bf16[2048,8512]{1,0} fusion(bf16[2048,2048]{1,0} %x, bf16[2048,8512]{1,0} %w)",
              # nemotron3-super's mixer state at 64 slots: 128 heads, 8 groups
              "%fusion.12 = f32[64,128,64,128]{3,2,1,0} fusion(...)",
              "%fusion.13 = f32[64,8,16,64,128]{4,3,2,1,0} fusion(...)"]
    assert all(mixer.search(name) for name in ours)
    assert not any(mixer.search(name) for name in theirs)


def test_the_window_credits_each_token_where_it_arrives():
    delivered_work = manifest.plugin("drivers", "granite_hybrid").delivered_work

    def rec(prompt, token_t, error=None):
        return {"prompt": [0] * prompt, "token_t": token_t, "error": error}

    records = [
        rec(100, [1.0, 2.0, 3.0]),            # prefilled before the window: 2 tokens inside
        rec(200, [4.0, 5.0, 11.0, 12.0]),     # prefilled inside, ends after: 200 + 2
        rec(300, [10.0]),                     # first token at the closing instant: 300 + 1
        rec(400, [10.5]),                     # after the window: nothing
        rec(500, [3.0, 4.0], error="RpcError: lost"),  # failed: nothing
        rec(600, []),                         # nothing arrived
    ]
    assert delivered_work(records, 1.5, 10.0) == 2 + 202 + 301
    # A window that holds every arrival credits each request whole, as drivers/lm does.
    whole = [r for r in records if not r["error"] and r["token_t"]]
    assert delivered_work(records, 0.0, 20.0) == sum(len(r["prompt"]) + len(r["token_t"]) for r in whole)


def test_granite_hybrid_sound_run_is_correct_and_its_control_is_not():
    judge = load_run().judge
    result = drive("granite_hybrid_tiny.json", "lm_tiny_traffic.json", "granite_hybrid",
                   seed=2**31 + 9, seconds=1.5, control="fp8")
    assert result["failed"] == 0
    # The rate is the work that arrived inside the window, whole requests or not.
    window = result["readings"]["window"]
    work = result["end_to_end"]["tokens_per_s"] * window.seconds
    assert 0 < work <= sum(len(r["prompt"]) + len(r["token_t"]) for r in result["readings"]["records"])
    assert judge(result["checks"]), result["checks"]
    program, control = result["checks"]["logit_gap_mean"], result["control_checks"]["logit_gap_mean"]
    assert control["value"] > program["limit"] >= program["value"], (control, program)
    assert not judge(result["control_checks"])
