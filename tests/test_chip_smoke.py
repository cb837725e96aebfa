"""chip_smoke.py on the CPU mesh: the script itself must refuse to run here,
and its phases — the same functions the chip run calls — must pass at a tiny
size (tinynet for ResNet-18, lm_small on a small page pool, interpreted
kernels at toy shapes)."""

import json

import jax
import numpy as np

import chip_smoke
import tiny_model  # noqa: F401  (registers "tinynet")
from dmlc_tpu.cluster.localcluster import stop_local_cluster


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert out == ""  # no result line off the chip
    assert "no TPU" in err


def test_result_line_has_exactly_the_contract_keys():
    line = json.loads(chip_smoke.result_line(jax.devices()))
    assert line == {"ok": True, "device": {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices())}}


def test_serve_and_generate_phases_at_tiny_size(tmp_path):
    n = tiny_model.N_CLASSES
    nodes, synsets, data_dir = chip_smoke.start_cluster(
        tmp_path, model="tinynet", n_classes=n, image_size=32,
        gen_model="lm_small", batch_size=8, dispatch_shard_size=16,
        gen_page_size=8, gen_num_pages=64, gen_max_prefill=16,
    )
    try:
        node = nodes[0]
        # Over the RPC surface a CPU node is tellable from a TPU one.
        info = node.rpc.call(node.self_member_addr, "node.info", {}, timeout=10.0)
        assert info["platform"] == "cpu" and info["device_kind"] == "cpu"
        assert info["decode_backend"] in ("native", "pil")

        served = chip_smoke.serve_phase(
            node, model="tinynet", synsets=synsets, data_dir=data_dir,
        )
        assert served["job"] == {
            "finished": n, "total": n, "shards": 3, "failed_shards": 0,
            "correct_vs_labels": served["job"]["correct_vs_labels"],
        }
        assert served["stream_program"]["compared"] + \
            served["stream_program"]["near_ties_skipped"] == n
        assert served["direct_rpc"]["compared"] > 0

        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 1024, k).tolist() for k in (3, 7, 12)]
        generated = chip_smoke.generate_phase(
            node, model="lm_small", prompts=prompts, max_new=[8, 6, 5],
        )
        assert generated["tokens_checked"] == 19
        # One attention on both sides here: every token is the reference's best.
        assert generated["tokens_best_of_reference"] == 19
        assert generated["worst_near_tie"] == 0.0
        assert generated["decode_steps"] < generated["serial_steps"]
        # Off the TPU the engine serves the XLA gather, and says so.
        assert generated["use_pallas"] is False
        engine = node._gen_backends["lm_small"]._scheduler.engine
        assert chip_smoke.MOSAIC_CALL not in chip_smoke.lowered_step_text(engine)
        admitted = chip_smoke.batched_admission(node.config, model="lm_small", prompts=prompts)
        assert admitted["prompts"] == 3 and admitted["steps_compared"] == 4
    finally:
        stop_local_cluster(nodes)


def test_family_phase_at_tiny_size():
    """The rotary family against its plain reference, three slots at different
    lengths in one step: float32 on the CPU, so far inside the chip's bound."""
    from dmlc_tpu.models.lfm2_moe import LFM2_MOE_TINY

    out = chip_smoke.family_phase(LFM2_MOE_TINY, lengths=(3, 17, 29), steps=3)
    assert out["rows_checked"] == 9 and out["use_pallas"] is False
    assert out["worst_rel_err"] < 1e-5


def test_kernels_phase_at_tiny_shapes():
    """The parity harness itself, through the interpreter: every kernel
    case runs and matches its reference — and none claims Mosaic here."""
    shapes = dict(
        chip_smoke.KERNEL_SHAPES, images=(4, 32, 32, 3), logits=(16, 40),
        attn_heads=2, attn_dh=16, s_resident=128, s_streamed=256, sp_s_local=64,
        paged_mha=(4, 4, 8), paged_gqa=(4, 2, 16), paged_mha_wide=(6, 6, 16),
        paged_gqa_64=(8, 2, 8), paged_latent=(4, 128, 32), paged_slots=5, paged_table=3,
    )
    out = chip_smoke.kernels_phase(jax.devices()[:2], shapes)
    assert len(out) == 12
    assert not any(case["mosaic"] for case in out.values())
