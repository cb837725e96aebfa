"""The prefill's grouped expert products: their events' pattern (the parent's
``ragged_dot`` call and the Pallas kernel that replaced it, nothing else),
the per-layer held weights from the three configurations whose prefill runs
them, and the two readers on an empty and on a recorded trace."""

import re
from types import SimpleNamespace

import pytest

from benchlib import manifest, peaks, trace
from test_hybrid import recorded  # noqa: F401  (the recorded excerpt, as a fixture)

KERNEL = "grouped_expert_matmul"

PRODUCTS = [
    "%ragged-dot-none = f32[11264,2688]{1,0:T(8,128)S(1)} custom-call(s32[1]{0} %n, "
    "bf16[11264,1024]{1,0} %rows, bf16[128,1024,2688]{2,1,0} %w1), "
    "custom_call_target=\"tpu_custom_call\"",
    "%ragged-dot-none.1 = f32[4096,2048]{1,0:T(8,128)} custom-call(s32[1]{0} %n, "
    "bf16[4096,1792]{1,0} %h, bf16[32,1792,2048]{2,1,0} %w2), custom_call_target=\"tpu_custom_call\"",
    "%_grouped_expert_matmul.1 = f32[11264,2688]{1,0:T(8,128)} custom-call(s32[] %visits, "
    "s32[215]{0} %group, bf16[11264,1024]{1,0} %rows, bf16[128,1024,2688]{2,1,0} %w1), "
    "custom_call_target=\"tpu_custom_call\"",
    "_grouped_expert_matmul = f32[12288,1536]{1,0} custom-call(s32[] %visits)",
]
OTHERS = [
    "%while.3 = (s32[], f32[11264,2688]{1,0}, bf16[128,1024,2688]{2,1,0}) while(s32[] %n, "
    "f32[11264,2688]{1,0} %_grouped_expert_matmul.1)",
    "%while.7 = (s32[], f32[4096,3584]{1,0}) while((s32[], f32[4096,3584]{1,0}) %ragged-dot-none)",
    "%_paged_decode_attention.3 = f32[2048,64]{1,0:T(8,128)S(1)} custom-call(s32[6144]{0} %table, "
    "bf16[18432,16,512]{2,1,0} %k), custom_call_target=\"tpu_custom_call\"",
    "%_paged_latent_decode_attention.3 = f32[2048,512]{1,0:T(8,128)S(1)} custom-call(s32[12288]{0} "
    "%t, bf16[294912,16,640]{2,1,0} %pool), custom_call_target=\"tpu_custom_call\"",
    "%fusion.12 = bf16[64,1024]{1,0} fusion(f32[128,64,2688]{2,1,0} %h, f32[64,128]{1,0} %g)",
    "%convolution_bitcast_fusion = f32[32,64,3584]{2,1,0} fusion(bf16[64,2048]{1,0} %x, "
    "bf16[32,2048,3584]{2,1,0} %w13)",
    "%fusion.7 = f32[16,64,1536]{2,1,0} fusion(bf16[64,2048]{1,0} %x, bf16[16,2048,1536]{2,1,0} %w13)",
    "%fusion.3 = bf16[11264,2688]{1,0} fusion(f32[11264,2688]{1,0} %_grouped_expert_matmul.1)",
]


def test_the_pattern_takes_both_sides_products_and_nothing_else():
    events = re.compile(manifest.plugin("kernels", KERNEL).EVENTS)
    assert all(events.search(name) for name in PRODUCTS)
    assert not any(events.search(name) for name in OTHERS)


@pytest.mark.parametrize("config,held_bytes", [
    ("nemotron3-super", 1_409_286_144),       # 128 x 2 x 1,024 x 2,688 x 2 B
    ("lfm2-8b-a1b", 704_643_072),             # 32 x 3 x 2,048 x 1,792 x 2 B
    ("kanana-2-30b-a3b", 150_994_944),        # 16 x 3 x 2,048 x 768 x 2 B
    ("gpt2-large", None), ("olmo-hybrid-7b", None), ("granite-4-h-micro", None),
])
def test_one_layers_held_weights_from_the_configuration(config, held_bytes):
    cfg = manifest.read_json(manifest.BENCH / "configs" / f"{config}.json")
    assert manifest.plugin("kernels", KERNEL).layer_bytes(cfg) == held_bytes


def _ctx(tr, config="nemotron3-super", kernels=None):
    return SimpleNamespace(config=manifest.read_json(manifest.BENCH / "configs" / f"{config}.json"),
                           trace=tr, peaks=peaks.peaks("TPU v5 lite"), chips=1,
                           kernels=kernels or manifest.plugins("kernels"))


@pytest.mark.parametrize("reader", ["kernel_us_per_call", "grouped_expert_hbm_roofline"])
def test_the_readers_find_nothing_without_the_products(recorded, reader):  # noqa: F811
    """An empty trace, and gpt2-large's recorded step and prefill (no grouped
    product), read nothing; so does a configuration without held experts."""
    read = manifest.plugin("readers", reader).read
    assert read(_ctx(trace.DeviceTrace(10.0, 16.0)), kernel=KERNEL) is None
    assert read(_ctx(recorded), kernel=KERNEL) is None
    assert read(_ctx(recorded), kernel="no_such") is None


def test_the_share_counts_one_layers_held_weights_every_two_events(recorded):  # noqa: F811
    """Over events a stand-in pattern finds in the recorded excerpt: the held
    weights of (events / 2) layers over their summed time and 819 GB/s; a
    configuration without held experts reads nothing over the same events."""
    module = manifest.plugin("kernels", KERNEL)
    stand_in = SimpleNamespace(EVENTS="fusion", layer_bytes=module.layer_bytes)
    kernels = {**manifest.plugins("kernels"), "stand_in": stand_in}
    seconds, events = recorded.op_seconds("fusion")
    assert events >= 2
    read = manifest.plugin("readers", "grouped_expert_hbm_roofline").read
    share = read(_ctx(recorded, kernels=kernels), kernel="stand_in")
    assert share == pytest.approx(100.0 * events / 2 * 1_409_286_144 / 819e9 / seconds)
    assert read(_ctx(recorded, "gpt2-large", kernels), kernel="stand_in") is None
