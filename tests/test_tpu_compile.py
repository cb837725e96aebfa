"""The generation engine's two programs compiled for a DESCRIBED v5e (the
chip's own compiler, no chip): both update the donated KV pools in place.

``chip_smoke.pool_memory`` is what the chip run itself checks, here at
gpt2-large's rows (20 heads x 64 = 1,280 wide, pages of 16, 24 slots, prompts
padded to 640) with two layers and, abstractly, 16,384 pages: one pool
(1.34 GB) outweighs what a step's attention keeps beside it, and a layout the
compiler re-lays around the writes (a trailing [20, 64], PERF.md PR 24
finding 2) shows as temporaries of several pools. Nothing runs and nothing
here is a time. Every test of this kind lives in this one file, and the
topology is described inside a fixture: one process at a time may load the
TPU's library.

The second test is the hybrid family's geometry (``models/olmo_hybrid`` at its
published widths, two periods of four layers): pool rows of 30 heads x 128 =
3,840 lanes, three times gpt2-large's, through the same attention kernel at
its 256-position chunk, beside a float32 matrix state of 2.2 MB a slot a
layer that both programs must also update in place. Its prefill runs the
family's chunk scans INSIDE the engine's loop over the admitted prompts: the
temporaries stay what the batch-1 program kept (501 MB at the two periods here,
620 at the cell's four against that program's 619; 3,772 before the family was
handed a state of one slot and the loop's body a depth-first order).

The third is ``models/lfm2_moe`` at the sizes of its cell (the configuration
file itself: 12 layers, the whole vocabulary, 64 slots, 6,144 pages, prompts
padded to 1,024): pool rows of 8 KV heads x 64 = 512 lanes under 32 query
heads, conv windows of 8 KB a layer a slot, and an expert layer whose dense
form keeps a float32 ``[32, 64, 3584]`` product beside 7 GB of experts.

The fourth is ``models/deepseek_v3`` at the sizes of ITS cell (24 layers, the
whole vocabulary, 64 slots, 12,288 pages, prompts padded to 2,048): ONE pool of
640-lane rows (6.04 GB) aliased, no second pool anywhere in either program,
the latent kernel through Mosaic 24 times a step, and beside 6.31 GB of
weights temporaries of 46 MB a step and 411 MB a prefill. A 576-lane row is
refused by the chip's compiler (and would occupy 640 lanes all the same).

The fifth is ``models/granite_hybrid`` at ITS cell's sizes (the whole model,
40 layers, 64 slots, 10,752 pages, prompts padded to 2,048): the recurrent
state of 36 mamba layers as ONE array per kind, ``[64, 36, 3, 4352]`` and
``[64, 36, 64, 64, 128]`` float32 (4.89 GB), each layer's ``[:, m]`` written
back in place: both arrays and both pools aliased, no copy of either array,
a step's temporaries 50 MB and a prefill's 1.28 GB (its 32 heads' float32
scores over 2,048 rows) beside 6.38 GB of weights.

The next pins the K/V form of the fused attention kernel at the accepted
cells' shapes to what the parent of PR 37 lowered it to: the Mosaic module
itself, read back from the custom call without its source locations.

The last compiles the grouped expert matmul (``ops/grouped_matmul``) at the
operand shapes of the three cells whose prefill runs it: every held expert's
whole matrix in one block, blocks inside the VMEM limit the kernel sets, and
no more temporaries than ``jax.lax.ragged_dot``'s program at the same shapes
kept (the prefill programs of the two families compiled above at their cells'
sizes are held to their parents' temporaries too).
"""

import pytest

pytest.importorskip("jax")
import jax  # noqa: E402

import chip_smoke  # noqa: E402
import lowered_programs  # noqa: E402

GEOMETRY = {**chip_smoke.POOL_GEOMETRY, "layers": 2, "vocab": 512, "num_pages": 16384}
#: Temporaries of the two families' programs at their cells' sizes while the
#: prefill's grouped products were ``jax.lax.ragged_dot`` (commit 3cc663f).
PARENT_TEMP_BYTES = {"lfm2_moe": {"step": 16_398_848, "prefill": 197_594_624},
                     "deepseek_v3": {"step": 45_589_504, "prefill": 410_936_832}}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_for_the_chip(monkeypatch):
    """The attention kernels and the grouped expert matmul lower through
    Mosaic, as they do on the chip, and the persistent cache is off: a
    compile for a described chip is written to it and cannot be read back
    without the chip."""
    from jax.experimental.compilation_cache import compilation_cache

    from dmlc_tpu.generate import engine
    from dmlc_tpu.ops import grouped_matmul, ragged_decode

    monkeypatch.setattr(ragged_decode, "interpret_mode", lambda: False)
    monkeypatch.setattr(grouped_matmul, "interpret_mode", lambda: False)
    monkeypatch.setattr(engine, "_compiles_for_tpu", lambda: True)
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def test_step_and_prefill_update_the_pools_in_place(one_chip, compiled_for_the_chip):
    from dmlc_tpu.models import registry

    try:
        memory = chip_smoke.pool_memory(GEOMETRY, sharding=one_chip, use_pallas=True)
    finally:
        registry._REGISTRY.pop("pool_geometry_lm", None)
    pool = 2 * 16384 * 16 * 1280 * 2
    assert memory["pool_bytes"] == pool
    for program in ("step", "prefill"):  # pool_memory raised already if these fail
        assert memory[program]["alias_bytes"] >= 2 * pool
        assert memory[program]["temp_bytes"] < pool
    assert memory["step"]["mosaic"] and not memory["prefill"]["mosaic"]
    # The prefill is the loop over a turn's admitted prompts (this family has no
    # loop of its own): pools through a loop's carry, still the donated buffers.
    assert memory["prefill"]["loop"] and not memory["step"]["loop"]
    # The attention reads the pool's pages and keeps no padded view of them:
    # the step's temporaries stay under ONE float32 unfolding of such a view
    # (the two gathered bfloat16 views alone were that much).
    g = GEOMETRY
    assert memory["step"]["temp_bytes"] < g["max_slots"] * g["max_len"] * g["hidden"] * 4


def test_hybrid_state_and_3840_lane_pools_update_in_place(one_chip, compiled_for_the_chip):
    import jax.numpy as jnp

    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models import olmo_hybrid as oh
    from dmlc_tpu.models import registry
    from dmlc_tpu.ops import ragged_decode

    # The published widths, two periods (deep enough that an order of the loop's
    # body that lets layers' temporaries overlap shows: 847 MB against 501); a
    # small vocabulary (the head is not the point).
    config = oh.OlmoHybridConfig(
        vocab_size=512, hidden_size=3840, intermediate_size=11008,
        layer_types=(oh.LINEAR, oh.LINEAR, oh.LINEAR, oh.FULL) * 2,
        num_attention_heads=30, num_key_value_heads=30,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192, linear_conv_kernel_dim=4,
        max_len=2048)
    slots, num_pages, dtype = 32, 2048, jnp.bfloat16
    spec = oh.register_olmo_hybrid("hybrid_geometry_lm", config)
    try:
        engine = GenerationEngine(spec.name, variables={}, dtype=dtype, max_slots=slots,
                                  page_size=16, num_pages=2, max_prefill=1536, use_pallas=True)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype),
            jax.eval_shape(lambda: spec.init_params(jax.random.PRNGKey(0), dtype=dtype)[1]))
        pool = jax.ShapeDtypeStruct((2 * num_pages, 16, 3840), dtype)   # two K/V layers
        args = chip_smoke.abstract_program_args(engine, variables=variables, pool=pool,
                                                sharding=one_chip)
        pool_bytes = 2 * num_pages * 16 * 3840 * 2
        # S is [slots, 30, 192, 96] float32 on tiles of 128 lanes: 96 lanes take 128.
        state_bytes = 6 * slots * (30 * 192 * 128 * 4 + 3 * 11520 * 2)
        assert engine.state.nbytes == 6 * slots * (30 * 192 * 96 * 4 + 3 * 11520 * 2)
        for name, program in (("step", engine._step), ("prefill", engine._prefill)):
            compiled = program.lower(*args[name]).compile()
            memory = compiled.memory_analysis()
            assert memory.alias_size_in_bytes >= 2 * pool_bytes + state_bytes, name
            # No copy of a pool or of the state: a step keeps a twentieth of a pool,
            # a prefill less than a pool beside its dense attention's float32 scores.
            scores = 30 * 1536 * 1536 * 4 if name == "prefill" else 0
            assert memory.temp_size_in_bytes < pool_bytes + scores, (name, memory.temp_size_in_bytes)
            assert (chip_smoke.MOSAIC_CALL in compiled.as_text()) == (name == "step")
    finally:
        registry._REGISTRY.pop(spec.name, None)
    # gpt2-large's rows and these share one chunk: 256 positions of K and V in flight.
    assert ragged_decode._CHUNK_TOKENS == 256


def test_rotary_gated_expert_family_at_its_cells_sizes(one_chip, compiled_for_the_chip):
    import json

    import jax.numpy as jnp
    import plain_reference

    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models import lfm2_moe as lf
    from dmlc_tpu.models import registry

    cfg = json.loads((plain_reference.REPO / "benchmark" / "configs" / "lfm2-8b-a1b.json").read_text())
    cluster = cfg["cluster"]
    config = lf.Lfm2MoeConfig.from_published(cfg, max_len=cfg["serving_positions"])
    slots, num_pages, dtype = cluster["gen_max_slots"], cluster["gen_num_pages"], jnp.bfloat16
    assert (slots, num_pages, cluster["gen_max_prefill"]) == (64, 6144, 1024)
    spec = lf.register_lfm2_moe("lfm2_geometry_lm", config)
    try:
        engine = GenerationEngine(spec.name, variables={}, dtype=dtype, max_slots=slots,
                                  page_size=cluster["gen_page_size"], num_pages=2,
                                  max_prefill=cluster["gen_max_prefill"], use_pallas=True)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype),
            jax.eval_shape(lambda: spec.init_params(jax.random.PRNGKey(0), dtype=dtype)[1]))
        pool = jax.ShapeDtypeStruct((3 * num_pages, 16, 512), dtype)    # three K/V layers
        args = chip_smoke.abstract_program_args(engine, variables=variables, pool=pool,
                                                sharding=one_chip)
        pool_bytes = 3 * num_pages * 16 * 512 * 2
        state_bytes = 9 * slots * 2 * 2048 * 2
        assert engine.state.nbytes == state_bytes
        weights = 2 * 3_928_728_256
        for name, program in (("step", engine._step), ("prefill", engine._prefill)):
            compiled = program.lower(*args[name]).compile()
            memory = compiled.memory_analysis()
            # Both pools and every conv window updated where they are.
            assert memory.alias_size_in_bytes == 2 * pool_bytes + state_bytes, name
            # No copy of a pool: a step keeps the experts' float32 product and little
            # else (16 MB), a prefill its 1,024 rows at the widest product and the
            # dense attention's float32 scores (198 MB), beside 7.86 GB of weights.
            assert memory.temp_size_in_bytes < pool_bytes, (name, memory.temp_size_in_bytes)
            assert memory.temp_size_in_bytes <= 1.1 * PARENT_TEMP_BYTES["lfm2_moe"][name], name
            assert weights < memory.argument_size_in_bytes < weights + 2 * pool_bytes + 2 ** 24
            text = compiled.as_text()
            # The static shapes chose: the step's experts are the dense form's two
            # batched products, the prefill's the grouped matmul (a Mosaic call of
            # its own on the chip); the step's three Mosaic calls are the attention.
            assert chip_smoke.MOSAIC_CALL in text
            assert ("_grouped_expert_matmul" in text) == (name == "prefill"), name
            assert "ragged-dot" not in text, name
            assert ("f32[32,64,3584]" in text) == (name == "step"), name
    finally:
        registry._REGISTRY.pop(spec.name, None)


def test_stacked_state_family_at_its_cells_sizes(one_chip, compiled_for_the_chip):
    import json
    import re

    import jax.numpy as jnp
    import plain_reference

    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models import granite_hybrid as gh
    from dmlc_tpu.models import registry

    cfg = json.loads(
        (plain_reference.REPO / "benchmark" / "configs" / "granite-4-h-micro.json").read_text())
    cluster = cfg["cluster"]
    slots, num_pages, dtype = cluster["gen_max_slots"], cluster["gen_num_pages"], jnp.bfloat16
    assert (slots, num_pages, cluster["gen_max_prefill"]) == (64, 10752, 2048)
    config = gh.GraniteHybridConfig.from_published(cfg, max_len=cfg["serving_positions"])
    spec = gh.register_granite_hybrid("granite_geometry_lm", config)
    try:
        engine = GenerationEngine(spec.name, variables={}, dtype=dtype, max_slots=slots,
                                  page_size=cluster["gen_page_size"], num_pages=2,
                                  max_prefill=cluster["gen_max_prefill"], use_pallas=True)
        variables = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, dtype),
            jax.eval_shape(lambda: spec.init_params(jax.random.PRNGKey(0), dtype=dtype)[1]))
        pool = jax.ShapeDtypeStruct((4 * num_pages, 16, 512), dtype)     # four K/V layers
        args = chip_smoke.abstract_program_args(engine, variables=variables, pool=pool,
                                                sharding=one_chip)
        pool_bytes = 4 * num_pages * 16 * 512 * 2
        state_bytes = slots * 36 * (64 * 64 * 128 * 4 + 3 * 4352 * 2)
        assert engine.state.nbytes == state_bytes == 4_892_000_256
        weights = 2 * 3_191_396_096
        for name, program in (("step", engine._step), ("prefill", engine._prefill)):
            compiled = program.lower(*args[name]).compile()
            memory = compiled.memory_analysis()
            # Both pools and both stacked arrays updated where they are.
            assert memory.alias_size_in_bytes == 2 * pool_bytes + state_bytes, name
            assert weights < memory.argument_size_in_bytes < weights + 2 * pool_bytes + state_bytes + 2 ** 24
            text = compiled.as_text()
            assert not re.search(r"copy(-start)?(\.\d+)? = [^ ]*\[64,36,", text), name
            assert (chip_smoke.MOSAIC_CALL in text) == (name == "step"), name
            # No copy of a state array: a step keeps 50 MB, a prefill its float32
            # scores and its 2,048 rows at the widest product (1.28 GB).
            limit = 2 ** 27 if name == "step" else 2 ** 31
            assert memory.temp_size_in_bytes < limit, (name, memory.temp_size_in_bytes)
    finally:
        registry._REGISTRY.pop(spec.name, None)


def _latent_engine(name, cfg, row_lanes=None):
    import jax.numpy as jnp

    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models import deepseek_v3 as ds

    cluster = cfg["cluster"]
    config = ds.DeepseekV3Config.from_published(
        cfg, n_routed_experts=cfg["published"]["n_routed_experts"],
        experts_held=cfg["deployment"]["experts_held"], max_len=cfg["serving_positions"])
    spec = ds.register_deepseek_v3(name, config)
    engine = GenerationEngine(spec.name, variables={}, dtype=jnp.bfloat16,
                              max_slots=cluster["gen_max_slots"], page_size=cluster["gen_page_size"],
                              num_pages=2, max_prefill=cluster["gen_max_prefill"], use_pallas=True)
    variables = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: spec.init_params(jax.random.PRNGKey(0), dtype=jnp.bfloat16)[1]))
    return engine, variables


def test_latent_family_at_its_cells_sizes_keeps_one_pool(one_chip, compiled_for_the_chip):
    import json

    import jax.numpy as jnp
    import plain_reference

    from dmlc_tpu.models import registry

    cfg = json.loads(
        (plain_reference.REPO / "benchmark" / "configs" / "kanana-2-30b-a3b.json").read_text())
    cluster = cfg["cluster"]
    assert (cluster["gen_max_slots"], cluster["gen_num_pages"], cluster["gen_max_prefill"]) == (
        64, 12288, 2048)
    try:
        engine, variables = _latent_engine("latent_geometry_lm", cfg)
        assert engine.latent_row == 640 and engine._v_state is None and engine.state.nbytes == 0
        pool = jax.ShapeDtypeStruct((24 * cluster["gen_num_pages"], 16, 640), jnp.bfloat16)
        memory = chip_smoke.program_memory(engine, variables, pool, sharding=one_chip)
    finally:
        registry._REGISTRY.pop("latent_geometry_lm", None)
    pool_bytes = 24 * 12288 * 16 * 640 * 2
    weights = 2 * 3_155_018_624
    assert memory["pools"] == 1 and memory["pool_bytes"] == pool_bytes == 6_039_797_760
    for name in ("step", "prefill"):
        # ONE pool aliased and nothing else of its size: a second pool would show
        # in the aliased bytes, in the arguments, or as a temporary.
        assert memory[name]["alias_bytes"] == pool_bytes, name
        assert weights + pool_bytes < memory[name]["argument_bytes"] < weights + pool_bytes + 2 ** 24
    # A step keeps the held experts' float32 product and the kernel's operands
    # (46 MB); a prefill its 2,048 rows at the widest product and a block of 512
    # query rows of float32 scores, not the whole square (411 MB, not 1.5 GB).
    assert memory["step"]["temp_bytes"] < 2 ** 26
    assert memory["prefill"]["temp_bytes"] < 2 ** 29
    for name in ("step", "prefill"):
        assert memory[name]["temp_bytes"] <= 1.1 * PARENT_TEMP_BYTES["deepseek_v3"][name], name
    assert memory["step"]["mosaic"] and memory["prefill"]["loop"] and not memory["step"]["loop"]


def test_a_576_lane_row_is_refused_by_the_chips_compiler(one_chip, compiled_for_the_chip, monkeypatch):
    """Why a cached row is stored on 640 lanes (PERF.md finding PR 37.1): the
    chip tiles the pool's trailing axis by 128 lanes (the error names the
    pool as ``x640`` in memory already) and the kernel's page copy may not
    cut a tile."""
    import jax.numpy as jnp

    from dmlc_tpu.ops.ragged_decode import paged_latent_decode_attention

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def lower(width):
        fn = jax.jit(lambda q, pool, table, lengths: paged_latent_decode_attention(
            q, pool, table, lengths, first_row=0, value_lanes=512, scale=192 ** -0.5))
        return fn.lower(abstract((8, 32, width), jnp.bfloat16),
                        abstract((64, 16, width), jnp.bfloat16), abstract((8, 4), jnp.int32),
                        abstract((8,), jnp.int32))

    assert chip_smoke.MOSAIC_CALL in lower(640).as_text()
    with pytest.raises(Exception, match=r"aligned to tiling \(128\), but is 576"):
        lower(576).compile()


#: The Mosaic module of ``paged_decode_attention`` at the accepted cells' shapes,
#: without source locations: SHA-256 (16 hex digits) on the parent of PR 37.
MOSAIC_PINS = {"docs": "9c0c9ce281e4e025", "answers": "dbd2a4a3d7bbee20",
               "briefs": "4fa738e6a100b6fd", "replies": "8f407f86b5c3f216"}


@pytest.mark.parametrize("case", sorted(lowered_programs.KERNEL_CASES))
def test_the_kv_kernel_lowers_to_the_parents_mosaic_module(one_chip, compiled_for_the_chip, case):
    module = lowered_programs.mosaic_module(lowered_programs.kernel_text_for_tpu(case, one_chip))
    assert "tpu.enqueue_dma" in module or "dma" in module
    assert lowered_programs.sha(module) == MOSAIC_PINS[case]


#: The grouped products of the three cells whose prefill runs the grouped form:
#: (pair rows, k, held experts, n), first product then second.
GROUPED_SHAPES = {
    "answers.w1": (11264, 1024, 128, 2688), "answers.w2": (11264, 2688, 128, 1024),
    "replies.w13": (4096, 2048, 32, 3584), "replies.w2": (4096, 1792, 32, 2048),
    "longform.w13": (12288, 2048, 16, 1536), "longform.w2": (12288, 768, 16, 2048),
}


@pytest.mark.parametrize("case", sorted(GROUPED_SHAPES))
def test_the_grouped_matmul_compiles_at_the_cells_shapes(one_chip, compiled_for_the_chip, case):
    import jax.numpy as jnp

    from dmlc_tpu.ops import grouped_matmul as gm

    m, k, count, n = GROUPED_SHAPES[case]

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (abstract((m, k), jnp.bfloat16), abstract((count, k, n), jnp.bfloat16),
            abstract((count,), jnp.int32))
    blocks = gm.blocks(m, k, n, count)
    # Each expert's whole matrix is one block, read once a call; the blocks fit
    # the limit the kernel asks for, and the limit the chip's 128 MiB.
    assert (blocks.tn, blocks.n_blocks, blocks.tm) == (n, 1, 128)
    assert blocks.vmem_block_bytes <= blocks.vmem_limit_bytes <= 64 << 20
    compiled = jax.jit(gm.grouped_matmul).lower(*args).compile()
    text = compiled.as_text()
    assert chip_smoke.MOSAIC_CALL in text and "_grouped_expert_matmul" in text
    assert "ragged-dot" not in text
    parent = jax.jit(lambda lhs, rhs, sizes: jax.lax.ragged_dot(
        lhs, rhs, sizes, preferred_element_type=jnp.float32)).lower(*args).compile()
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.1 * parent.memory_analysis().temp_size_in_bytes, temp
