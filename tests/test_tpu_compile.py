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
"""

import pytest

pytest.importorskip("jax")
import jax  # noqa: E402

import chip_smoke  # noqa: E402

GEOMETRY = {**chip_smoke.POOL_GEOMETRY, "layers": 2, "vocab": 512, "num_pages": 16384}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_step_and_prefill_update_the_pools_in_place(one_chip, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    from dmlc_tpu.models import registry
    from dmlc_tpu.ops import ragged_decode

    # The attention kernel must lower through Mosaic, as it does on the chip.
    monkeypatch.setattr(ragged_decode, "interpret_mode", lambda: False)
    # A compile for a described chip is written to the persistent cache and
    # cannot be read back without the chip: keep it out.
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        memory = chip_smoke.pool_memory(GEOMETRY, sharding=one_chip, use_pallas=True)
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()
        registry._REGISTRY.pop("pool_geometry_lm", None)
    pool = 2 * 16384 * 16 * 1280 * 2
    assert memory["pool_bytes"] == pool
    for program in ("step", "prefill"):  # pool_memory raised already if these fail
        assert memory[program]["alias_bytes"] >= 2 * pool
        assert memory[program]["temp_bytes"] < pool
    assert memory["step"]["mosaic"] and not memory["prefill"]["mosaic"]
    # The attention reads the pool's pages and keeps no padded view of them:
    # the step's temporaries stay under ONE float32 unfolding of such a view
    # (the two gathered bfloat16 views alone were that much).
    g = GEOMETRY
    assert memory["step"]["temp_bytes"] < g["max_slots"] * g["max_len"] * g["hidden"] * 4
