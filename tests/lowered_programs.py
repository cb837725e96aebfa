"""The engine's two programs of a registered model, lowered from abstract
arguments (nothing is drawn, nothing runs), as text and as its SHA-256: what
a change to ``generate/`` or a kernel must leave byte for byte for the
families it does not serve. ``python tests/lowered_programs.py`` prints the
table the pin in ``tests/test_lowered_programs.py`` holds (run it on the
PARENT of a change that means to keep the programs)."""

import hashlib

#: The families behind the engine's seam before ``models/deepseek_v3``, each
#: at its CPU preset: (registry name, slots) at two batch sizes, one of them
#: a form of its own for the expert layer (``t k >= 2 n_experts`` or not).
CASES = [(model, slots) for model in ("lm_small", "nemotron_h_tiny", "olmo_hybrid_tiny",
                                      "lfm2_moe_tiny") for slots in (4, 24)]


def program_texts(model: str, slots: int, use_pallas: bool) -> dict:
    """``{"step": text, "prefill": text}`` of ``model``'s engine at ``slots``
    slots, float32, pages of 8, prompts padded to 32."""
    import jax
    import jax.numpy as jnp

    import chip_smoke
    from dmlc_tpu.generate.engine import GenerationEngine
    from dmlc_tpu.models.registry import get_model

    spec = get_model(model)
    engine = GenerationEngine(model, variables={}, dtype=jnp.float32, max_slots=slots,
                              page_size=8, num_pages=64, max_prefill=32, use_pallas=use_pallas)
    variables = jax.eval_shape(
        lambda: spec.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)[1])
    args = chip_smoke.abstract_program_args(engine, variables=variables)
    return {name: program.lower(*args[name]).as_text()
            for name, program in (("step", engine._step), ("prefill", engine._prefill))}


#: The K/V form of the fused decode attention at the shapes the accepted cells
#: run it at: (heads, KV heads, head width, slots, pages a table row names).
KERNEL_CASES = {"docs": (20, 20, 64, 24, 64), "answers": (32, 2, 128, 64, 48),
                "briefs": (30, 30, 128, 32, 128), "replies": (32, 8, 64, 64, 96)}


def kernel_text_for_tpu(case: str, sharding) -> str:
    """``paged_decode_attention`` lowered through Mosaic for a described chip
    (the caller has turned the interpreter off, tests/test_tpu_compile.py)."""
    import jax
    import jax.numpy as jnp

    from dmlc_tpu.ops.ragged_decode import paged_decode_attention

    heads, kv_heads, head_dim, slots, pages = KERNEL_CASES[case]

    def abstract(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    pool = abstract((2 * 512, 16, kv_heads * head_dim), jnp.bfloat16)
    fn = jax.jit(lambda q, k, v, table, lengths, first: paged_decode_attention(
        q, k, v, table, lengths, first_row=first, kv_heads=kv_heads))
    return fn.lower(abstract((slots, heads, head_dim), jnp.bfloat16), pool, pool,
                    abstract((slots, pages), jnp.int32), abstract((slots,), jnp.int32),
                    abstract((), jnp.int32)).as_text()


def mosaic_module(lowered_text: str) -> str:
    """The Mosaic kernel a lowered program carries (serialized in its custom
    call's ``body``), as MLIR text WITHOUT source locations: those name this
    repo's files and lines, which any edit above the kernel moves."""
    import base64
    import re

    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', lowered_text).group(1)
    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def digests(use_pallas: bool) -> dict:
    return {f"{model}/{slots}/{name}": sha(text)
            for model, slots in CASES
            for name, text in program_texts(model, slots, use_pallas).items()}


if __name__ == "__main__":
    import json
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    out = {"xla": digests(False), "kernel": digests(True)}
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from dmlc_tpu.ops import ragged_decode

    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2").devices[0])
    ragged_decode.interpret_mode = lambda: False
    out["mosaic"] = {case: sha(mosaic_module(kernel_text_for_tpu(case, chip)))
                     for case in KERNEL_CASES}
    print(json.dumps(out, indent=1))
