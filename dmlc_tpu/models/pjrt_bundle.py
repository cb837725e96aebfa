"""Export a serving model as a native PJRT host bundle.

Produces the directory ``native/pjrt_host run`` consumes — the fully
Python-free serving deployment (the program compiles and executes through
the PJRT C ABI; reference analog: the Rust+libtorch native serving host,
services.rs:513-524):

    bundle/
      program.mlir         StableHLO of the serving forward (uint8 NHWC ->
                           top-1 index + prob), weights as PARAMETERS
      compile_options.pb   serialized default xla CompileOptionsProto
      args.txt             manifest: one "dtype:d0,d1,...[=file]" line per
                           executable input, in the exported flatten order
      arg<N>.raw           raw bytes for each weight leaf (row-major)

A plugin that needs client-create options takes them from an operator-
supplied file (``pjrt_host run --options``, or ``client_options.txt`` dropped
into the bundle); the exporter writes none.

Weights ship as raw files SEPARATE from the program, so a weight update
(the `train` verb's SDFS republish) never recompiles — same split the
Python-side ExportedBackend uses.

Entry points: the cluster CLI's `export-bundle` verb and
`python tools/export_pjrt_bundle.py --model resnet18 --batch 8 --out /tmp/bundle`.
"""

from __future__ import annotations

from pathlib import Path


_DTYPE_NAMES = {"uint8": "u8", "float32": "f32", "int32": "i32", "bfloat16": "bf16"}


def export_bundle(
    model_name: str,
    batch_size: int,
    out_dir: Path,
    seed: int = 0,
    image_paths: list[str] | None = None,
    variables=None,
) -> dict:
    import jax
    import numpy as np

    from dmlc_tpu.models import export as export_lib
    from dmlc_tpu.models.registry import get_model

    out_dir.mkdir(parents=True, exist_ok=True)
    blob = export_lib.export_serving(model_name, batch_size=batch_size)
    _, exported = export_lib.load_serving(blob, expect_model=model_name)
    (out_dir / "program.mlir").write_text(exported.mlir_module())

    from jax._src.lib import xla_client

    (out_dir / "compile_options.pb").write_bytes(
        xla_client.CompileOptions().SerializeAsString()
    )

    # Weights in the exported flatten order, dumped as raw row-major bytes
    # next to their manifest lines. ``variables`` lets callers bundle LIVE
    # weights (the CLI verb passes the cluster's published SDFS weights);
    # default is a fixed-seed init for smoke bundles.
    spec = get_model(model_name)
    if variables is None:
        _, variables = spec.init_params(jax.random.PRNGKey(seed), dtype=jax.numpy.bfloat16)
    flat_vars = jax.tree_util.tree_leaves(variables)
    lines = []
    n_weight_args = 0
    for aval in exported.in_avals:
        dt = _DTYPE_NAMES.get(str(aval.dtype))
        if dt is None:
            raise ValueError(f"unsupported exported input dtype {aval.dtype}")
        shape = ",".join(str(d) for d in aval.shape)
        if str(aval.dtype) == "uint8" and len(aval.shape) == 4:
            if image_paths:
                # Stage REAL decoded pixels so the native host classifies
                # actual JPEG data, not zeros; pad the batch by repeating.
                from dmlc_tpu.ops import preprocess as pp

                if len(image_paths) > batch_size:
                    raise ValueError(
                        f"{len(image_paths)} images but batch size "
                        f"{batch_size}: the extras would be silently "
                        "dropped — raise --batch or trim --image"
                    )
                size = int(aval.shape[1])
                batch = pp.load_batch(image_paths, size=size)
                reps = -(-batch_size // batch.shape[0])
                batch = np.tile(batch, (reps, 1, 1, 1))[:batch_size]
                if tuple(batch.shape) != tuple(aval.shape):
                    # Mirrors the weight-leaf guard: fail at export time,
                    # not at the host's deploy-time byte-size check.
                    raise ValueError(
                        f"staged image batch {batch.shape} != exported "
                        f"input aval {tuple(aval.shape)}"
                    )
                (out_dir / "image.raw").write_bytes(batch.tobytes())
                lines.append(f"{dt}:{shape}=image.raw")
            else:
                lines.append(f"{dt}:{shape}")  # the image batch: zeros
        else:
            leaf = np.asarray(flat_vars[n_weight_args])
            if tuple(leaf.shape) != tuple(aval.shape):
                raise ValueError(
                    f"weight leaf {n_weight_args} shape {leaf.shape} != "
                    f"exported aval {aval.shape} — flatten order drifted"
                )
            if str(leaf.dtype) != str(aval.dtype):
                # Same-itemsize mismatches (i32 vs f32) would otherwise write
                # silently-wrong raw bytes the host stages verbatim. Pure
                # precision differences (an f32-trained checkpoint feeding a
                # bf16 program) are cast; anything kind-crossing is a real
                # flatten drift and fails here, not at host load.
                import jax.numpy as jnp

                if jnp.issubdtype(leaf.dtype, np.floating) and jnp.issubdtype(
                    aval.dtype, np.floating
                ):
                    leaf = np.asarray(leaf, dtype=aval.dtype)
                else:
                    raise ValueError(
                        f"weight leaf {n_weight_args} dtype {leaf.dtype} != "
                        f"exported aval dtype {aval.dtype} — flatten order drifted"
                    )
            fname = f"arg{n_weight_args}.raw"
            (out_dir / fname).write_bytes(leaf.tobytes())
            lines.append(f"{dt}:{shape}={fname}")
            n_weight_args += 1
    if n_weight_args != len(flat_vars):
        raise ValueError(
            f"exported {n_weight_args} weight inputs but the tree has "
            f"{len(flat_vars)} leaves"
        )
    (out_dir / "args.txt").write_text("\n".join(lines) + "\n")
    return {
        "model": model_name,
        "batch": batch_size,
        "inputs": len(lines),
        "weight_args": n_weight_args,
        "program_bytes": (out_dir / "program.mlir").stat().st_size,
    }
