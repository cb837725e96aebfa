"""Pipeline parallelism: GPipe-style microbatch pipelining over a ``pp``
mesh axis.

The reference has no intra-model parallelism of any kind (SURVEY.md §2:
"Pipeline parallel: Absent" — every forward runs whole on one CPU). Here
pipelining is TPU-first: each device along ``pp`` holds ONE stage's
parameters (stacked stage params sharded on their leading axis), and
activations move stage-to-stage with ``lax.ppermute`` — one ICI hop per
tick — inside a ``lax.scan`` systolic schedule. Microbatches fill the
pipeline, steady-state keeps every stage busy, and the drain phase empties
it: ``n_micro + n_stages - 1`` ticks total. The whole schedule is one
compiled XLA program; no Python control flow at dispatch time.

Composes with ``dp`` (shard the microbatch dim) and with the tp rules in
mesh.py (shard inside stage_fn's matmuls) on the same mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(per_stage_params: list) -> jax.Array:
    """Stack per-stage parameter pytrees along a new leading 'stage' axis;
    ``pipeline_apply`` shards that axis over pp via its shard_map in_specs."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *per_stage_params)


def _pipeline_local(params, x, *, stage_fn, axis_name: str, n_micro: int):
    """Per-device body under shard_map.

    params: this stage's params (leading stage axis of size 1, squeezed).
    x: [n_micro_local? no — full] microbatched input [n_micro, mb, ...],
       meaningful on stage 0 (identical copies elsewhere are ignored).
    Returns [n_micro, mb, ...] outputs, valid on every device after the
    final broadcast (all devices return the last stage's results).
    """
    n_stages = lax.axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda a: a[0], params)  # drop stage axis
    mb_shape = x.shape[1:]

    # Probe the stage output shape/dtype statically.
    out_shape = jax.eval_shape(stage_fn, params, jax.ShapeDtypeStruct(mb_shape, x.dtype))
    assert out_shape.shape == mb_shape, (
        "pipeline stages must preserve activation shape "
        f"(got {out_shape.shape} from {mb_shape})"
    )

    total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def tick(carry, t):
        recv, outputs = carry
        # Stage 0 injects microbatch t (zeros past the fill phase);
        # other stages consume what the ring delivered.
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        injected = lax.dynamic_index_in_dim(x, mb_idx, axis=0, keepdims=False)
        inp = jnp.where(stage == 0, injected, recv)
        out = stage_fn(params, inp)
        # Last stage banks microbatch (t - (n_stages-1)) when it's valid.
        done_idx = t - (n_stages - 1)
        outputs = jnp.where(
            (stage == n_stages - 1) & (done_idx >= 0),
            lax.dynamic_update_index_in_dim(
                outputs, out.astype(outputs.dtype), jnp.clip(done_idx, 0, n_micro - 1), axis=0
            ),
            outputs,
        )
        recv_next = lax.ppermute(out, axis_name, perm)
        return (recv_next, outputs), None

    recv0 = jnp.zeros(mb_shape, x.dtype)
    outputs0 = jnp.zeros((n_micro, *mb_shape), x.dtype)
    (_, outputs), _ = lax.scan(tick, (recv0, outputs0), jnp.arange(total))
    # Broadcast the last stage's banked outputs to every pp rank so the
    # result has a plain replicated-over-pp layout.
    gathered = lax.all_gather(outputs, axis_name)  # [n_stages, n_micro, ...]
    return gathered[n_stages - 1]


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x,
    mesh: Mesh,
    *,
    n_micro: int,
    axis_name: str = "pp",
    batch_axis: str | None = "dp",
):
    """Run ``x`` through the pipeline.

    stage_fn(params, activation[mb, ...]) -> activation[mb, ...]
    stacked_params: pytree with leading stage axis == mesh.shape[axis_name]
    x: [batch, ...]; batch must divide into n_micro microbatches (and each
    microbatch over the mesh's ``batch_axis`` when present — dp and pp
    compose: every dp replica pipelines its own slice of each microbatch).
    Returns [batch, ...] outputs.
    """
    batch = x.shape[0]
    if batch % n_micro:
        raise ValueError(f"batch {batch} not divisible into {n_micro} microbatches")
    mb = batch // n_micro
    use_dp = batch_axis is not None and batch_axis in mesh.axis_names
    if use_dp and mb % mesh.shape[batch_axis]:
        raise ValueError(
            f"microbatch {mb} not divisible over {batch_axis}={mesh.shape[batch_axis]}"
        )
    xm = x.reshape(n_micro, mb, *x.shape[1:])
    data_spec = P(None, batch_axis) if use_dp else P()

    param_specs = jax.tree_util.tree_map(lambda _: P(axis_name), stacked_params)
    fn = partial(
        _pipeline_local, stage_fn=stage_fn, axis_name=axis_name, n_micro=n_micro
    )
    out = jax.shard_map(
        fn,
        mesh=mesh,
        in_specs=(param_specs, data_spec),
        out_specs=data_spec,
        check_vma=False,  # outputs are made uniform over pp by the all_gather
    )(stacked_params, xm)
    return out.reshape(batch, *out.shape[2:])


def reference_apply(stage_fn: Callable, per_stage_params: list, x):
    """Sequential single-device reference for parity tests."""
    for p in per_stage_params:
        x = stage_fn(p, x)
    return x
