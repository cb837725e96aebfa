"""Pretrained-weight distribution: serialize, validate, publish, import.

The reference's entire ML story is pretrained weights: ``.ot`` VarStore files
loaded at member startup (src/services.rs:513-524) and re-broadcast by the
`train` verb (src/services.rs:139-144, README.md:21). Here the equivalent
pipeline is:

1. import an external checkpoint into our Flax layout
   (``import_external`` -> models/convert.py per family),
2. ``weights_to_bytes`` -> one self-describing blob (magic + model name +
   flax msgpack),
3. ``sdfs put`` the blob as ``models/{model_name}`` (versioned, replicated),
4. the `train` verb fans the blob to every member, whose ModelLoader
   (scheduler/worker.py) deserializes and hot-swaps it into the running
   InferenceEngine — predictions change without a restart.

Every deserialized tree is validated against the registry model's abstract
init (structure + shapes) before it can reach an engine, so a corrupt or
mismatched blob fails at load, not mid-forward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from flax import serialization

from dmlc_tpu.models import convert
from dmlc_tpu.models.registry import get_model

MAGIC = b"DMLCWTS1"


def sdfs_weights_name(model_name: str) -> str:
    """Canonical SDFS name for a model's weights blob (the `train` payload)."""
    return f"models/{model_name}"


@functools.lru_cache(maxsize=None)
def variables_template(model_name: str):
    """Abstract (ShapeDtypeStruct) variables tree for a registry model —
    no compilation, and cached: every model.load RPC validates against it."""
    spec = get_model(model_name)
    model = spec.module(dtype=jnp.float32)
    if spec.kind == "lm":
        dummy_tokens = jnp.zeros((1, 8), jnp.int32)
        return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dummy_tokens))
    dummy = jnp.zeros((1, spec.input_size, spec.input_size, 3), jnp.float32)
    return jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), dummy, train=False))


def check_variables(model_name: str, variables) -> None:
    """Raise ValueError unless ``variables`` matches the model's tree
    structure and leaf shapes."""
    template = variables_template(model_name)
    t_paths = jax.tree_util.tree_flatten_with_path(template)[0]
    v_paths = jax.tree_util.tree_flatten_with_path(variables)[0]
    t_map = {jax.tree_util.keystr(p): leaf.shape for p, leaf in t_paths}
    v_map = {jax.tree_util.keystr(p): np.shape(leaf) for p, leaf in v_paths}
    if t_map.keys() != v_map.keys():
        missing = sorted(t_map.keys() - v_map.keys())[:3]
        extra = sorted(v_map.keys() - t_map.keys())[:3]
        raise ValueError(
            f"variables tree mismatch for {model_name!r}: missing={missing} extra={extra}"
        )
    for key, shape in t_map.items():
        if tuple(v_map[key]) != tuple(shape):
            raise ValueError(
                f"shape mismatch for {model_name!r} at {key}: "
                f"got {tuple(v_map[key])}, want {tuple(shape)}"
            )


def weights_to_bytes(model_name: str, variables) -> bytes:
    """Serialize a validated variables tree into the distribution blob."""
    check_variables(model_name, variables)
    name_b = model_name.encode()
    payload = serialization.msgpack_serialize(
        jax.tree_util.tree_map(np.asarray, variables)
    )
    return MAGIC + len(name_b).to_bytes(2, "big") + name_b + payload


def weights_from_bytes(data: bytes, expect_model: str | None = None):
    """-> (model_name, variables), validated against the registry model."""
    if data[: len(MAGIC)] != MAGIC:
        raise ValueError("not a dmlc weights blob (bad magic)")
    off = len(MAGIC)
    n = int.from_bytes(data[off : off + 2], "big")
    model_name = data[off + 2 : off + 2 + n].decode()
    if expect_model is not None and model_name != expect_model:
        raise ValueError(f"weights are for {model_name!r}, expected {expect_model!r}")
    variables = serialization.msgpack_restore(data[off + 2 + n :])
    check_variables(model_name, variables)
    return model_name, variables


def publish_weights(sdfs_client, model_name: str, variables) -> int:
    """Put a new weights version into SDFS; returns the version number."""
    blob = weights_to_bytes(model_name, variables)
    return sdfs_client.put_bytes(blob, sdfs_weights_name(model_name))["version"]


# ---------------------------------------------------------------------------
# External checkpoint import (dispatch over models/convert.py)
# ---------------------------------------------------------------------------

_RESNET_STAGES = {
    "resnet18": ([2, 2, 2, 2], False),
    "resnet34": ([3, 4, 6, 3], False),
    "resnet50": ([3, 4, 6, 3], True),
}
_VIT_LAYERS = {"vit_b16": 12, "vit_l14": 24}
_CLIP_LAYERS = {"clip_vit_l14": 24, "clip_vit_b32": 12}


def import_external(model_name: str, state_dict) -> dict:
    """External state dict (numpy values) -> validated variables tree.

    torchvision layouts for resnet/alexnet, HuggingFace layouts for
    vit/clip — the layouts the ecosystem's pretrained checkpoints ship in
    (the reference's `.ot` files played this role, services.rs:513-524).
    """
    if model_name in _RESNET_STAGES:
        sizes, bottleneck = _RESNET_STAGES[model_name]
        variables = convert.resnet_params_from_torch(state_dict, sizes, bottleneck)
    elif model_name == "alexnet":
        variables = convert.alexnet_params_from_torch(state_dict)
    elif model_name in _VIT_LAYERS:
        variables = convert.vit_params_from_hf(state_dict, _VIT_LAYERS[model_name])
    elif model_name in _CLIP_LAYERS:
        variables = convert.clip_params_from_hf(state_dict, _CLIP_LAYERS[model_name])
    else:
        raise KeyError(f"no external-checkpoint importer for {model_name!r}")
    check_variables(model_name, variables)
    return variables
