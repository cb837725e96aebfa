"""The number formats of the control of ``correct``: a tensor rounded
through the nearest precision below the configuration's, one scale a tensor.
Shared by the plain references; used by no benchmark run."""

from __future__ import annotations

#: The nearest precision below the one a configuration states.
BELOW = {"float32": "bf16", "bfloat16": "fp8", "float16": "fp8", "int8": "int4", "fp8": "int4"}


def quantize(x, mode: str):
    import jax.numpy as jnp

    if mode == "bf16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if mode == "fp8":
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    if mode in ("int8", "int4"):
        top = 127.0 if mode == "int8" else 7.0
        scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
        return jnp.clip(jnp.round(x / scale), -top, top) * scale
    raise ValueError(f"unknown control precision {mode!r}")
