"""Weights from the seed, drawn LEAF BY LEAF on the device.

``benchlib/weights.make`` draws every value as one flat array and then cuts
it, which holds the model twice while it runs: right for 0.8 B parameters,
not for 4.6 B (9.3 GB twice on a 16 GB chip). Here each leaf is its own
draw under its own key, ``fold_in(key(seed), index of the path in sorted
order)``: the rule table is the same (``weights.rule_for``), and the same
seed and rules still give the same arrays whoever asks, so the program's
engine and the plain reference get the same weights.
"""

from __future__ import annotations

import functools

from benchlib.weights import rule_for


@functools.lru_cache(maxsize=None)
def _drawer(shape: tuple, dtype_name: str):
    """One compiled draw per distinct leaf shape; mean and spread are arguments."""
    import jax
    import jax.numpy as jnp

    dtype = jnp.dtype(dtype_name)

    def draw(key, mean, std):
        leaf = jax.random.normal(key, shape, jnp.bfloat16).astype(jnp.float32) * std + mean
        return leaf.astype(dtype)

    return jax.jit(draw)


def make(shapes: dict, rules, seed: int, dtype):
    """``shapes``: {path: shape tuple}. Returns {path: device array of dtype}."""
    import jax
    import jax.numpy as jnp

    # The seed may be a little over 2**31: fold it in as two 31-bit halves.
    seed = int(seed)
    base = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(20240924), seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
    out = {}
    for index, path in enumerate(sorted(shapes)):
        shape = tuple(shapes[path])
        mean, std = rule_for(path, shape, rules)
        out[path] = _drawer(shape, jnp.dtype(dtype).name)(
            jax.random.fold_in(base, index), jnp.float32(mean), jnp.float32(std))
    return out
