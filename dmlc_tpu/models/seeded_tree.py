"""A parameter tree drawn from a seed, for families that are plain functions
over a tree (every family ``registry.register_family`` registers): what the
registry's ``init_params`` needs of a module, ``init(rng, tokens) ->
{"params": tree}``, and nothing else, so there is no flax module to keep in
step with the functions.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp


class SeededTreeModule:
    """``shapes``: nested ``{name: shape tuple}``; ``leaf_mean_std(path)``
    gives each leaf's normal draw. A leaf's key follows from the sorted names
    on its path, so the same ``rng`` gives the same tree whoever asks."""

    def __init__(self, shapes: dict, leaf_mean_std: Callable[[str], tuple[float, float]], *,
                 vocab: int, max_len: int, dtype: Any = jnp.float32) -> None:
        self._shapes = shapes
        self._leaf_mean_std = leaf_mean_std
        self.vocab = vocab
        self.max_len = max_len
        self.dtype = dtype

    def init(self, rng: Any, tokens: Any = None) -> dict:
        del tokens  # the tree is sized by the config, not by an example

        def build(node: Any, path: str, key: Any) -> Any:
            if isinstance(node, tuple):
                mean, std = self._leaf_mean_std(path)
                return (mean + std * jax.random.normal(key, node, jnp.float32)).astype(self.dtype)
            keys = jax.random.split(key, len(node))
            return {name: build(child, f"{path}/{name}", k)
                    for (name, child), k in zip(sorted(node.items()), keys)}

        return {"params": build(self._shapes, "", rng)}
