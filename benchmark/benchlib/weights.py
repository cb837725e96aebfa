"""Weights from the seed, on the device, in one jitted call, in the type
they are served in.

The program's registry gives only the tree's SHAPES (``jax.eval_shape`` of
its init); every value is drawn here. One flat normal draw is cut into the
leaves in sorted path order and each leaf is moved to the mean and spread
its rule names, so the same seed and rules give the same weights whoever
asks: the program's engine and the plain reference get the same arrays.
"""

from __future__ import annotations

import math
import re


def leaf_paths(tree, prefix: str = "") -> dict:
    """Nested dict -> {"a/b/c": leaf}."""
    out = {}
    for key, value in tree.items():
        path = f"{prefix}/{key}" if prefix else str(key)
        if isinstance(value, dict):
            out.update(leaf_paths(value, path))
        else:
            out[path] = value
    return out


def unflatten(flat: dict) -> dict:
    out: dict = {}
    for path, leaf in flat.items():
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return out


def rule_for(path: str, shape, rules) -> tuple[float, float]:
    """(mean, std) of the first rule whose regex matches ``path``. A rule's
    ``std`` is a number, or ``"he"`` for ``gain * sqrt(2 / fan_in)`` with
    fan_in the product of all but the last axis."""
    for rule in rules:
        if re.search(rule["match"], path):
            std = rule.get("std", 0.0)
            if std == "he":
                std = rule.get("gain", 1.0) * math.sqrt(2.0 / max(1, math.prod(shape[:-1])))
            return float(rule.get("mean", 0.0)), float(std)
    raise SystemExit(f"benchmark: no init rule matches weight {path!r}")


def make(shapes: dict, rules, seed: int, dtype):
    """``shapes``: {path: shape tuple}. Returns {path: device array of dtype}."""
    import jax
    import jax.numpy as jnp

    order = sorted(shapes)
    plan = [(p, tuple(shapes[p]), *rule_for(p, tuple(shapes[p]), rules)) for p in order]
    total = sum(math.prod(shape) for _, shape, _, _ in plan)

    def build(key):
        flat = jax.random.normal(key, (total,), jnp.bfloat16)
        out, at = [], 0
        for _, shape, mean, std in plan:
            n = math.prod(shape)
            leaf = flat[at:at + n].astype(jnp.float32) * std + mean
            out.append(leaf.reshape(shape).astype(dtype))
            at += n
        return out

    # The seed may be a little over 2**31: fold it in as two 31-bit halves.
    seed = int(seed)
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(20240924), seed & 0x7FFFFFFF),
                             (seed >> 31) & 0x7FFFFFFF)
    leaves = jax.jit(build)(key)
    return dict(zip(order, leaves))
