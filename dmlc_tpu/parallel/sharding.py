"""Partition-rule engine: regex rules -> PartitionSpec pytrees -> sharded programs.

Every registry model declares its sharding ONCE as an ordered table of
``(regex, PartitionSpec)`` rules (``ModelSpec.partition_rules``). The engine
matches each rule with ``re.search`` against the '/'-joined path of every
parameter leaf — first match wins, scalars and size-1 leaves are always
replicated — and compiles the resulting spec pytree into jit programs at ANY
mesh shape: axes a mesh does not carry (or that do not divide a leaf's dim)
are clamped to replication, so the same table serves a 1-chip replica, a
2-chip tensor-parallel gang, and an 8-chip dp x tp grid without edits.

This generalizes the hardcoded Megatron walk in ``mesh.param_spec`` (kept as
the engine-internal fallback for models that declare no table) and is what
the serving gang path (``scheduler/worker.LmBackend``) runs at predict time.

Rule-table hygiene is checked twice: statically by analyzer rule A8
(tools/analyze/rules/devsem.py — bad regexes, rules shadowed by an earlier
catch-all, tables with no terminal catch-all) and dynamically by
``validate_rules`` against the real abstract parameter tree (dead rules that
match no param, params no rule matches). See docs/SHARDING.md.
"""

from __future__ import annotations

import math
import re
from dataclasses import dataclass
from typing import Any, Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PyTree = Any
PartitionRule = tuple[str, PartitionSpec]

# Megatron-style table for every transformer in the zoo (SPTransformerLM,
# ViT, the CLIP vision trunk — they share Dense naming): attention q/k/v and
# MLP-in split the OUTPUT feature dim over tp, attention-out and MLP-out
# split the INPUT dim, so each block pays exactly one psum; the vocab/class
# head splits its output and is gathered once at the end. Everything else
# (embeddings, norms, convs, the out-projection biases added after the psum)
# replicates via the terminal catch-all.
TRANSFORMER_PARTITION_RULES: tuple[PartitionRule, ...] = (
    (r"(query|key|value|mlp_in)/kernel$", PartitionSpec(None, "tp")),
    (r"(query|key|value|mlp_in)/bias$", PartitionSpec("tp")),
    (r"(out|mlp_out)/kernel$", PartitionSpec("tp", None)),
    (r"(head|projection)/kernel$", PartitionSpec(None, "tp")),
    (r".*", PartitionSpec()),
)

# CNN families: the win is dp over the batch; XLA gains nothing from
# splitting 3x3 convs at these sizes (see mesh.param_spec's rationale).
REPLICATED_PARTITION_RULES: tuple[PartitionRule, ...] = ((r".*", PartitionSpec()),)


def _key_str(entry: Any) -> str:
    for attr in ("key", "idx", "name"):
        if hasattr(entry, attr):
            return str(getattr(entry, attr))
    return str(entry)


def tree_paths(tree: PyTree) -> list[tuple[str, Any]]:
    """Flatten a pytree to ``[('/joined/param/path', leaf), ...]``."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [("/".join(_key_str(k) for k in path), leaf) for path, leaf in flat]


def match_partition_rules(
    rules: Sequence[PartitionRule], tree: PyTree, *, strict: bool = True
) -> PyTree:
    """Map every leaf to the spec of the FIRST rule whose regex ``search``es
    its '/'-joined path. Scalars and size-1 leaves always get ``P()``. With
    ``strict`` (the default), a leaf no rule matches raises ``ValueError`` —
    an unsharded multi-GB param silently replicated onto every chip is the
    bug this engine exists to prevent."""
    compiled = [(re.compile(pat), spec) for pat, spec in rules]
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    specs: list[PartitionSpec] = []
    for path, leaf in flat:
        name = "/".join(_key_str(k) for k in path)
        shape = tuple(getattr(leaf, "shape", ()))
        if not shape or math.prod(shape) == 1:
            specs.append(PartitionSpec())
            continue
        for pat, spec in compiled:
            if pat.search(name):
                specs.append(spec)
                break
        else:
            if strict:
                raise ValueError(f"no partition rule matches param {name!r}")
            specs.append(PartitionSpec())
    return jax.tree_util.tree_unflatten(treedef, specs)


@dataclass(frozen=True)
class RuleReport:
    """Dynamic rule-table audit: the runtime half of analyzer rule A8."""

    dead_rules: tuple[str, ...]  # patterns matching NO param path in the tree
    unmatched: tuple[str, ...]   # param paths no rule matches (spec-less at mesh>1)

    @property
    def ok(self) -> bool:
        return not self.dead_rules and not self.unmatched


def validate_rules(rules: Sequence[PartitionRule], tree: PyTree) -> RuleReport:
    """Audit a rule table against a real (or abstract) parameter tree."""
    paths = [p for p, leaf in tree_paths(tree)]
    compiled = [(pat, re.compile(pat)) for pat, _ in rules]
    dead = tuple(pat for pat, rx in compiled if not any(rx.search(p) for p in paths))
    unmatched = tuple(
        p for p in paths if not any(rx.search(p) for _, rx in compiled)
    )
    return RuleReport(dead_rules=dead, unmatched=unmatched)


def clamp_spec(spec: PartitionSpec, mesh: Mesh, shape: Sequence[int]) -> PartitionSpec:
    """Make a spec valid on THIS mesh and leaf shape: drop axes the mesh does
    not carry (or carries at size 1), and fall back to replication on any dim
    the surviving axes do not divide evenly. This is what lets one rule table
    compile at every mesh shape."""
    sizes: dict[str, int] = dict(zip(mesh.axis_names, mesh.devices.shape))
    out: list[Any] = []
    for dim, entry in enumerate(tuple(spec)[: len(shape)]):
        axes = entry if isinstance(entry, tuple) else (entry,)
        keep = [a for a in axes if a is not None and sizes.get(str(a), 1) > 1]
        factor = math.prod(sizes[str(a)] for a in keep) if keep else 1
        if factor > 1 and shape[dim] % factor:
            keep = []
        out.append(tuple(keep) if len(keep) > 1 else (keep[0] if keep else None))
    return PartitionSpec(*out)


def shardings_for_tree(
    mesh: Mesh,
    tree: PyTree,
    rules: Sequence[PartitionRule],
    *,
    strict: bool = True,
) -> PyTree:
    """Rule table + abstract/real param tree -> pytree of NamedShardings,
    clamped to this mesh."""
    specs = match_partition_rules(rules, tree, strict=strict)
    return jax.tree_util.tree_map(
        lambda leaf, spec: NamedSharding(
            mesh, clamp_spec(spec, mesh, tuple(getattr(leaf, "shape", ())))
        ),
        tree,
        specs,
    )


def make_shard_and_gather_fns(
    mesh: Mesh, shardings: PyTree
) -> tuple[Callable[[PyTree], PyTree], Callable[[PyTree], PyTree]]:
    """``(shard_fn, gather_fn)``: shard_fn places a host tree onto the mesh
    per the shardings; gather_fn pulls a device tree back to host numpy."""

    def shard_fn(tree: PyTree) -> PyTree:
        return jax.tree_util.tree_map(
            lambda leaf, shd: jax.device_put(leaf, shd), tree, shardings
        )

    def gather_fn(tree: PyTree) -> PyTree:
        return jax.tree_util.tree_map(lambda leaf: np.asarray(jax.device_get(leaf)), tree)

    return shard_fn, gather_fn


def plan_axes(
    n_devices: int, *, num_heads: int | None = None, max_tp: int | None = None
) -> dict[str, int]:
    """Mesh-shape selection for a gang of ``n_devices`` chips: tp is the
    largest divisor of n that also divides the head count (attention heads
    cannot split fractionally), capped by ``max_tp``; the rest is dp. A prime
    gang (n=3) with 4 heads therefore runs pure dp; n=8 with 4 heads runs
    dp=2 x tp=4."""
    if n_devices < 1:
        raise ValueError(f"gang needs at least one device, got {n_devices}")
    cap = n_devices if max_tp is None else max(1, min(max_tp, n_devices))
    tp = 1
    for cand in range(1, n_devices + 1):
        if n_devices % cand or cand > cap:
            continue
        if num_heads is not None and num_heads % cand:
            continue
        tp = cand
    return {"dp": n_devices // tp, "tp": tp}


def min_gang_width(
    model_bytes: int, per_chip_budget: int, *, max_width: int
) -> int | None:
    """Smallest gang width whose even ceil-share of the model's resident
    bytes fits the per-chip budget — the replica-count-vs-shard-width trade
    the PlacementAdvisor makes. None when even the widest gang cannot fit."""
    if per_chip_budget <= 0:
        return None
    for width in range(1, max(1, max_width) + 1):
        if -(-model_bytes // width) <= per_chip_budget:
            return width
    return None


def rules_for_model(model_name: str) -> tuple[PartitionRule, ...]:
    """The registry model's declared table, or full replication."""
    from dmlc_tpu.models.registry import get_model

    rules = get_model(model_name).partition_rules
    return tuple(rules) if rules else REPLICATED_PARTITION_RULES


def abstract_params(model_name: str, dtype: Any = jnp.float32) -> PyTree:
    """Shape/dtype-only variables pytree (no device allocation)."""
    from dmlc_tpu.models.registry import get_model

    spec = get_model(model_name)

    def init() -> Any:
        return spec.init_params(jax.random.PRNGKey(0), dtype=dtype)[1]

    return jax.eval_shape(init)


def validate_model_rules(model_name: str, dtype: Any = jnp.float32) -> RuleReport:
    """Audit a registry model's declared table against its abstract tree."""
    return validate_rules(rules_for_model(model_name), abstract_params(model_name, dtype))


def sharded_bytes_per_chip(
    model_name: str, mesh: Mesh, dtype: Any = jnp.float32
) -> int:
    """Per-chip resident weight bytes under this mesh: each leaf contributes
    its bytes divided by the product of mesh-axis sizes its clamped spec
    actually shards over. The gauge the node publishes per gang member."""
    tree = abstract_params(model_name, dtype)
    specs = match_partition_rules(rules_for_model(model_name), tree, strict=False)
    sizes: dict[str, int] = dict(zip(mesh.axis_names, mesh.devices.shape))
    total = 0
    for (path, leaf), (_, spec) in zip(tree_paths(tree), tree_paths(specs)):
        shape = tuple(leaf.shape)
        clamped = clamp_spec(spec, mesh, shape)
        factor = 1
        for entry in tuple(clamped):
            for ax in entry if isinstance(entry, tuple) else (entry,):
                if ax is not None:
                    factor *= sizes.get(str(ax), 1)
        width = jnp.dtype(dtype).itemsize if dtype is not None else jnp.dtype(leaf.dtype).itemsize
        total += -(-math.prod(shape) * width // factor)
    return total


# ---------------------------------------------------------------------------
# Sharded program construction


class ShardedProgram:
    """A registry model compiled at a specific mesh shape: rule-sharded
    params resident on the mesh, a jit forward with batch over dp, plus the
    matching next-token / embedding entry points. One instance == one gang's
    executable; ``mesh`` of 1 device == the unsharded reference."""

    def __init__(
        self,
        model_name: str,
        mesh: Mesh,
        *,
        dtype: Any = jnp.float32,
        seed: int = 0,
    ) -> None:
        from dmlc_tpu.models.registry import get_model

        self.model_name = model_name
        self.mesh = mesh
        self.dtype = dtype
        self.spec = get_model(model_name)
        rules = rules_for_model(model_name)
        model, variables = self.spec.init_params(
            jax.random.PRNGKey(seed), dtype=dtype
        )
        self.model = model
        shardings = shardings_for_tree(mesh, variables, rules)
        shard_fn, self._gather_fn = make_shard_and_gather_fns(mesh, shardings)
        self.variables = shard_fn(variables)
        self._param_shardings = shardings
        self._data_sharding = NamedSharding(mesh, clamp_spec(PartitionSpec("dp"), mesh, (0,)))
        self._forward: Any = None

    @property
    def dp(self) -> int:
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        return int(sizes.get("dp", 1))

    def load_variables(self, variables: PyTree) -> None:
        """Hot-swap weights (SDFS blob path), re-sharded under the same rules."""
        shardings = shardings_for_tree(
            self.mesh, variables, rules_for_model(self.model_name)
        )
        shard_fn, _ = make_shard_and_gather_fns(self.mesh, shardings)
        self.variables = shard_fn(variables)
        self._param_shardings = shardings

    def _build_forward(self) -> Any:
        if self._forward is not None:
            return self._forward
        repl = NamedSharding(self.mesh, PartitionSpec())

        if self.spec.kind == "lm":

            def forward(variables: PyTree, tokens: Any) -> Any:
                logits = self.model.apply(variables, tokens)  # [B, S, V]
                return jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)

        else:
            mean = jnp.asarray([0.485, 0.456, 0.406], self.dtype) * 255.0
            std = jnp.asarray([0.229, 0.224, 0.225], self.dtype) * 255.0

            def forward(variables: PyTree, images: Any) -> Any:
                x = (images.astype(self.dtype) - mean) / std
                out = self.model.apply(variables, x, train=False)
                if self.spec.classifier:
                    return jnp.argmax(out, axis=-1).astype(jnp.int32)
                return out

        self._forward = jax.jit(
            forward,
            in_shardings=(self._param_shardings, self._data_sharding),
            out_shardings=repl,
        )
        return self._forward

    def _pad_to_dp(self, batch: np.ndarray) -> tuple[np.ndarray, int]:
        n = batch.shape[0]
        dp = self.dp
        pad = (-n) % dp
        if pad:
            batch = np.concatenate([batch, np.repeat(batch[-1:], pad, axis=0)], axis=0)
        return batch, n

    def run(self, batch: np.ndarray) -> np.ndarray:
        """Forward a host batch (tokens [B,S] int32 for LMs, uint8 NHWC for
        image models); returns host numpy, padding stripped."""
        fwd = self._build_forward()
        padded, n = self._pad_to_dp(np.asarray(batch))
        dev = jax.device_put(jnp.asarray(padded), self._data_sharding)
        out = np.asarray(jax.device_get(fwd(self.variables, dev)))
        return out[:n]


def tokens_for_prompt(prompt: str, length: int, vocab: int) -> np.ndarray:
    """Deterministic prompt encoding shared by every serving path (cluster
    members, the reference process, the bench): pure arithmetic on a crc32
    seed, so it is stable across processes, PYTHONHASHSEED, and platforms."""
    import zlib

    seed = zlib.crc32(prompt.encode("utf-8"))
    return np.asarray(
        [(seed + i * 2654435761) % vocab for i in range(length)], dtype=np.int32
    )


def encode_prompts(prompts: Iterable[str], length: int, vocab: int) -> np.ndarray:
    return np.stack([tokens_for_prompt(p, length, vocab) for p in prompts])
