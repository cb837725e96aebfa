"""Expert layers. Two live here:

- the SERVED one (bottom of the file: ``route_sigmoid_topk`` +
  ``held_experts_ffn``): a chip is told which experts it holds, routes over
  ALL experts in float32, keeps the gate normalisation over every chosen
  expert, and computes the part of the result its own experts give. No
  capacity, no dropped token, nothing that stands in for the absent chips or
  their exchange: on one chip the layer runs without it. The expert's own
  form is its caller's to state (``activation``): ``models/nemotron_h``
  serves ``relu(x W1)^2 W2`` through it, ``models/lfm2_moe`` the gated
  ``(silu(x W1) * x W3) W2`` with ``W1 | W3`` side by side in one kernel.
- the training-time GShard layer the multi-chip dry run shards over an ``ep``
  axis (``MoEMlp``; capacity routing that drops overflow tokens; no served
  path reaches it).

Expert parallelism: Mixture-of-Experts layer sharded over an ``ep`` axis.

The reference has no expert parallelism (SURVEY.md §2: "Expert parallel:
Absent"). This is the TPU-idiomatic Mesh-TensorFlow/GShard formulation:
routing produces dense one-hot dispatch/combine tensors, expert compute is
one batched einsum over a leading expert axis, and the expert axis is
sharded over ``ep`` — under jit, XLA lowers the token->expert and
expert->token einsums to all_to_all collectives over ICI. No gather/scatter,
no ragged shapes, fully static: exactly the shape the MXU and the compiler
want.

Capacity semantics: each expert processes at most ``capacity`` tokens per
batch; overflow tokens fall through the residual connection (standard GShard
behavior), so shapes stay static regardless of routing skew.
"""

from __future__ import annotations

from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.ops.grouped_matmul import grouped_matmul


def top1_routing(logits: jax.Array, capacity: int):
    """GShard-style top-1 routing with per-expert capacity.

    logits: [T, E]. Returns (dispatch [T, E, C] one-hot, combine [T, E, C]
    gate-weighted, aux_loss scalar).
    """
    t, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)              # [T, E]
    expert = jnp.argmax(gates, axis=-1)                   # [T]
    onehot = jax.nn.one_hot(expert, e, dtype=logits.dtype)  # [T, E]
    # Position of each token in its expert's queue (cumulative count).
    position = jnp.cumsum(onehot, axis=0) * onehot - 1.0  # [T, E], -1 elsewhere
    kept = (position >= 0) & (position < capacity)
    pos_oh = jax.nn.one_hot(
        position.max(axis=-1).astype(jnp.int32), capacity, dtype=logits.dtype
    )  # [T, C]
    dispatch = onehot[:, :, None] * pos_oh[:, None, :] * kept.max(axis=-1)[:, None, None]
    gate = (gates * onehot).sum(-1)                       # [T] chosen gate value
    combine = dispatch * gate[:, None, None]
    # Load-balancing aux loss (Switch/GShard): mean_gates . mean_assignment * E
    density = onehot.mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux = (density * density_proxy).sum() * e
    return dispatch, combine, aux


def top2_routing(logits: jax.Array, capacity: int):
    """GShard top-2 routing with per-expert capacity.

    Each token goes to its two highest-gate experts (second choice masked
    off the first), gates renormalized over the pair so kept tokens mix to
    weight ~1. Second-choice tokens queue BEHIND every first-choice token
    at the same expert (the GShard position offset), so under pressure the
    primary assignment wins capacity. Returns (dispatch [T, E, C],
    combine [T, E, C], aux_loss) like top1_routing.
    """
    t, e = logits.shape
    if e < 2:
        raise ValueError(f"top-2 routing needs >= 2 experts, got {e}")
    gates = jax.nn.softmax(logits, axis=-1)                      # [T, E]
    expert1 = jnp.argmax(gates, axis=-1)                          # [T]
    mask1 = jax.nn.one_hot(expert1, e, dtype=logits.dtype)
    gates_wo1 = jnp.where(mask1 > 0, -jnp.inf, gates)
    expert2 = jnp.argmax(gates_wo1, axis=-1)
    mask2 = jax.nn.one_hot(expert2, e, dtype=logits.dtype)

    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - 1.0                # [T, E]
    # Second choices queue after ALL first choices at that expert.
    pos2 = (jnp.cumsum(mask2, axis=0) + mask1.sum(axis=0)[None, :]) * mask2 - 1.0

    g1 = (gates * mask1).sum(-1)                                  # [T]
    g2 = (gates * mask2).sum(-1)
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    def build(mask, pos, gate):
        kept = (pos >= 0) & (pos < capacity)
        pos_oh = jax.nn.one_hot(
            pos.max(axis=-1).astype(jnp.int32), capacity, dtype=logits.dtype
        )
        dispatch = mask[:, :, None] * pos_oh[:, None, :] * kept.max(axis=-1)[:, None, None]
        return dispatch, dispatch * gate[:, None, None]

    d1, c1 = build(mask1, pos1, g1)
    d2, c2 = build(mask2, pos2, g2)
    # Aux loss on the PRIMARY assignment (Switch/GShard convention).
    density = mask1.mean(axis=0)
    density_proxy = gates.mean(axis=0)
    aux = (density * density_proxy).sum() * e
    return d1 + d2, c1 + c2, aux


class MoEMlp(nn.Module):
    """Expert-parallel MLP block: router -> E expert FFNs -> combine.

    Input [T, D] tokens (flatten batch x sequence first), output [T, D].
    Expert params have leading axis E — shard it over ``ep`` with
    ``moe_param_shardings``.
    """

    num_experts: int
    hidden_dim: int
    capacity_factor: float = 1.25
    # 1 = Switch-style single expert per token; 2 = GShard top-2 (second
    # choice queues behind first choices, gates renormalized per pair).
    router_top_k: int = 1
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        t, d = x.shape
        e = self.num_experts
        # Top-2 sends ~2x the tokens through experts; scale capacity with k
        # so the drop rate stays comparable across router settings.
        capacity = max(1, int(self.capacity_factor * self.router_top_k * t / e))
        router = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32, name="router")
        if self.router_top_k == 1:
            routing = top1_routing
        elif self.router_top_k == 2:
            routing = top2_routing
        else:
            raise ValueError(f"router_top_k must be 1 or 2, got {self.router_top_k}")
        dispatch, combine, aux = routing(router(x.astype(jnp.float32)), capacity)
        dispatch = dispatch.astype(self.dtype)
        combine = combine.astype(self.dtype)

        w_in = self.param(
            "w_in", nn.initializers.lecun_normal(), (e, d, self.hidden_dim), jnp.float32
        )
        w_out = self.param(
            "w_out", nn.initializers.lecun_normal(), (e, self.hidden_dim, d), jnp.float32
        )
        # Token -> expert buffers: XLA lowers this to an all_to_all when the
        # e axis is sharded over ep.
        xs = jnp.einsum("tec,td->ecd", dispatch, x.astype(self.dtype))  # [E, C, D]
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", xs, w_in.astype(self.dtype)))
        ys = jnp.einsum("ech,ehd->ecd", h, w_out.astype(self.dtype))    # [E, C, D]
        # Expert -> token combine (the reverse all_to_all) + residual for
        # dropped tokens (combine rows are all-zero for them).
        out = jnp.einsum("tec,ecd->td", combine, ys)
        self.sow("intermediates", "aux_loss", aux)
        return x + out.astype(x.dtype)


def moe_param_spec(path: tuple[str, ...], leaf) -> P:
    """Partition rule: expert weights shard their leading E axis over ep;
    the router stays replicated."""
    names = [str(p) for p in path]
    if any(n in ("w_in", "w_out") for n in names):
        return P("ep")
    return P()


def moe_param_shardings(mesh: Mesh, variables):
    def one(path, leaf):
        names = tuple(str(getattr(p, "key", p)) for p in path)
        return NamedSharding(mesh, moe_param_spec(names, leaf))

    return jax.tree_util.tree_map_with_path(one, variables)


def shard_moe_params(mesh: Mesh, variables):
    return jax.tree_util.tree_map(
        jax.device_put, variables, moe_param_shardings(mesh, variables)
    )


# ---------------------------------------------------------------------------
# the served expert layer: this chip's share of an expert-parallel layer
# ---------------------------------------------------------------------------


def route_sigmoid_topk(u, router_kernel, score_bias, top_k: int, *, scaling: float = 1.0,
                       normalize: bool = True, eps: float = 1e-20):
    """Sigmoid router over every expert of the model, in float32 whatever
    the activations' type. ``u`` [T, D]; ``router_kernel`` [D, E];
    ``score_bias`` [E] (the score-correction bias: it picks, it does not
    weigh). Returns (idx [T, k] int32, gates [T, k] float32): the ``top_k``
    experts of largest ``s + bias``, each weighed by its own ``s``,
    normalised over ALL chosen experts (held here or not; ``eps`` is what the
    family adds to that sum) and scaled."""
    scores = jax.nn.sigmoid(jnp.dot(
        u.astype(jnp.float32), router_kernel.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(scores + score_bias.astype(jnp.float32), top_k)
    gates = jnp.take_along_axis(scores, idx, axis=-1)
    if normalize:
        gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + eps)
    return idx.astype(jnp.int32), gates * scaling


def held_experts_ffn(x, w1, w2, idx, gates, held: tuple[int, int], rows=None, *,
                     n_experts: int, activation, dense: bool | None = None):
    """``sum over chosen e that are held of g_e * activation(x W1_e) W2_e``.

    ``x`` [T, d]; ``w1`` [count, d, f1], ``w2`` [count, f, d]: experts
    ``first .. first + count - 1`` of the layer's ``n_experts``;
    ``activation`` maps the first product ``[.., f1]`` (float32) to the
    second's operand ``[.., f]`` and is the expert's form, stated by the
    family: ``relu(h)^2`` with ``f1 = f``, or for a gated expert whose kernel
    holds ``W1 | W3``, ``silu(h[:f]) * h[f:]`` with ``f1 = 2 f``; ``idx``/
    ``gates`` [T, k] from the router over all experts; ``rows`` [T] bool
    marks rows that hold a token (padding and empty slots route nowhere).
    Exact at any routing skew, with no capacity, by one of two forms chosen
    from the static shapes (``dense`` overrides the choice; tests pin the
    two to each other):

    - *grouped*: the token-expert pairs are sorted by expert and each held
      expert multiplies just its own rows (``ops/grouped_matmul``: a Pallas
      grouped matmul that reads each hit expert's matrix from HBM once, in
      whole-expert blocks); pairs of experts that live elsewhere sort last,
      belong to no group and add nothing. Its cost follows the experts hit:
      right for a prefill's hundreds of rows.
    - *dense*: every held expert multiplies every row and a ``[T, count]``
      gate matrix (0 where the expert was not chosen) weighs the results.
      It reads each expert once at full bandwidth whatever the routing:
      right for a decode batch of at most one MXU tile of rows whose pairs
      hit most experts anyway (``T k >= 2 n_experts``: 86% or more of them
      expected). At 64 rows, top 22 of 512, 128 held: 1.93 ms a layer
      against 4.45 ms grouped (my chip run, PR 27).

    Returns (routed [T, d] float32, pairs per held expert [count] int32)."""
    first, count = held
    t, k = idx.shape
    local = idx - first
    mine = (local >= 0) & (local < count)
    if rows is not None:
        mine = mine & rows[:, None]
    key = jnp.where(mine, local, count)                      # [T, k]; `count` = lives elsewhere
    group_sizes = jnp.zeros(count + 1, jnp.int32).at[key.reshape(t * k)].add(1)[:count]
    if dense is None:
        dense = t <= 128 and t * k >= 2 * n_experts
    if dense:
        weight = jnp.zeros((t, count + 1), jnp.float32).at[
            jnp.arange(t)[:, None], key].add(gates)[:, :count]
        # Operands upcast, products accumulated in float32 (the TPU compiler folds
        # the converts into the dot: the weights are still read as they are stored).
        f32 = jnp.float32
        h = jnp.einsum("td,edf->etf", x.astype(f32), w1.astype(f32))
        h = activation(h).astype(x.dtype)
        y = jnp.einsum("etf,efd->etd", h.astype(f32), w2.astype(f32))
        return jnp.einsum("etd,te->td", y, weight), group_sizes
    key = key.reshape(t * k)
    order = jnp.argsort(key, stable=True)
    h = grouped_matmul(x[order // k], w1, group_sizes)
    h = activation(h).astype(x.dtype)
    y = grouped_matmul(h, w2, group_sizes)
    # Rows past the last group are whatever the kernel left there: zeroed here.
    in_a_group = (key[order] < count)[:, None]
    y = jnp.where(in_a_group, y * gates.reshape(t * k)[order][:, None], 0.0)
    back = jnp.zeros_like(order).at[order].set(jnp.arange(t * k, dtype=order.dtype))
    routed = y[back].reshape(t, k, -1).sum(axis=1)
    return routed, group_sizes
