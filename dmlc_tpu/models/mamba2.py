"""The Mamba-2 mixer (Dao and Gu, arXiv:2405.21060) as the hybrid families
that carry it run it: ``models/nemotron_h`` (``M`` layers) and
``models/granite_hybrid`` (``mamba`` layers). Each family's config hands
these functions a ``Mamba2Dims``; the mixer's parameters are the same subtree
in both (``in_proj``, ``conv``, ``dt_bias``, ``A_log``, ``D``, ``norm``,
``out_proj``).

Per token ``u`` (normed): ``z | xBC | dt = W_in u``; a causal depthwise conv
of ``conv_kernel`` taps with bias over ``xBC``, then SiLU; ``xBC`` splits into
``x`` (``num_heads`` heads of ``head_dim``), ``B`` and ``C`` (``n_groups``
groups of ``state_size``, head ``h`` reading group ``h // (heads / groups)``);
``Delta = softplus(dt + dt_bias)`` (no clamp), ``A = -exp(A_log)``; ``h_t =
exp(Delta_t A) h_{t-1} + Delta_t x_t (x) B_t``, ``y_t = h_t C_t + D x_t``;
``y <- RMSNorm over each group's slice of (y * silu(z))``; out ``W_out y``.

State a slot carries between steps: the last ``conv_kernel - 1``
pre-activation ``xBC`` rows and ``h`` ``[heads, head_dim, state_size]`` in
float32. Padded prefill is exact for the recurrence: padding sits at the END,
and ``Delta`` is forced to 0 at padded positions, where ``h_t = exp(0)
h_{t-1} + 0`` carries ``h`` unchanged, so the state after the padded scan IS
the state after position ``length - 1``; the conv window is rows
``length-K+1 .. length-1``. Prefill runs the chunked form of the scan
(``chunk_size``), decode the one-step recurrence; tests pin both to the
sequential definition.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Mamba2Dims:
    """The sizes the mixer reads, whatever a family's config calls them."""

    num_heads: int
    head_dim: int
    n_groups: int
    state_size: int
    conv_kernel: int
    chunk_size: int
    norm_eps: float

    @property
    def inner(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.n_groups * self.state_size


def _gated_group_norm(y: Any, z: Any, scale: Any, groups: int, eps: float) -> Any:
    """``RMSNorm over each of `groups` slices of ( y * silu(z) )``: the gate
    is applied before the grouped norm. y, z: [..., inner] float32."""
    g = y * jax.nn.silu(z)
    shaped = g.reshape(*g.shape[:-1], groups, g.shape[-1] // groups)
    shaped = shaped * jax.lax.rsqrt(jnp.mean(jnp.square(shaped), axis=-1, keepdims=True) + eps)
    return shaped.reshape(g.shape) * scale.astype(jnp.float32)


def _mamba_split(dims: Mamba2Dims, zxbcdt: Any) -> tuple[Any, Any, Any]:
    inner = dims.inner
    return (zxbcdt[..., :inner], zxbcdt[..., inner:inner + dims.conv_dim],
            zxbcdt[..., inner + dims.conv_dim:])


def _xbc_split(dims: Mamba2Dims, xbc: Any) -> tuple[Any, Any, Any]:
    """Activated ``xBC`` -> xs [..., H, P], B and C [..., G, N], float32."""
    inner, gn = dims.inner, dims.n_groups * dims.state_size
    lead = xbc.shape[:-1]
    xs = xbc[..., :inner].reshape(*lead, dims.num_heads, dims.head_dim)
    b = xbc[..., inner:inner + gn].reshape(*lead, dims.n_groups, dims.state_size)
    c = xbc[..., inner + gn:].reshape(*lead, dims.n_groups, dims.state_size)
    return xs.astype(jnp.float32), b.astype(jnp.float32), c.astype(jnp.float32)


def _dt_and_decay(p: Any, dt_raw: Any) -> tuple[Any, Any]:
    """``Delta = softplus(dt + dt_bias)`` (no upper clamp) and ``A = -exp(A_log)``."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    return dt, -jnp.exp(p["A_log"].astype(jnp.float32))


def ssd_chunked(xs: Any, dt: Any, a: Any, b: Any, c: Any, chunk: int) -> tuple[Any, Any]:
    """The chunked form of ``h_t = exp(dt_t a) h_{t-1} + dt_t xs_t (x) B_t``,
    ``y_t = h_t C_t`` from ``h_{-1} = 0``. xs [S, H, P]; dt [S, H]; a [H];
    b, c [S, G, N], all float32; S a multiple of ``chunk``. Returns
    (y [S, H, P], h after the last position [H, P, N])."""
    s, heads, p_dim = xs.shape
    groups, n = b.shape[1], b.shape[2]
    per = heads // groups
    nc = s // chunk
    xs = xs.reshape(nc, chunk, groups, per, p_dim)
    dt = dt.reshape(nc, chunk, groups, per)
    b = b.reshape(nc, chunk, groups, n)
    c = c.reshape(nc, chunk, groups, n)
    da = dt * a.reshape(groups, per)                          # [c, L, G, R], <= 0
    cum = jnp.cumsum(da, axis=1)                              # inclusive
    dtx = dt[..., None] * xs                                  # [c, L, G, R, P]
    # Inside a chunk: position l reads s <= l with decay exp(cum_l - cum_s).
    seg = cum[:, :, None] - cum[:, None, :]                   # [c, L(l), L(s), G, R]
    causal = jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None, :]
    decay = jnp.where(causal[None, :, :, None, None], jnp.exp(jnp.minimum(seg, 0.0)), 0.0)
    cb = jnp.einsum("clgn,csgn->clsg", c, b, precision=HIGHEST)
    y_diag = jnp.einsum("clsgr,csgrp->clgrp", cb[..., None] * decay, dtx, precision=HIGHEST)
    # What each chunk adds to the state at its own end ...
    to_end = jnp.exp(cum[:, -1:] - cum)                       # [c, L, G, R]
    states = jnp.einsum("clgr,clgrp,clgn->cgrpn", to_end, dtx, b, precision=HIGHEST)
    # ... carried across chunks by the one sequential part of the scan.
    chunk_decay = jnp.exp(cum[:, -1])                         # [c, G, R]

    def carry(h, inp):
        st, dec = inp
        return dec[..., None, None] * h + st, h

    h0 = jnp.zeros((groups, per, p_dim, n), jnp.float32)
    h_last, h_before = jax.lax.scan(carry, h0, (states, chunk_decay))
    y_off = jnp.einsum("clgn,cgrpn,clgr->clgrp", c, h_before, jnp.exp(cum), precision=HIGHEST)
    y = (y_diag + y_off).reshape(s, heads, p_dim)
    return y, h_last.reshape(heads, p_dim, n)


def mamba_prefill(p: Any, dims: Mamba2Dims, u: Any, length: Any) -> tuple[Any, Any, Any]:
    """One prompt, padded at the end. u: [S, D] (normed). Returns
    (out [S, D], conv window [K-1, conv_dim] = the pre-activation xBC rows
    ``length-K+1 .. length-1``, h after position ``length - 1`` [H, P, N])."""
    s = u.shape[0]
    k = dims.conv_kernel
    z, xbc, dt_raw = _mamba_split(dims, u @ p["in_proj"]["kernel"])
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
    window = jax.lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
    w = p["conv"]["kernel"].astype(jnp.float32)
    conv = sum(padded[j:j + s].astype(jnp.float32) * w[j] for j in range(k))
    act = jax.nn.silu(conv + p["conv"]["bias"].astype(jnp.float32)).astype(u.dtype)
    xs, b, c = _xbc_split(dims, act)
    dt, a = _dt_and_decay(p, dt_raw)
    dt = jnp.where((jnp.arange(s) < length)[:, None], dt, 0.0)   # padding carries h unchanged
    pad = -s % dims.chunk_size
    if pad:
        xs, dt, b, c = (jnp.pad(t, [(0, pad)] + [(0, 0)] * (t.ndim - 1)) for t in (xs, dt, b, c))
    y, h_last = ssd_chunked(xs, dt, a, b, c, dims.chunk_size)
    y = y[:s] + p["D"].astype(jnp.float32)[None, :, None] * xs[:s]
    y = _gated_group_norm(y.reshape(s, -1), z.astype(jnp.float32), p["norm"]["scale"],
                          dims.n_groups, dims.norm_eps)
    return y.astype(u.dtype) @ p["out_proj"]["kernel"], window, h_last


def mamba_decode(p: Any, dims: Mamba2Dims, u: Any, window: Any, h: Any,
                 active: Any) -> tuple[Any, Any, Any]:
    """One token per slot. u: [B, D]; window [B, K-1, conv_dim]; h
    [B, H, P, N] float32; rows with ``active`` false keep their state."""
    bsz = u.shape[0]
    z, xbc, dt_raw = _mamba_split(dims, u @ p["in_proj"]["kernel"])
    full = jnp.concatenate([window, xbc[:, None].astype(window.dtype)], axis=1)   # [B, K, C]
    w = p["conv"]["kernel"].astype(jnp.float32)
    conv = jnp.sum(full.astype(jnp.float32) * w[None], axis=1)
    act = jax.nn.silu(conv + p["conv"]["bias"].astype(jnp.float32)).astype(u.dtype)
    xs, b, c = _xbc_split(dims, act)                                  # [B,H,P], [B,G,N]
    dt, a = _dt_and_decay(p, dt_raw)                                  # [B,H], [H]
    groups, per = dims.n_groups, dims.num_heads // dims.n_groups
    hg = h.reshape(bsz, groups, per, dims.head_dim, dims.state_size)
    decay = jnp.exp(dt * a).reshape(bsz, groups, per, 1, 1)
    dtx = (dt[..., None] * xs).reshape(bsz, groups, per, dims.head_dim, 1)
    h_new = decay * hg + dtx * b[:, :, None, None, :]
    # Elementwise in float32 on purpose: a dot would round h to the MXU's inputs.
    y = jnp.sum(h_new * c[:, :, None, None, :], axis=-1).reshape(bsz, dims.num_heads, -1)
    y = y + p["D"].astype(jnp.float32)[None, :, None] * xs
    y = _gated_group_norm(y.reshape(bsz, -1), z.astype(jnp.float32), p["norm"]["scale"],
                          dims.n_groups, dims.norm_eps)
    keep = active[:, None, None]
    return (y.astype(u.dtype) @ p["out_proj"]["kernel"],
            jnp.where(keep, full[:, 1:], window),
            jnp.where(keep[..., None], h_new.reshape(h.shape), h))
