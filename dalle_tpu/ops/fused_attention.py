"""Fused-boundary whole-sequence attention: qkv lands as (b, n, 3·h·d).

A kernel whose operands are per-head (b, h, n, d) tensors makes XLA
materialize the head split around the custom call, which the dense path
folds into its einsums. This kernel puts the boundary where the data
already is: the operand is the qkv projection's own output layout
(b, n, 3·h·d) and the result is the pre-to_out merged layout (b, n, h·d) —
the head split/merge, scaling, causal mask, and softmax all live INSIDE
the kernel, so XLA sees a matmul → custom-call → matmul chain with no
layout work between. Rotary stays outside but is applied on the
(b, n, 3h, d) VIEW of the projection output (a reshape, not a transpose —
see models/transformer.py Attention.__call__).

Grid: one program per batch row (the decode kernel's "fewer, bigger
programs" lesson — ops/decode_attention.py), heads unrolled inside.
Backward is a second per-batch-row kernel recomputing scores from the
saved qkv operand, emitting dqkv in the same (n, 3·h·d) merged layout the
to_qkv backward wants; residual memory stays O(n·h·d).

Reference bar: the dense Attention hot path this replaces
(dalle_pytorch/attention.py:58-99).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e9

# per-program live set must fit scoped VMEM (16M on v5e). Calibrated against
# the compiler's own reports: Mosaic DOUBLE-BUFFERS the operand/output block
# windows, so the backward pass (the larger one) costs ~2×(qkv + do + dqkv)
# bf16 windows + the merged bf16 grad accumulators + ~3 (n, n) f32 score
# tiles (+ the double-buffered int8 mask window when present). The small
# config (n=513, h·d=512) compiles at ~12M; medium (h·d=1024) was reported
# at 25.68M by the compiler — the budget below accepts the former and
# rejects the latter with headroom.
_VMEM_BUDGET = 14 * 1024 * 1024
# Mosaic's default scoped-vmem ceiling is 16M, but it is a COMPILER DEFAULT,
# not hardware: pallas_call(compiler_params=CompilerParams(vmem_limit_bytes=
# 32M)) compiles the medium (h·d=1024) merged backward that the default
# rejected at 25.68M demand (r5). Kernels whose estimated demand exceeds the
# default budget request the raised limit; the hard gate below keeps shapes
# that would bust even that (flagship h·d=1792 bwd ≈ 35M) on dense.
# raised tiers for the ceiling request: 32M covers the medium merged
# backward (25.68M demand); 48M serves near-budget estimates that need the
# extra headroom (see _compiler_params) and explicit experiments at the
# flagship-head shape (~35M — compiles, measured PARITY: 0.622 vs 0.622 at
# d=128, where dense attention is already MXU-efficient). The AUTO budget
# admits shapes whose merged kernel measured a win: small (+17%) and
# medium (+22%, 0.638 vs 0.523 MFU); the flagship headline stays dense.
_VMEM_RAISED_LIMITS = ((30 * 1024 * 1024, 32 * 1024 * 1024),
                       (44 * 1024 * 1024, 48 * 1024 * 1024))
_VMEM_RAISED_BUDGET = 30 * 1024 * 1024


def _bwd_bytes(n: int, hd: int) -> int:
    return 34 * n * hd + 12 * n * n + 2 * n * n


def fused_fits(n: int, dim_head: int, heads: int) -> bool:
    """Backward-pass VMEM bound (the larger of the two passes) against the
    RAISED Mosaic limit; the int8 validity-table window (2·n²
    double-buffered) is always shipped."""
    return _bwd_bytes(n, heads * dim_head) <= _VMEM_RAISED_BUDGET


def _compiler_params(bytes_estimate: int):
    """Request the smallest raised scoped-vmem ceiling with ≥25% headroom
    over the ESTIMATE — the formula underestimates the compiler's real
    demand by ~19% at the calibration point (21.55M estimated vs 25.68M
    reported for medium), so a ceiling chosen without headroom could admit
    a shape whose true demand busts it with no dense fallback. Small
    shapes keep the default pipeline headroom."""
    from jax.experimental.pallas import tpu as pltpu
    if bytes_estimate <= _VMEM_BUDGET:
        return None
    need = bytes_estimate + bytes_estimate // 4
    for _, limit in _VMEM_RAISED_LIMITS:
        if need <= limit:
            return pltpu.CompilerParams(vmem_limit_bytes=limit)
    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_RAISED_LIMITS[-1][1])


def use_spec(mask_spec) -> bool:
    """Structured (axial/conv) specs are pure functions of (qpos, kpos) that
    the VALIDITY TABLE is built from host-side (numpy, compile-time). An
    earlier r5 iteration computed them from in-kernel iotas to skip the
    table operand, but the compiler's stack accounting showed two (n, n)
    i32 iotas cost ~4x the double-buffered int8 table window they saved —
    the margin that decides whether the medium (h·d=1024) forward fits
    scoped VMEM. Measured reversal: every fused kernel now ships one
    pre-ANDed int8 table (causality included) and does zero index math."""
    return mask_spec is not None and mask_spec[0] in ("axial", "conv")


def validity_table(n: int, mask, mask_spec) -> "np.ndarray":
    """Host-side (n, n) int8 validity (1 = attend), causality pre-ANDed."""
    import numpy as np
    if use_spec(mask_spec):
        from .flash_attention import elem_fn_from_spec
        ri = np.arange(n)[:, None]
        ci = np.arange(n)[None, :]
        vis = np.asarray(elem_fn_from_spec(mask_spec)(ri, ci), bool)
        return (vis & (ci <= ri)).astype(np.int8)
    if mask is not None:
        return np.asarray(mask, np.int8)  # tables already include causality
    return np.tril(np.ones((n, n), np.int8))


def _fwd_kernel(qkv_ref, mask_ref, o_ref, *, scale, n, h, d):
    hd = h * d
    valid = mask_ref[...] != 0
    # two liveness levers that together admit the medium (h·d=1024) forward
    # under scoped VMEM: slice each head's operands straight from the ref
    # (a whole-block load would hold an extra (n, 3hd) copy on the stack)
    # and store per 128-lane-aligned head group instead of accumulating a
    # merged concat (frees h×(n, d) of accumulator liveness)
    group = max(1, 128 // d) if (128 % d == 0 and h % max(1, 128 // d) == 0
                                 and d <= 128) else h
    outs = []
    for i in range(h):
        q = qkv_ref[0, :, i * d:(i + 1) * d]
        k = qkv_ref[0, :, hd + i * d:hd + (i + 1) * d]
        v = qkv_ref[0, :, 2 * hd + i * d:2 * hd + (i + 1) * d]
        qs = (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
        s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)   # (n, n)
        s = jnp.where(valid, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        o = jax.lax.dot_general((p / l).astype(jnp.bfloat16), v,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        outs.append(o.astype(o_ref.dtype))
        if len(outs) == group:   # h % group == 0 by construction: the final
            lo = (i + 1 - group) * d   # head of each group drains the list
            o_ref[0, :, lo:lo + group * d] = (
                outs[0] if group == 1 else jnp.concatenate(outs, axis=-1))
            outs = []


def _bwd_kernel(qkv_ref, do_ref, mask_ref, dqkv_ref, *, scale, n, h, d):
    hd = h * d
    valid = mask_ref[...] != 0
    dqs, dks, dvs = [], [], []
    for i in range(h):
        q = qkv_ref[0, :, i * d:(i + 1) * d]
        k = qkv_ref[0, :, hd + i * d:hd + (i + 1) * d]
        v = qkv_ref[0, :, 2 * hd + i * d:2 * hd + (i + 1) * d]
        do16 = do_ref[0, :, i * d:(i + 1) * d]
        do32 = do16.astype(jnp.float32)
        qs = (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)
        s = jax.lax.dot_general(qs, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = jnp.where(valid, s, NEG_INF)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        p = e / jnp.sum(e, axis=-1, keepdims=True)                  # (n, n)
        p16 = p.astype(jnp.bfloat16)
        dp = jax.lax.dot_general(do16, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        o = jax.lax.dot_general(p16, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        delta = jnp.sum(o * do32, axis=-1, keepdims=True)
        ds = (p * (dp - delta)).astype(jnp.bfloat16)
        dq = jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        dk = jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32) * scale
        dv = jax.lax.dot_general(p16, do16, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dqs.append(dq.astype(dqkv_ref.dtype))
        dks.append(dk.astype(dqkv_ref.dtype))
        dvs.append(dv.astype(dqkv_ref.dtype))
    dqkv_ref[0] = jnp.concatenate(dqs + dks + dvs, axis=-1)


def _interp(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3, 4, 5))
def fused_qkv_attention(qkv, mask=None, heads: int = 8,
                        scale: Optional[float] = None,
                        interpret: Optional[bool] = None,
                        mask_spec=None):
    """Causal multi-head attention straight off the qkv projection.

    qkv: (b, n, 3·h·d) in [q_0..q_{h-1} | k_0.. | v_0..] head-major slices
    (the ``_split`` convention, models/transformer.py) → (b, n, h·d) merged
    output ready for to_out. ``mask`` is an optional host-side (n, n) numpy
    bool table (True = attend, causality included); None = plain causal.
    A structured ``mask_spec`` (axial/conv — see use_spec) replaces the
    table with an in-kernel iota test and the table is not shipped."""
    return _fused_fwd(qkv, mask, heads, scale, interpret, mask_spec)[0]


def _layout(b, n, hd3, hd):
    qkv_spec = pl.BlockSpec((1, n, hd3), lambda ib: (ib, 0, 0))
    out_spec = pl.BlockSpec((1, n, hd), lambda ib: (ib, 0, 0))
    mask_spec_ = pl.BlockSpec((n, n), lambda ib: (0, 0))
    return qkv_spec, out_spec, mask_spec_


def _fused_fwd(qkv, mask, heads, scale, interpret, mask_spec=None):
    b, n, hd3 = qkv.shape
    hd = hd3 // 3
    d = hd // heads
    if scale is None:
        scale = d ** -0.5
    tbl = validity_table(n, mask, mask_spec)
    qkv_spec, out_spec, mspec = _layout(b, n, hd3, hd)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, n=n, h=heads, d=d),
        grid=(b,),
        in_specs=[qkv_spec, mspec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, hd), qkv.dtype),
        compiler_params=_compiler_params(18 * n * hd + 10 * n * n),
        interpret=_interp(interpret),
        name="fused_attn_fwd",
    )(qkv.astype(jnp.bfloat16), jnp.asarray(tbl))
    return out, (qkv,)


def _fused_bwd(mask, heads, scale, interpret, mask_spec, res, do):
    (qkv,) = res
    b, n, hd3 = qkv.shape
    hd = hd3 // 3
    d = hd // heads
    if scale is None:
        scale = d ** -0.5
    tbl = validity_table(n, mask, mask_spec)
    qkv_spec, out_spec, mspec = _layout(b, n, hd3, hd)
    dqkv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, n=n, h=heads, d=d),
        grid=(b,),
        in_specs=[qkv_spec, out_spec, mspec],
        out_specs=qkv_spec,
        out_shape=jax.ShapeDtypeStruct((b, n, hd3), qkv.dtype),
        compiler_params=_compiler_params(_bwd_bytes(n, hd)),
        interpret=_interp(interpret),
        name="fused_attn_bwd",
    )(qkv.astype(jnp.bfloat16), do.astype(jnp.bfloat16), jnp.asarray(tbl))
    return (dqkv,)


fused_qkv_attention.defvjp(
    lambda qkv, mask, heads, scale, interpret, mask_spec:
        _fused_fwd(qkv, mask, heads, scale, interpret, mask_spec),
    _fused_bwd)
