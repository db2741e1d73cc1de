"""Offset-parameterized flash chunk kernels — the ring-attention inner step.

These are the Pallas building blocks that let sequence parallelism
(parallel/ring_attention.py) run each (q-chunk, k-chunk) pair flash-style
instead of materializing (n_local, n_local) f32 score tensors per ring step.
The reference has no sequence parallelism at all (SURVEY.md §5.7) — this is
beyond-reference capability; the design target is the repo's own dense ring
body, whose per-step score materialization capped the chunk size a device
could hold.

Differences from the full-sequence kernels (ops/flash_attention.py):
  * Global positions are ``offset + local``: the chunk's global q/k offsets
    arrive as *traced scalars* via scalar prefetch (SMEM), because inside
    ``shard_map`` the device index — and therefore the chunk origin — is a
    traced value. The full-sequence kernels bake positions into the grid.
  * No host-side block lists: causal + sequence-validity block skipping is
    computed *in kernel* from the SMEM offsets (per-q-block `hi` bound for
    the forward/dq loops, per-k-block `lo` bound for dkv). A chunk wholly in
    a query block's future costs one launch with a zero-trip loop.
  * The forward returns (o, lse) per chunk pair; the caller merges chunks
    online with logaddexp weights (numerically the same online softmax the
    in-kernel loop uses, lifted one level up). Empty rows get lse = -1e9 so
    their merge weight is exactly zero.
  * Structured mask specs (axial/conv — flash_attention.elem_fn_from_spec)
    evaluate on *global* positions, so the same element test that serves the
    single-chip kernels extends sequence parallelism beyond full-causal.

All three kernels recompute scores from (q, k) — the ring's custom_vjp saves
only (q, k, v, o, lse) per device, giving the O(n_local) residual footprint
that makes sp a real memory lever (tests/test_ring_attention.py asserts the
compiled peak-memory scaling).
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def pick_block(n: int, cap: int = 256) -> Optional[int]:
    """Largest power-of-two divisor of ``n`` up to ``cap``; None if no tiling
    ≥ 8 exists (the ring falls back to its dense body for tiny chunks)."""
    b = 1
    while b * 2 <= min(n, cap) and n % (b * 2) == 0:
        b *= 2
    return b if b >= 8 else None


def _qblock(d, bq):
    return pl.BlockSpec((1, 1, bq, d), lambda ib, ih, i, *_: (ib, ih, i, 0))


def _full(n, d):
    return pl.BlockSpec((1, 1, n, d), lambda ib, ih, i, *_: (ib, ih, 0, 0))


def _lane(n):
    return pl.BlockSpec((1, 1, n, 128), lambda ib, ih, i, *_: (ib, ih, 0, 0))


def _hi_blocks(q_off, k_off, iq, bq, bk, nk, n_valid, causal):
    """Number of leading k blocks this q block must visit (scalar math on the
    SMEM offsets): bounded by sequence validity and, when causal, by the q
    block's last global row."""
    hi = (n_valid - k_off + bk - 1) // bk
    if causal:
        hi = jnp.minimum(hi, (q_off + (iq + 1) * bq - 1 - k_off) // bk + 1)
    return jnp.clip(hi, 0, nk)


def _chunk_fwd_kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      scale, block_k, nk, n_valid, causal, elem_fn):
    iq = pl.program_id(2)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * scale
    q_off, k_off = off_ref[0], off_ref[1]
    qpos = q_off + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 0)

    def body(jb, carry):
        acc, m, l = carry
        k = k_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k_off + jb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = kpos < n_valid
        if causal:
            valid &= kpos <= qpos
        if elem_fn is not None:
            valid &= elem_fn(qpos, kpos)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc, m_new, l

    hi = _hi_blocks(q_off, k_off, iq, bq, block_k, nk, n_valid, causal)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, hi, body, (acc0, m0, l0))
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)
    # empty rows → -1e9: the caller's logaddexp merge weights them to zero
    # (the single-chip kernel uses +1e9 here — that is the *final* lse fed to
    # backward; the ring flips sign once after the last merge)
    lse = jnp.where(l > 0, m + jnp.log(safe_l), NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:]).astype(jnp.float32)


def _chunk_dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                     dq_ref, *, scale, block_k, nk, n_valid, causal, elem_fn):
    iq = pl.program_id(2)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]
    q_off, k_off = off_ref[0], off_ref[1]
    qpos = q_off + iq * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, block_k), 0)

    def body(jb, dq):
        k = k_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = k_off + jb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = kpos < n_valid
        if causal:
            valid &= kpos <= qpos
        if elem_fn is not None:
            valid &= elem_fn(qpos, kpos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    hi = _hi_blocks(q_off, k_off, iq, bq, block_k, nk, n_valid, causal)
    dq = jax.lax.fori_loop(0, hi, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _chunk_dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dk_ref, dv_ref, *, scale, block_q, nq, n_valid, causal,
                      elem_fn):
    jk = pl.program_id(2)
    bk, d = dk_ref.shape[2], dk_ref.shape[3]
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    q_off, k_off = off_ref[0], off_ref[1]
    kpos = k_off + jk * bk + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, bk), 1)

    def body(ib, carry):
        dk, dv = carry
        q = q_ref[0, 0, pl.ds(ib * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(ib * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(ib * block_q, block_q), :][:, :1]
        delta = delta_ref[0, 0, pl.ds(ib * block_q, block_q), :][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q_off + ib * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        valid = kpos < n_valid
        if causal:
            valid &= kpos <= qpos
        if elem_fn is not None:
            valid &= elem_fn(qpos, kpos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    # first q block with any row not before this k block's first global column
    lo = jnp.int32(0)
    if causal:
        lo = jnp.clip((k_off + jk * bk - q_off) // block_q, 0, nq)
    z = jnp.zeros((bk, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(lo, nq, body, (z, z))
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


def _interp(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def chunk_flash_fwd(q, k, v, q_off, k_off, *, scale: float, n_valid: int,
                    causal: bool = True, block_q: int, block_k: int,
                    elem_fn: Optional[Callable] = None,
                    interpret: Optional[bool] = None):
    """Flash forward over one (q-chunk, k-chunk) pair at traced global
    offsets. Returns (o_f32, lse) with lse shape (b, h, nq); empty rows carry
    lse = -1e9 (zero weight under the caller's logaddexp merge)."""
    b, h, nq_, d = q.shape
    nk_ = k.shape[2]
    nq, nk = nq_ // block_q, nk_ // block_k
    offs = jnp.stack([jnp.asarray(q_off), jnp.asarray(k_off)]).astype(jnp.int32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq),
        in_specs=[_qblock(d, block_q), _full(nk_, d), _full(nk_, d)],
        out_specs=[_qblock(d, block_q),
                   pl.BlockSpec((1, 1, block_q, 128),
                                lambda ib, ih, i, *_: (ib, ih, i, 0))],
    )
    o, lse = pl.pallas_call(
        functools.partial(_chunk_fwd_kernel, scale=scale, block_k=block_k,
                          nk=nk, n_valid=n_valid, causal=causal,
                          elem_fn=elem_fn),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, nq_, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, nq_, 128), jnp.float32)],
        interpret=_interp(interpret),
        name="chunk_attn_fwd",
    )(offs, q, k, v)
    return o, lse[..., 0]


def chunk_flash_dq(q, k, v, do, lse, delta, q_off, k_off, *, scale: float,
                   n_valid: int, causal: bool = True, block_q: int,
                   block_k: int, elem_fn: Optional[Callable] = None,
                   interpret: Optional[bool] = None):
    """dq contribution of one chunk pair. ``lse``/``delta``: (b, h, nq)."""
    b, h, nq_, d = q.shape
    nk_ = k.shape[2]
    nq, nk = nq_ // block_q, nk_ // block_k
    offs = jnp.stack([jnp.asarray(q_off), jnp.asarray(k_off)]).astype(jnp.int32)
    lse128 = jnp.broadcast_to(lse[..., None], (b, h, nq_, 128))
    delta128 = jnp.broadcast_to(delta[..., None], (b, h, nq_, 128))
    lane_q = pl.BlockSpec((1, 1, block_q, 128),
                          lambda ib, ih, i, *_: (ib, ih, i, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nq),
        in_specs=[_qblock(d, block_q), _full(nk_, d), _full(nk_, d),
                  _qblock(d, block_q), lane_q, lane_q],
        out_specs=_qblock(d, block_q),
    )
    return pl.pallas_call(
        functools.partial(_chunk_dq_kernel, scale=scale, block_k=block_k,
                          nk=nk, n_valid=n_valid, causal=causal,
                          elem_fn=elem_fn),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, nq_, d), jnp.float32),
        interpret=_interp(interpret),
        name="chunk_attn_dq",
    )(offs, q, k, v, do, lse128, delta128)


def chunk_flash_dkv(q, k, v, do, lse, delta, q_off, k_off, *, scale: float,
                    n_valid: int, causal: bool = True, block_q: int,
                    block_k: int, elem_fn: Optional[Callable] = None,
                    interpret: Optional[bool] = None):
    """(dk, dv) contribution of the held k chunk from the local q chunk."""
    b, h, nq_, d = q.shape
    nk_ = k.shape[2]
    nq, nk = nq_ // block_q, nk_ // block_k
    offs = jnp.stack([jnp.asarray(q_off), jnp.asarray(k_off)]).astype(jnp.int32)
    lse128 = jnp.broadcast_to(lse[..., None], (b, h, nq_, 128))
    delta128 = jnp.broadcast_to(delta[..., None], (b, h, nq_, 128))
    kblock = pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, j, *_: (ib, ih, j, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, nk),
        in_specs=[_full(nq_, d), kblock, kblock, _full(nq_, d),
                  _lane(nq_), _lane(nq_)],
        out_specs=[kblock, kblock],
    )
    return pl.pallas_call(
        functools.partial(_chunk_dkv_kernel, scale=scale, block_q=block_q,
                          nq=nq, n_valid=n_valid, causal=causal,
                          elem_fn=elem_fn),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, h, nk_, d), jnp.float32),
                   jax.ShapeDtypeStruct((b, h, nk_, d), jnp.float32)],
        interpret=_interp(interpret),
        name="chunk_attn_dkv",
    )(offs, q, k, v, do, lse128, delta128)


def merge_chunk(o, lse, o_t, lse_t):
    """Online logaddexp merge of per-chunk flash results: exact streaming
    softmax combination. Empty contributions (lse == -1e9) get weight 0."""
    lse_new = jnp.logaddexp(lse, lse_t)
    w1 = jnp.exp(lse - lse_new)[..., None]
    w2 = jnp.exp(lse_t - lse_new)[..., None]
    return o * w1 + o_t * w2, lse_new
