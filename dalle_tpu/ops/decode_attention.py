"""Pallas single-token decode attention — the hot op of batched generation.

A profile of the b64 DALL·E-small decode loop on a v5e (before the
benchmark; no cell measures decode yet) showed XLA's lowering of cached
attention (dequant-multiply + dot as kLoop fusions over the int8 cache) at
~100 us/layer-step against a ~44 us HBM roofline — 67% of the whole decode
loop. Alternatives measured on-chip before landing here:
post-scale dequant restructures and int8 MXU dots in XLA (equal or worse),
a per-(b,h)-program Pallas kernel (3x worse — per-program DMA overhead),
and per-head in-kernel dots (1.7x worse — M=1 MXU staging). The winning
shape, ~59 us/iter standalone (74% of roofline):

  * ONE program per batch row over a sequence-major (S, h*d) cache block —
    a single contiguous DMA per tensor per program.
  * All heads in ONE MXU dot via a block-diagonal query: Q_bd (h, h*d) has
    q_h in diagonal block h, so Q_bd @ K^T computes every head's scores
    simultaneously; the output side uses the same mask plus a constant
    (h*d, d) gather matrix to extract each head's diagonal block.
  * int8 dequant folds into per-(h, S) row scales AFTER the score dot and
    into the probability rows BEFORE the output dot (exact: scales are
    constant along the contractions).
  * validity (j < length) and optional static-mask rows evaluate on an
    in-kernel iota; softmax is f32 throughout.

Works for int8 (with per-position scales), bf16, and f32 caches. The caller
(ops/attention.cached_attend) self-selects the kernel on TPU when shapes
tile (see ``decode_kernel_supported``) and falls back to the dense XLA path
otherwise — numerics match the dense path within f32 softmax tolerance
(tests/test_decode_attention.py, interpret mode + on-chip).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9

# per-program VMEM budget for the K+V blocks (double-buffered by the
# pipeline; the chip's scoped-vmem ceiling is 16M)
_VMEM_BUDGET = 6 * 1024 * 1024


def _decode_kernel(len_ref, q_ref, kv_ref, sc_ref, row_ref, o_ref, *,
                   scale, heads):
    h = heads
    S = kv_ref.shape[1]
    hd = kv_ref.shape[2] // 2
    d = hd // h

    # f32 caches keep exact f32 dot math; int8/bf16 storage computes in bf16
    # (already at/below storage precision; bandwidth-bound either way)
    dot_dt = (jnp.float32 if kv_ref.dtype == jnp.float32 else jnp.bfloat16)

    q = q_ref[0].astype(jnp.float32) * scale                   # (h, d)
    qt = jnp.concatenate([q] * h, axis=1)                      # (h, h*d)
    lane = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0)
    bd = (lane // d) == row                                    # block-diag mask
    qbd = jnp.where(bd, qt, 0.0).astype(dot_dt)

    k = kv_ref[0, :, :hd].astype(dot_dt)                       # (S, h*d)
    s = jax.lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (h, S)
    if sc_ref is not None:
        s = s * sc_ref[0, :h]                                  # fold K dequant
    kpos = jax.lax.broadcasted_iota(jnp.int32, (h, S), 1)
    valid = kpos < len_ref[0]
    if row_ref is not None:
        valid &= row_ref[0] != 0
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)                  # (h, S)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sc_ref is not None:
        p = p * sc_ref[0, h:]                                  # fold V dequant

    v = kv_ref[0, :, hd:].astype(dot_dt)                       # (S, h*d)
    obd = jax.lax.dot_general(p.astype(dot_dt), v,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (h, h*d)
    gr = jax.lax.broadcasted_iota(jnp.int32, (hd, d), 0)
    gc = jax.lax.broadcasted_iota(jnp.int32, (hd, d), 1)
    gather = ((gr % d) == gc).astype(jnp.float32)              # (h*d, d)
    o = jax.lax.dot_general(jnp.where(bd, obd, 0.0), gather,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (h, d)
    o_ref[0] = (o / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def decode_attend_kernel(q, cache, length, *,
                         mask_row: Optional[jnp.ndarray] = None,
                         scale: Optional[float] = None,
                         out_dtype=None,
                         interpret: Optional[bool] = None):
    """q (b,h,1,d) × KVCache (sequence-major layout — ops/attention.KVCache)
    → (b,h,1,d). ``length`` is a traced scalar; ``mask_row`` an optional (S,)
    bool/int validity row (the static mask row for this query position)."""
    b, h, _, d = q.shape
    S = cache.kv.shape[1]
    hd2 = cache.kv.shape[2]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_dtype = out_dtype or q.dtype

    quant = cache.scale is not None
    full = pl.BlockSpec((1, S, hd2), lambda ib, *_: (ib, 0, 0))
    qspec = pl.BlockSpec((1, h, d), lambda ib, *_: (ib, 0, 0))
    in_specs = [qspec, full]
    args = [q[:, :, 0, :], cache.kv]
    if quant:
        in_specs += [pl.BlockSpec((1, 2 * h, S), lambda ib, *_: (ib, 0, 0))]
        args += [cache.scale]
    if mask_row is not None:
        in_specs += [pl.BlockSpec((1, S), lambda ib, *_: (0, 0))]
        args += [mask_row.astype(jnp.int32)[None, :]]

    def kern(len_ref, *refs):
        q_ref, kv_ref = refs[0], refs[1]
        nxt = 2
        sc_ref = row_ref = None
        if quant:
            sc_ref = refs[nxt]
            nxt += 1
        if mask_row is not None:
            row_ref = refs[nxt]
            nxt += 1
        _decode_kernel(len_ref, q_ref, kv_ref, sc_ref, row_ref,
                       refs[nxt], scale=scale, heads=h)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=in_specs,
        out_specs=qspec,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), out_dtype),
        interpret=interpret,
        name="decode_attn",
    )(jnp.asarray(length, jnp.int32).reshape(1), *args)
    return out[:, :, None, :]


def decode_kernel_supported(q, cache, *, stable: bool) -> bool:
    """Shape/mode gate for the kernel path (caller falls back to dense XLA
    otherwise): 1-token query, lane-tiled cache, merged K+V block within the
    per-program VMEM budget, no stable-softmax variant (its pre-division
    changes the math the kernel hardcodes)."""
    b, h, i, d = q.shape
    S, hd2 = cache.kv.shape[1], cache.kv.shape[2]
    itemsize = jnp.dtype(cache.kv.dtype).itemsize
    # per-program VMEM: merged K+V block + (2h, S) f32 scale block on the
    # int8 path + the (1, S) i32 mask row (counted unconditionally — it is
    # noise next to the KV block and keeps this gate mask-agnostic)
    vmem_bytes = S * hd2 * itemsize + S * 4
    if cache.kv.dtype == jnp.int8:
        vmem_bytes += 2 * h * S * 4
    return (i == 1 and not stable and S % 128 == 0 and S >= 128
            and (hd2 // 2) % 128 == 0 and d % 8 == 0
            and vmem_bytes <= _VMEM_BUDGET)


# ---------------------------------------------------------------------------
# windowed multi-token variant with PER-ROW lengths: the speculative verify
# step and the serving engine's per-row decode/refill
# ---------------------------------------------------------------------------
# Same program shape as the single-token kernel — ONE program per batch row,
# one contiguous (S, 2·h·d) DMA, all dots on the MXU — but the query block
# carries w window tokens. The block-diagonal trick extends directly: the
# (w·h, h·d) query has token j / head h's vector in block h of row j·h+h, so
# ONE dot computes every (token, head) score row; causality against the
# per-row prefix AND within the window falls out of one iota compare
# (kpos <= start_b + j). Per-row starts arrive as a prefetched (b,) scalar
# vector — rows at different sequence positions ride one launch with no
# recompile, which is what makes slot-based continuous batching shape-static.


def _decode_window_kernel(starts_ref, q_ref, kv_ref, sc_ref, o_ref, *,
                          scale, heads, window):
    h, w = heads, window
    S = kv_ref.shape[1]
    hd = kv_ref.shape[2] // 2
    d = hd // h
    wh = w * h
    dot_dt = (jnp.float32 if kv_ref.dtype == jnp.float32 else jnp.bfloat16)
    start = starts_ref[pl.program_id(0)]

    q = q_ref[0].astype(jnp.float32) * scale                   # (w*h, d)
    qt = jnp.concatenate([q] * h, axis=1)                      # (w*h, h*d)
    lane = jax.lax.broadcasted_iota(jnp.int32, (wh, hd), 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (wh, hd), 0)
    bd = (lane // d) == (row % h)                              # block-diag mask
    qbd = jnp.where(bd, qt, 0.0).astype(dot_dt)

    k = kv_ref[0, :, :hd].astype(dot_dt)                       # (S, h*d)
    s = jax.lax.dot_general(qbd, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (w*h, S)
    if sc_ref is not None:
        ksc = sc_ref[0, :h]                                    # (h, S)
        s = s * jnp.concatenate([ksc] * w, axis=0)             # row j*h+h ↔ h
    kpos = jax.lax.broadcasted_iota(jnp.int32, (wh, S), 1)
    wrow = jax.lax.broadcasted_iota(jnp.int32, (wh, S), 0) // h  # window slot
    valid = kpos <= start + wrow
    s = jnp.where(valid, s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(s - m), 0.0)                  # (w*h, S)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if sc_ref is not None:
        vsc = sc_ref[0, h:]
        p = p * jnp.concatenate([vsc] * w, axis=0)             # fold V dequant

    v = kv_ref[0, :, hd:].astype(dot_dt)                       # (S, h*d)
    obd = jax.lax.dot_general(p.astype(dot_dt), v,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (w*h, h*d)
    gr = jax.lax.broadcasted_iota(jnp.int32, (hd, d), 0)
    gc = jax.lax.broadcasted_iota(jnp.int32, (hd, d), 1)
    gather = ((gr % d) == gc).astype(jnp.float32)              # (h*d, d)
    o = jax.lax.dot_general(jnp.where(bd, obd, 0.0), gather,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (w*h, d)
    o_ref[0] = (o / jnp.where(l > 0, l, 1.0)).astype(o_ref.dtype)


def decode_attend_window_kernel(q, cache, starts, *,
                                scale: Optional[float] = None,
                                out_dtype=None,
                                interpret: Optional[bool] = None):
    """q (b,h,w,d) × KVCache → (b,h,w,d) with PER-ROW absolute positions:
    query j of row b occupies position ``starts[b]+j`` and attends cache
    slots ≤ that (the cached_attend_window contract). ``starts`` is a (b,)
    traced int vector, prefetched so rows at ragged offsets share one
    compiled launch. Full causal attention only (no static-mask rows —
    matching the dense path it replaces)."""
    b, h, w, d = q.shape
    S = cache.kv.shape[1]
    hd2 = cache.kv.shape[2]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    out_dtype = out_dtype or q.dtype

    quant = cache.scale is not None
    # (b, w*h, d) row-major (token, head) — built OUTSIDE the kernel so the
    # lane→sublane reshape never happens in Mosaic
    qr = q.transpose(0, 2, 1, 3).reshape(b, w * h, d)
    qspec = pl.BlockSpec((1, w * h, d), lambda ib, *_: (ib, 0, 0))
    in_specs = [qspec, pl.BlockSpec((1, S, hd2), lambda ib, *_: (ib, 0, 0))]
    args = [qr, cache.kv]
    if quant:
        in_specs += [pl.BlockSpec((1, 2 * h, S), lambda ib, *_: (ib, 0, 0))]
        args += [cache.scale]

    def kern(starts_ref, *refs):
        sc_ref = refs[2] if quant else None
        _decode_window_kernel(starts_ref, refs[0], refs[1], sc_ref, refs[-1],
                              scale=scale, heads=h, window=w)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b,),
        in_specs=in_specs,
        out_specs=qspec,
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, w * h, d), out_dtype),
        interpret=interpret,
        name="decode_attn_window",
    )(jnp.asarray(starts, jnp.int32).reshape(b), *args)
    return out.reshape(b, w, h, d).transpose(0, 2, 1, 3)


def decode_window_kernel_supported(q, cache, *, stable: bool,
                                   max_window: int = 64) -> bool:
    """Runtime-shape gate for the windowed kernel (mirrors ``fused_fits``:
    the caller re-checks with the shapes it actually traced, so an unfit
    shape falls to the dense path rather than a failing Mosaic compile):
    lane-tiled cache, merged K+V block + the (w·h, S) f32 score tile within
    the per-program VMEM budget, no stable-softmax variant, and a bounded
    window (beyond ~64 rows the score tile stops being noise and this shape
    has never been measured)."""
    b, h, w, d = q.shape
    S, hd2 = cache.kv.shape[1], cache.kv.shape[2]
    hd = hd2 // 2
    itemsize = jnp.dtype(cache.kv.dtype).itemsize
    dot_size = 4 if cache.kv.dtype == jnp.float32 else 2
    vmem_bytes = (S * hd2 * itemsize          # merged K+V block
                  + 2 * S * hd * dot_size     # K/V upcast copies for the dots
                  + 2 * w * h * S * 4         # s/p score tiles
                  # qt/qbd/obd/masked-obd: the (w·h, h·d) f32-widened blocks
                  # the block-diag trick builds — they dominate at wide w
                  + 4 * w * h * hd * 4
                  + 2 * w * h * d * 4)        # q in / o out
    if cache.kv.dtype == jnp.int8:
        vmem_bytes += 2 * h * S * 4
    return (1 <= w <= max_window and not stable
            and S % 128 == 0 and S >= 128
            and (hd2 // 2) % 128 == 0 and d % 8 == 0
            and vmem_bytes <= _VMEM_BUDGET)


def decode_attend_window_paged(q, cache, starts, *,
                               scale=None, out_dtype=None, interpret=None):
    """Windowed decode attention over a PAGED cache (graftpage,
    ops/paged_kv.PagedKVCache): gather the block pool through the page table
    back into the dense (b, max_seq, 2hd) slab layout, then launch the SAME
    windowed kernel as the dense path — per-row starts still ride the
    prefetched scalar vector, the page table stays device data (an int32
    gather operand, never a shape), so admission/COW/eviction never change
    this program's signature.

    The gather-then-kernel split is deliberate: XLA fuses the take into the
    kernel's operand stream, and keeping the kernel body page-oblivious
    means the dense and paged paths share one Mosaic program — the bitwise
    exactness argument (identical attend math on identical valid lanes)
    holds at the kernel level too. An in-kernel per-block DMA gather is the
    follow-on once Mosaic's dynamic-slice-from-SMEM lands for this shape
    family; the graftir entry ``decode_attend_window_paged`` pins today's
    gather so that swap shows up as an intentional golden diff."""
    dense = cache.gather_dense()
    return decode_attend_window_kernel(q, dense, starts, scale=scale,
                                       out_dtype=out_dtype,
                                       interpret=interpret)
