"""Pallas TPU flash attention with static-mask block sparsity.

Reference capability: the dense causal `Attention` (dalle_pytorch/attention.py:39-99)
and the DeepSpeed block-sparse CUDA kernel it wraps (`SparseSelfAttention`,
attention.py:339-398) — see SURVEY.md §2.9. This module is the TPU-native
replacement for both, and also accelerates the axial/conv-like variants, which
the framework represents as static masks (ops/attn_masks.py).

Design (one kernel family, sparsity by block skipping):
  * Tiled online-softmax flash attention: q blocks stream against k/v blocks,
    accumulating (acc, running max, running sum) — O(n) memory, MXU-shaped
    (block_q × d) @ (d × block_k) matmuls in fp32 accumulation.
  * Any static (seq, seq) boolean mask is lowered host-side to *block lists*:
    for each q block, the list of k blocks with any visible entry (and the
    transpose for the backward dk/dv kernel). The lists ride scalar prefetch
    (SMEM, `PrefetchScalarGridSpec`) and the kernel loops only over listed
    blocks — inactive blocks are never touched, which is exactly the DeepSpeed
    variable-sparsity skip, retiled to the 128-lane TPU geometry.
  * Element-level masking inside a visited block is recomputed from the mask
    constant + causal iota compare, fused into the softmax epilogue by Mosaic.
  * Backward is the standard two-kernel flash backward (dq by q-block rows,
    dk/dv by k-block columns) over the same block lists, wrapped in
    `jax.custom_vjp`; the forward saves only (o, lse).

The kernels run in interpret mode automatically off-TPU so the test suite
exercises them on CPU (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e9


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


class BlockLists(NamedTuple):
    """Host-side (numpy) sparsity schedule for the kernels."""
    k_ids: np.ndarray    # (nq, max_k)  active k-block ids per q block
    k_cnt: np.ndarray    # (nq,)        how many of k_ids are valid
    q_ids: np.ndarray    # (nk, max_q)  active q-block ids per k block
    q_cnt: np.ndarray    # (nk,)


def build_block_lists(n_pad: int, block_q: int, block_k: int,
                      mask: Optional[np.ndarray] = None,
                      causal: bool = True) -> BlockLists:
    """Lower a (seq, seq) boolean mask (True = may attend) to block lists.
    ``mask`` may be smaller than n_pad — padded rows/cols count as invisible."""
    nq, nk = n_pad // block_q, n_pad // block_k
    vis = np.zeros((n_pad, n_pad), dtype=bool)
    if mask is not None:
        # the mask may be larger than the runtime sequence (e.g. built for
        # seq_len+1 while training feeds seq_len after dropping the last
        # token, reference dalle_pytorch.py:608-613) — trim to n_pad
        s = min(mask.shape[0], n_pad)
        vis[:s, :s] = mask[:s, :s]
    else:
        vis[:, :] = True
    if causal:
        vis &= np.tril(np.ones((n_pad, n_pad), dtype=bool))
    blk = vis.reshape(nq, block_q, nk, block_k).any(axis=(1, 3))

    def lists(b):
        rows = [np.nonzero(r)[0] for r in b]
        mx = max((len(r) for r in rows), default=1) or 1
        ids = np.zeros((b.shape[0], mx), dtype=np.int32)
        cnt = np.zeros((b.shape[0],), dtype=np.int32)
        for i, r in enumerate(rows):
            ids[i, :len(r)] = r
            cnt[i] = len(r)
        return ids, cnt

    k_ids, k_cnt = lists(blk)
    q_ids, q_cnt = lists(blk.T)
    return BlockLists(k_ids, k_cnt, q_ids, q_cnt)


def elem_fn_from_spec(spec):
    """Build the in-kernel element visibility test for a *structured* mask
    spec — ("axial", text_len, fmap, axis) or ("conv", text_len, fmap,
    kernel, dilation). Structured masks are pure functions of (qpos, kpos),
    so the kernels compute them from iotas instead of loading a
    (block, n_pad) int32 mask row per grid step — that row was as much VMEM
    traffic as the scores themselves (see ops/attn_masks.py for the table
    semantics these reproduce)."""
    if spec is None:
        return None
    kind = spec[0]
    if kind == "block":
        # block-aligned pattern (e.g. the DeepSpeed-style random-block
        # 'sparse' variant): every kernel tile is either wholly visible or
        # wholly skipped by the block lists, so no element test is needed —
        # flash_attention pins the kernel block size to the pattern's
        return None
    if kind == "axial":
        _, text_len, fmap, axis = spec

        def fn(qpos, kpos):
            qi, ki = qpos - text_len, kpos - text_len
            if axis == 0:
                same = (qi // fmap) == (ki // fmap)
            else:
                same = (qi % fmap) == (ki % fmap)
            img_pair = (qpos >= text_len) & (kpos >= text_len)
            return (kpos < text_len) | (img_pair & same)
        return fn
    if kind == "conv":
        _, text_len, fmap, kernel, dil = spec
        span = (kernel - 1) * dil

        def fn(qpos, kpos):
            qi, ki = qpos - text_len, kpos - text_len
            dr = qi // fmap - ki // fmap
            dc = qi % fmap - ki % fmap
            win = (dr >= 0) & (dr <= span) & (dc >= 0) & (dc <= span)
            if dil > 1:
                win &= (dr % dil == 0) & (dc % dil == 0)
            img_pair = (qpos >= text_len) & (kpos >= text_len)
            return (kpos < text_len) | (img_pair & win)
        return fn
    raise ValueError(f"unknown mask spec {spec!r}")


# ---------------------------------------------------------------------------
# kernels (grid = (b, h, n_blocks); block lists in SMEM via scalar prefetch)
# ---------------------------------------------------------------------------

def _fwd_kernel(ids_ref, cnt_ref, q_ref, k_ref, v_ref, *rest,
                scale, block_k, n_valid, causal, has_mask, elem_fn=None):
    if has_mask:
        mask_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
    iq = pl.program_id(2)
    bq, d = q_ref.shape[2], v_ref.shape[3]          # d: the value width
    q = q_ref[0, 0].astype(jnp.float32) * scale                    # (bq, dk)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(t, carry):
        acc, m, l = carry
        jb = ids_ref[iq, t]
        k = k_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = jb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = kpos < n_valid
        if causal:
            valid &= kpos <= qpos
        if has_mask:
            valid &= mask_ref[:, pl.ds(jb * block_k, block_k)] > 0
        elif elem_fn is not None:
            valid &= elem_fn(qpos, kpos)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # for a fully-masked row m_new == NEG_INF and exp(s - m_new) would be
        # exp(0) == 1 — force masked entries to 0 so l stays 0 and the
        # empty-row guard below fires (valid scores never approach NEG_INF/2)
        p = jnp.where(s > 0.5 * NEG_INF, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        return acc, m_new, l

    acc0 = jnp.zeros((bq, d), jnp.float32)
    m0 = jnp.full((bq, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, cnt_ref[iq], body, (acc0, m0, l0))
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (acc / safe_l).astype(o_ref.dtype)
    # rows with no visible key get a huge lse so backward p == 0; lane-
    # replicated (bq, 128) layout per the TPU tiling rules
    lse = jnp.where(l > 0, m + jnp.log(safe_l), -NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:]).astype(jnp.float32)


def _bwd_dq_kernel(ids_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, *rest, scale, block_k, n_valid, causal,
                   has_mask, elem_fn=None):
    if has_mask:
        mask_ref, dq_ref = rest
    else:
        (dq_ref,) = rest
    iq = pl.program_id(2)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0].astype(jnp.float32) * scale
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0][:, :1]
    delta = delta_ref[0, 0][:, :1]
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)

    def body(t, dq):
        jb = ids_ref[iq, t]
        k = k_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(jb * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kpos = jb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (bq, block_k), 1)
        valid = kpos < n_valid
        if causal:
            valid &= kpos <= qpos
        if has_mask:
            valid &= mask_ref[:, pl.ds(jb * block_k, block_k)] > 0
        elif elem_fn is not None:
            valid &= elem_fn(qpos, kpos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, cnt_ref[iq], body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(ids_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, *rest, scale, block_q, n_valid, causal,
                    has_mask, elem_fn=None):
    if has_mask:
        mask_ref, dk_ref, dv_ref = rest
    else:
        dk_ref, dv_ref = rest
    jk = pl.program_id(2)
    bk, d = dk_ref.shape[2], dk_ref.shape[3]
    k = k_ref[0, 0].astype(jnp.float32)                            # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    kpos = jk * bk + jax.lax.broadcasted_iota(jnp.int32, (block_q, bk), 1)

    def body(t, carry):
        dk, dv = carry
        ib = ids_ref[jk, t]
        q = q_ref[0, 0, pl.ds(ib * block_q, block_q), :].astype(jnp.float32) * scale
        do = do_ref[0, 0, pl.ds(ib * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(ib * block_q, block_q), :][:, :1]
        delta = delta_ref[0, 0, pl.ds(ib * block_q, block_q), :][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = ib * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, bk), 0)
        valid = kpos < n_valid
        if causal:
            valid &= kpos <= qpos
        if has_mask:
            valid &= mask_ref[pl.ds(ib * block_q, block_q), :] > 0
        elif elem_fn is not None:
            valid &= elem_fn(qpos, kpos)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)                                       # (blkq, bk)
        dv = dv + jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), jnp.float32)
    zv = (z if dv_ref.shape[3] == d
          else jnp.zeros((bk, dv_ref.shape[3]), jnp.float32))
    dk, dv = jax.lax.fori_loop(0, cnt_ref[jk], body, (z, zv))
    # q was pre-scaled inside body, so dk = dS^T (scale·Q) is already complete
    dk_ref[0, 0] = dk.astype(dk_ref.dtype)
    dv_ref[0, 0] = dv.astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side wrapper with custom_vjp
# ---------------------------------------------------------------------------

def _qblock_spec(d, bq):
    return pl.BlockSpec((1, 1, bq, d), lambda ib, ih, i, *_: (ib, ih, i, 0))


def _full_spec(n_pad, d):
    return pl.BlockSpec((1, 1, n_pad, d), lambda ib, ih, i, *_: (ib, ih, 0, 0))


@functools.lru_cache(maxsize=64)
def _make_flash_fn(n: int, n_pad: int, block_q: int, block_k: int,
                   causal: bool, mask_key, interpret: bool,
                   mask_spec=None):
    """Build the custom_vjp flash function for one (seq, mask) geometry.
    ``mask_key`` is (bytes, shape) of the numpy mask, or None. A structured
    ``mask_spec`` replaces the element-mask operand with an in-kernel test
    (block lists still come from the numpy mask)."""
    if mask_key is None:
        mask_np = None
    else:
        buf, shape = mask_key
        mask_np = np.frombuffer(buf, dtype=bool).reshape(shape)
    lists = build_block_lists(n_pad, block_q, block_k, mask_np, causal)
    # with no element mask (pure causal / padding handled by iota compares)
    # the kernels take no mask operand at all — the (block_q, n_pad) int32
    # mask row was as much VMEM traffic per grid step as the scores
    # themselves, and the dkv kernel's scoped VMEM overflowed at long seq
    elem_fn = elem_fn_from_spec(mask_spec)
    has_mask = mask_np is not None and mask_spec is None
    # int32 mask: Mosaic v5e has no i8 or packed-bf16 vector compare, so 4
    # bytes/entry is the narrowest workable element mask; long-seq masked
    # configs therefore top out at block 128/256 (VMEM), which the tuner picks.
    # Only allocated when a kernel actually takes the operand — an (n_pad,
    # n_pad) int32 table pinned in this lru-cached closure is ~85MB at seq 4k.
    # Keep closure constants as NUMPY: jnp conversion inside a jit trace would
    # capture per-trace tracers in the lru-cached closure (leaked-tracer error)
    mask_c = None
    if has_mask:
        mask_c = np.zeros((n_pad, n_pad), dtype=np.int32)
        s = min(mask_np.shape[0], n_pad)
        mask_c[:s, :s] = mask_np[:s, :s]
    k_ids, k_cnt = lists.k_ids, lists.k_cnt
    q_ids, q_cnt = lists.q_ids, lists.q_cnt
    nq, nk = n_pad // block_q, n_pad // block_k

    def pad(t):
        return jnp.pad(t, ((0, 0), (0, 0), (0, n_pad - n), (0, 0)))

    # q and k are ``d`` wide, v and o (and their gradients) ``dv``: latent
    # attention's keys carry a rotary part its values have not
    def _fwd_call(q, k, v, scale):
        b, h, _, d = q.shape
        dv = v.shape[-1]
        in_specs = [
            _qblock_spec(d, block_q),
            _full_spec(n_pad, d),
            _full_spec(n_pad, dv),
        ]
        operands = [k_ids, k_cnt, q, k, v]
        if has_mask:
            in_specs.append(
                pl.BlockSpec((block_q, n_pad), lambda ib, ih, i, *_: (i, 0)))
            operands.append(mask_c)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nq),
            in_specs=in_specs,
            out_specs=[
                _qblock_spec(dv, block_q),
                pl.BlockSpec((1, 1, block_q, 128),
                             lambda ib, ih, i, *_: (ib, ih, i, 0)),
            ],
        )
        return pl.pallas_call(
            functools.partial(_fwd_kernel, scale=scale, block_k=block_k,
                              n_valid=n, causal=causal, has_mask=has_mask,
                              elem_fn=elem_fn),
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, h, n_pad, dv), q.dtype),
                jax.ShapeDtypeStruct((b, h, n_pad, 128), jnp.float32),
            ],
            interpret=interpret,
            name="flash_attn_fwd",
        )(*operands)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
    def flash(q, k, v, scale):
        o, _ = _fwd_call(pad(q), pad(k), pad(v), scale)
        return o[:, :, :n]

    def flash_fwd(q, k, v, scale):
        qp, kp, vp = pad(q), pad(k), pad(v)
        o, lse = _fwd_call(qp, kp, vp, scale)
        return o[:, :, :n], (qp, kp, vp, o, lse)

    def flash_bwd(scale, res, g):
        qp, kp, vp, o, lse = res
        b, h, _, d = qp.shape
        dv = vp.shape[-1]
        gp = pad(g)
        delta = jnp.sum(gp.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                                   # (b,h,n_pad)
        delta = jnp.broadcast_to(delta[..., None], delta.shape + (128,))
        lse_qspec = pl.BlockSpec((1, 1, block_q, 128),
                                 lambda ib, ih, i, *_: (ib, ih, i, 0))
        dq_in_specs = [
            _qblock_spec(d, block_q),
            _full_spec(n_pad, d),
            _full_spec(n_pad, dv),
            _qblock_spec(dv, block_q),
            lse_qspec,
            lse_qspec,
        ]
        dq_operands = [k_ids, k_cnt, qp, kp, vp, gp, lse, delta]
        if has_mask:
            dq_in_specs.append(
                pl.BlockSpec((block_q, n_pad), lambda ib, ih, i, *_: (i, 0)))
            dq_operands.append(mask_c)
        dq_grid = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nq),
            in_specs=dq_in_specs,
            out_specs=_qblock_spec(d, block_q),
        )
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, scale=scale, block_k=block_k,
                              n_valid=n, causal=causal, has_mask=has_mask,
                              elem_fn=elem_fn),
            grid_spec=dq_grid,
            out_shape=jax.ShapeDtypeStruct((b, h, n_pad, d), qp.dtype),
            interpret=interpret,
            name="flash_attn_dq",
        )(*dq_operands)

        def kblock_spec(width):
            return pl.BlockSpec((1, 1, block_k, width),
                                lambda ib, ih, j, *_: (ib, ih, j, 0))
        lse_fullspec = pl.BlockSpec((1, 1, n_pad, 128),
                                    lambda ib, ih, j, *_: (ib, ih, 0, 0))
        dkv_in_specs = [
            _full_spec(n_pad, d),
            kblock_spec(d),
            kblock_spec(dv),
            _full_spec(n_pad, dv),
            lse_fullspec,
            lse_fullspec,
        ]
        dkv_operands = [q_ids, q_cnt, qp, kp, vp, gp, lse, delta]
        if has_mask:
            dkv_in_specs.append(
                pl.BlockSpec((n_pad, block_k), lambda ib, ih, j, *_: (0, j)))
            dkv_operands.append(mask_c)
        dkv_grid = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, nk),
            in_specs=dkv_in_specs,
            out_specs=[kblock_spec(d), kblock_spec(dv)],
        )
        # whole rows of q, dO, lse and delta stay resident beside a key
        # block: with keys wider than a lane tile (latent attention's 192)
        # that passes Mosaic's default scoped-VMEM ceiling of 16M by 0.4M at
        # 4352 positions. The ceiling is a compiler default, not hardware
        # (ops/fused_attention.py asks for the same 32M)
        wide = ({"compiler_params": pltpu.CompilerParams(
            vmem_limit_bytes=32 * 1024 * 1024)} if d > 128 else {})
        dk, dv = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, scale=scale, block_q=block_q,
                              n_valid=n, causal=causal, has_mask=has_mask,
                              elem_fn=elem_fn),
            grid_spec=dkv_grid,
            out_shape=[
                jax.ShapeDtypeStruct((b, h, n_pad, d), qp.dtype),
                jax.ShapeDtypeStruct((b, h, n_pad, dv), qp.dtype),
            ],
            interpret=interpret,
            name="flash_attn_dkv",
            **wide,
        )(*dkv_operands)
        return dq[:, :, :n], dk[:, :, :n], dv[:, :, :n]

    flash.defvjp(flash_fwd, flash_bwd)
    return flash


def sparsity_fraction(n: int, block_q: int = 128, block_k: int = 128,
                      mask: Optional[np.ndarray] = None,
                      causal: bool = True) -> float:
    """Fraction of (q,k) blocks actually visited — the compute saving."""
    n_pad = _ceil_to(n, max(block_q, block_k))
    lists = build_block_lists(n_pad, block_q, block_k, mask, causal)
    nq, nk = n_pad // block_q, n_pad // block_k
    return float(lists.k_cnt.sum()) / float(nq * nk)


def _auto_block(n: int, has_mask: bool) -> int:
    """Block sizes read on a v5e before the benchmark (fwd+bwd, bf16):
    mask-free kernels carry no element-mask operand so bigger blocks fit;
    masked kernels hold a (block, n_pad) int32 mask row and hit the 16M
    scoped-VMEM limit earlier as n grows."""
    if has_mask:
        blk = 256 if n <= 2560 else 128
    else:
        blk = 512 if n <= 2560 else 256
    return min(blk, max(128, _ceil_to(n, 128)))


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    mask: Optional[np.ndarray] = None,
                    mask_spec=None,
                    causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    scale: Optional[float] = None,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Flash attention over (b, h, n, d) with optional static (n, n) bool mask.
    ``q`` and ``k`` share one width and ``v`` may have another (latent
    attention: 192 against 128); the output is ``v``'s.

    Replaces reference dense attention (attention.py:58-99) AND the DeepSpeed
    block-sparse kernel (attention.py:339-398): blocks with no visible entry
    are skipped entirely via host-precomputed block lists.

    ``mask`` must be host-side numpy (it is a compile-time sparsity pattern).
    ``block_q``/``block_k`` default to measured-on-v5e auto sizes.
    ``interpret`` defaults to True off-TPU so tests run on CPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    n = q.shape[2]
    if mask_spec is not None and mask_spec[0] == "block":
        if int(mask_spec[1]) % 128 != 0:
            # a non-lane-aligned pattern block (e.g. the reference's size 16,
            # attention.py:358) would force tiny Mosaic tiles — a lowering
            # failure/perf cliff on real TPU. Fall back to the tabled
            # element-mask path, which handles arbitrary masks at 128+ tiles.
            mask_spec = None
        else:
            # block-aligned pattern: kernel tiles must coincide with the
            # pattern's block grid for the no-element-mask shortcut to be exact
            block_q = block_k = int(mask_spec[1])
    # a structured spec carries no element-mask operand: auto blocks use the
    # roomier mask-free VMEM budget
    tabled = mask is not None and mask_spec is None
    if block_q is None:
        block_q = _auto_block(n, tabled)
    if block_k is None:
        block_k = _auto_block(n, tabled)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n_pad = _ceil_to(n, max(block_q, block_k))
    if mask is not None:
        assert isinstance(mask, np.ndarray), "mask must be host-side numpy"
        mask_key = (mask.astype(bool).tobytes(), mask.shape)
    else:
        mask_key = None
    fn = _make_flash_fn(n, n_pad, block_q, block_k, causal, mask_key,
                        interpret, mask_spec)
    return fn(q, k, v, float(scale))
