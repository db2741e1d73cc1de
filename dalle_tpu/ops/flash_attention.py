"""Pallas TPU flash attention with static-mask block sparsity.

Reference capability: the dense causal `Attention` (dalle_pytorch/attention.py:39-99)
and the DeepSpeed block-sparse CUDA kernel it wraps (`SparseSelfAttention`,
attention.py:339-398) — see SURVEY.md §2.9. This module is the TPU-native
replacement for both, and also accelerates the axial/conv-like variants, which
the framework represents as static masks (ops/attn_masks.py).

Design (one kernel family, sparsity by block skipping):
  * Tiled online-softmax flash attention: q blocks stream against k/v blocks,
    accumulating (acc, running max, running sum) — O(n) memory.
  * Products in the inputs' own type: q, k, v and dO go to the MXU as they
    arrive (bfloat16 in the training cells, float32 in the CPU tests), p and
    dS are rounded to that type once, just before their products, and every
    product sums in float32 (`_dot`); running maximum and sum, lse, delta and
    the accumulators stay float32. The softmax scale multiplies the float32
    scores, never a bfloat16 q. No flag: the type is read from the input.
    (Measured on the v5e, PR 35: Mosaic already multiplied float32 operands
    in one bfloat16 pass, so this alone moved nothing; it halves the vregs
    the operands hold.)
  * Any static (seq, seq) boolean mask is lowered host-side to *block lists*:
    for each q block, the list of k blocks with any visible entry (and the
    transpose for the backward dk/dv kernel). The lists ride scalar prefetch
    (SMEM, `PrefetchScalarGridSpec`) and the kernel loops only over listed
    blocks — inactive blocks are never touched, which is exactly the DeepSpeed
    variable-sparsity skip, retiled to the 128-lane TPU geometry.
  * Element-level masking inside a visited block is recomputed from iotas,
    the mask table or the structured `elem_fn`, in EVERY visited block. A
    second loop body without it for the wholly visible blocks (136 of the 153
    visited for a causal 4352 at 256; `flash_block_counts` counts them) was
    measured on the v5e at PR 35 and left out: the three kernels together read
    1.1 % slower with it (dq and dk/dv 1-4 % slower for the longer program,
    the forward 1-5 % faster): the mask's selects hide behind the products
    and the exponentials.
  * Orientation — what did move the kernels (PR 35, v5e readings of the
    three alone with the next entry's overlap: 42.3 -> 34.5 ms at 2 x 64
    heads of 128, 22.1 -> 19.1 at 2 x 32 of 192 / 128): the forward and the
    dk/dv kernel hold their scores TRANSPOSED, keys down the sublanes and
    queries along the lanes. The forward's running maximum and sum are then
    (1, bq) rows, reduced down the sublanes on the VALU (as (bq, 1) columns
    they were 32 vregs each and 542 XLU slots a block); the dk/dv kernel's
    two sums over queries are plain products (no transpose of p and dS) and
    lse, delta are rows. The dq kernel keeps queries down the sublanes and
    reads lse and delta as the lane-replicated tiles they arrive as.
  * MXU overlap in the forward: it forms the NEXT block's scores while the
    vector units work on this block's softmax, so both products keep the MXU
    busy together; the last listed block, which has no next one, is taken out
    of the loop (tried in dq and dk/dv too: slower there).
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


def _visible_tiles(n_pad: int, block_q: int, block_k: int,
                   mask: Optional[np.ndarray], causal: bool,
                   n_valid: Optional[int]) -> np.ndarray:
    """The (n_pad, n_pad) visibility table as (nq, block_q, nk, block_k)
    tiles. ``mask`` may be smaller than n_pad — padded rows/cols count as
    invisible, and so do the keys from ``n_valid`` on (the sequence's own
    length, where it is not a block multiple)."""
    vis = np.zeros((n_pad, n_pad), dtype=bool)
    if mask is not None:
        # the mask may be larger than the runtime sequence (e.g. built for
        # seq_len+1 while training feeds seq_len after dropping the last
        # token, reference dalle_pytorch.py:608-613) — trim to n_pad
        s = min(mask.shape[0], n_pad)
        vis[:s, :s] = mask[:s, :s]
    else:
        vis[:, :] = True
    if n_valid is not None:
        vis[:, n_valid:] = False
    if causal:
        vis &= np.tril(np.ones((n_pad, n_pad), dtype=bool))
    return vis.reshape(n_pad // block_q, block_q, n_pad // block_k, block_k)


def build_block_lists(n_pad: int, block_q: int, block_k: int,
                      mask: Optional[np.ndarray] = None,
                      causal: bool = True,
                      n_valid: Optional[int] = None) -> BlockLists:
    """Lower a (seq, seq) boolean mask (True = may attend) to block lists:
    a block is listed when any entry of it is visible."""
    blk = _visible_tiles(n_pad, block_q, block_k, mask, causal,
                         n_valid).any(axis=(1, 3))

    def lists(b):
        rows = [np.nonzero(r)[0] for r in b]
        mx = max((len(r) for r in rows), default=1) or 1
        ids = np.zeros((b.shape[0], mx), dtype=np.int32)
        cnt = np.zeros((b.shape[0],), dtype=np.int32)
        for i, r in enumerate(rows):
            ids[i, :len(r)] = r
            cnt[i] = len(r)
        return ids, cnt

    return BlockLists(*lists(blk), *lists(blk.T))


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

def _dot(a, b, contract):
    """A product of operands in their own type with float32 sums."""
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NT = ((1,), (1,))      # a bᵀ
_NN = ((1,), (0,))      # a b
_TN = ((0,), (0,))      # aᵀ b


def _visible(first_q, first_k, shape, q_axis, n_valid, causal, mask_blk,
             elem_fn):
    """Entry-wise visibility inside a visited block; the block's queries run
    along ``q_axis`` from position ``first_q``, its keys along the other axis
    from ``first_k``."""
    qpos = first_q + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kpos = first_k + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    valid = kpos < n_valid
    if causal:
        valid &= kpos <= qpos
    if mask_blk is not None:
        valid &= mask_blk > 0
    elif elem_fn is not None:
        valid &= elem_fn(qpos, kpos)
    return valid


def _beside(col, width):
    """A lane-replicated (rows, 128) column as wide as ``width`` scores."""
    reps, rest = divmod(width, col.shape[1])
    return col[:, :1] if rest else jnp.tile(col, (1, reps))


def _as_row(col):
    """A lane-replicated (rows, 128) column as a (1, rows) row."""
    return col.T[:1]


def _fwd_kernel(ids_ref, cnt_ref, q_ref, k_ref, v_ref, *rest, scale, block_k,
                n_valid, causal, has_mask, elem_fn=None):
    """One query block against its key blocks, the scores held transposed
    (keys down the sublanes, queries along the lanes): the running maximum
    and sum are (1, bq) rows and their reductions run down the sublanes."""
    if has_mask:
        mask_ref, o_ref, lse_ref = rest      # mask_ref: (n_pad, bq), [k, q]
    else:
        o_ref, lse_ref = rest
    iq = pl.program_id(2)
    bq, d = q_ref.shape[2], v_ref.shape[3]          # d: the value width
    q = q_ref[0, 0]                                                # (bq, dk)
    cnt = cnt_ref[iq]

    def scores(t):
        # the scale goes on the float32 scores: on a bfloat16 q it would be
        # a rounding the float32 path has not
        rows = pl.ds(ids_ref[iq, t] * block_k, block_k)
        return _dot(k_ref[0, 0, rows, :], q, _NT) * scale          # (bk, bq)

    def softmax(t, s, acc, m, l):           # (bk, bq), (d, bq), (1, bq) twice
        jb = ids_ref[iq, t]
        rows = pl.ds(jb * block_k, block_k)
        v = v_ref[0, 0, rows, :]
        valid = _visible(iq * bq, jb * block_k, s.shape, 1, n_valid, causal,
                         mask_ref[rows, :] if has_mask else None, elem_fn)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
        p = jnp.exp(s - m_new)
        # for a fully-masked row m_new == NEG_INF and exp(s - m_new) is
        # exp(0) == 1 — zero p on masked entries so l stays 0 and the
        # empty-row guard below fires
        p = jnp.where(valid, p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=0, keepdims=True)
        acc = acc * corr + _dot(v, p.astype(v.dtype), _TN)         # (d, bq)
        return acc, m_new, l

    def body(t, carry):
        # the next block's scores have no part in this block's softmax: the
        # MXU forms them while the vector units work
        s, *stats = carry
        return (scores(t + 1), *softmax(t, s, *stats))

    # the last listed block has no next one: it is taken out of the loop.
    # A row of blocks with nothing listed (cnt == 0, a static mask's) walks
    # block 0 there and drops what it found: an unlisted block is invisible
    # whatever the element test says, as it is to the backward's loops
    last = jnp.maximum(cnt - 1, 0)
    s, *stats = jax.lax.fori_loop(
        0, last, body,
        (scores(0), jnp.zeros((d, bq), jnp.float32),
         jnp.full((1, bq), NEG_INF, jnp.float32),
         jnp.zeros((1, bq), jnp.float32)))
    acc, m, l = softmax(last, s, *stats)
    acc, l = jnp.where(cnt > 0, acc, 0.0), jnp.where(cnt > 0, l, 0.0)
    safe_l = jnp.where(l > 0, l, 1.0)
    o_ref[0, 0] = (acc * (1.0 / safe_l)).T.astype(o_ref.dtype)
    # rows with no visible key get a huge lse so backward p == 0; lane-
    # replicated (bq, 128) layout per the TPU tiling rules
    lse = jnp.where(l > 0, m + jnp.log(safe_l), -NEG_INF)
    lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[:1:-1]).T


def _bwd_dq_kernel(ids_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, *rest, scale, block_k, n_valid, causal,
                   has_mask, elem_fn=None):
    if has_mask:
        mask_ref, dq_ref = rest              # mask_ref: (n_pad, bq), [k, q]
    else:
        (dq_ref,) = rest
    iq = pl.program_id(2)
    bq, d = q_ref.shape[2], q_ref.shape[3]
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    # lse and delta arrive lane-replicated: a (bq, 128) tile beside every
    # 128 columns of the scores, no broadcast inside the loop
    lse = _beside(lse_ref[0, 0], block_k)
    delta = _beside(delta_ref[0, 0], block_k)

    def body(t, dq):
        jb = ids_ref[iq, t]
        cols = pl.ds(jb * block_k, block_k)
        k = k_ref[0, 0, cols, :]
        v = v_ref[0, 0, cols, :]
        s = _dot(q, k, _NT) * scale                                # (bq, bk)
        # the one kernel with queries down the sublanes: it turns its slice
        # of the [k, q] table round
        valid = _visible(iq * bq, jb * block_k, s.shape, 0, n_valid, causal,
                         mask_ref[cols, :].T if has_mask else None, elem_fn)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        ds = p * (_dot(do, v, _NT) - delta)
        return dq + _dot(ds.astype(k.dtype), k, _NN)

    dq = jax.lax.fori_loop(0, cnt_ref[iq], body,
                           jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, 0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(ids_ref, cnt_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, *rest, scale, block_q, n_valid, causal,
                    has_mask, elem_fn=None):
    """One key block against its query blocks, the scores held transposed
    as in the forward: both sums over queries are then plain products, and
    lse and delta are (1, bq) rows."""
    if has_mask:
        mask_ref, dk_ref, dv_ref = rest      # mask_ref: (bk, n_pad), [k, q]
    else:
        dk_ref, dv_ref = rest
    jk = pl.program_id(2)
    bk, d = dk_ref.shape[2], dk_ref.shape[3]
    k = k_ref[0, 0]                                                # (bk, d)
    v = v_ref[0, 0]

    def body(t, carry):
        dk, dv = carry
        ib = ids_ref[jk, t]
        rows = pl.ds(ib * block_q, block_q)
        q = q_ref[0, 0, rows, :]
        do = do_ref[0, 0, rows, :]
        lse = _as_row(lse_ref[0, 0, rows, :])
        delta = _as_row(delta_ref[0, 0, rows, :])
        s = _dot(k, q, _NT) * scale                                # (bk, bq)
        valid = _visible(ib * block_q, jk * bk, s.shape, 1, n_valid, causal,
                         mask_ref[:, rows] if has_mask else None, elem_fn)
        s = jnp.where(valid, s, NEG_INF)
        p = jnp.exp(s - lse)
        dv = dv + _dot(p.astype(do.dtype), do, _NN)
        ds = p * (_dot(v, do, _NT) - delta)
        dk = dk + _dot(ds.astype(q.dtype), q, _NN)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        0, cnt_ref[jk], body,
        (jnp.zeros((bk, d), jnp.float32),
         jnp.zeros((bk, dv_ref.shape[3]), jnp.float32)))
    # the scale the scores carry, once on the sum: dk = scale · dSᵀ Q
    dk_ref[0, 0] = (dk * scale).astype(dk_ref.dtype)
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
    lists = build_block_lists(n_pad, block_q, block_k, mask_np, causal, n)
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
    # One table, transposed ([k, q]): the forward and dkv kernels hold their
    # scores that way, and dq turns its slice round in the kernel
    mask_t = None
    if has_mask:
        mask_t = np.zeros((n_pad, n_pad), dtype=np.int32)
        s = min(mask_np.shape[0], n_pad)
        mask_t[:s, :s] = mask_np[:s, :s].T
    by_q = [lists.k_ids, lists.k_cnt]
    by_k = [lists.q_ids, lists.q_cnt]
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
        operands = [*by_q, q, k, v]
        if has_mask:
            in_specs.append(
                pl.BlockSpec((n_pad, block_q), lambda ib, ih, i, *_: (0, i)))
            operands.append(mask_t)
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
        dq_operands = [*by_q, qp, kp, vp, gp, lse, delta]
        if has_mask:
            dq_in_specs.append(
                pl.BlockSpec((n_pad, block_q), lambda ib, ih, i, *_: (0, i)))
            dq_operands.append(mask_t)
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
        dkv_operands = [*by_k, qp, kp, vp, gp, lse, delta]
        if has_mask:
            dkv_in_specs.append(
                pl.BlockSpec((block_k, n_pad), lambda ib, ih, j, *_: (j, 0)))
            dkv_operands.append(mask_t)
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


def flash_block_counts(n: int, block_q: Optional[int] = None,
                       block_k: Optional[int] = None,
                       mask: Optional[np.ndarray] = None,
                       causal: bool = True, mask_spec=None) -> dict:
    """A host-side count of what the kernels walk at ``n`` positions, from
    the table their lists are built from: the (q, k) blocks ``visited``,
    those of them wholly visible (``full``: the mask's arithmetic changes
    nothing there; the kernels do it all the same, see the module's notes),
    and the square's ``total``. Block sizes left out are the ones
    ``flash_attention`` would take. A structured ``mask_spec`` that comes
    without its table is tested entry by entry inside the kernels only, so
    no block can be called full."""
    block_q, block_k, mask_spec = _plan_blocks(n, mask, mask_spec,
                                               block_q, block_k)
    n_pad = _ceil_to(n, max(block_q, block_k))
    tiles = _visible_tiles(n_pad, block_q, block_k, mask, causal, n)
    untabled = mask is None and elem_fn_from_spec(mask_spec) is not None
    return {"visited": int(tiles.any(axis=(1, 3)).sum()),
            "full": 0 if untabled else int(tiles.all(axis=(1, 3)).sum()),
            "total": tiles.shape[0] * tiles.shape[2]}


def sparsity_fraction(n: int, block_q: int = 128, block_k: int = 128,
                      mask: Optional[np.ndarray] = None,
                      causal: bool = True) -> float:
    """Fraction of (q,k) blocks actually visited — the compute saving."""
    counts = flash_block_counts(n, block_q, block_k, mask, causal)
    return counts["visited"] / counts["total"]


def _auto_block(n: int, has_mask: bool) -> int:
    """Block sizes read on a v5e before the benchmark (fwd+bwd, bf16):
    mask-free kernels carry no element-mask operand so bigger blocks fit;
    masked kernels hold a (block, n_pad) int32 mask row and hit the 16M
    scoped-VMEM limit earlier as n grows. Read again at 4352 positions for
    PR 35's kernels (v5e, the three kernels alone, 64 x 128 and 32 x 192 /
    128 heads): 256 x 256 stays. 128-wide blocks cost 1.3-2.1 x the time in
    every kernel; 512 does not divide 4352, and padded to 4608 the kernels
    gain 13 % at 128-wide heads and lose 7 % at 192, less than the pad and
    slice copies around them cost."""
    if has_mask:
        blk = 256 if n <= 2560 else 128
    else:
        blk = 512 if n <= 2560 else 256
    return min(blk, max(128, _ceil_to(n, 128)))


def _plan_blocks(n: int, mask, mask_spec, block_q, block_k):
    """The block sizes a call runs with, and the spec it keeps."""
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
    return block_q, block_k, mask_spec


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
    block_q, block_k, mask_spec = _plan_blocks(n, mask, mask_spec,
                                               block_q, block_k)
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
