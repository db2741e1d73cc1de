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
to_qkv backward wants; residual memory stays O(n·h·d): the qkv operand and
the forward's output, which to_out's backward keeps alive anyway.

The block plan (``block_plan``, host-side, static): both kernels form only
the score blocks the validity table leaves something in. The table is cut
into 128-wide blocks of rows and of keys (a ragged last one where 128 does
not divide n). Per row block the key *extent* ends with the last key block
holding a valid entry; the scores, the softmax (every key the rows may see
is inside the extent, so one pass: no running maximum, no rescaling; the
entries left out were exp(-1e9 - max) = 0) and the products run on the
blocks inside the extents alone, and a block the table lets through whole
runs no ``where``. For the plain causal table at n = 512 that is 10 of 16
blocks, 4 of them (the diagonal) masked. A table that cuts no extent short
(or n ≤ 128) gives one block of the whole square. One algorithm; the blocks
follow from (n, table).

How the blocks are walked (measured on a v5e, PERF.md §6 PR 33): a row
block at a time, 128 rows' scores → softmax → product chains are too short
to hide the units' latencies and ran SLOWER than the whole square. So each
product runs a KEY block at a time over all the rows that reach it
(``_scores``, ``_apply``: few, long products whose results are cut into the
plan's blocks), the softmax per row block over its blocks, and dk / dv a
row block at a time over its extent (``_down``). The backward runs five
products a head on those blocks: scores, dp = dO Vᵀ, dq, dk, dv; delta =
Σ o·dO comes from the saved output, not from a second P V. Two 64-wide
heads share one 128-lane slab (``_slabs``): a head is picked out by zeroing
the other's lanes of one operand of each product, not by slicing lanes.

Reference bar: the dense Attention hot path this replaces
(dalle_pytorch/attention.py:58-99).
"""

from __future__ import annotations

import functools
import operator
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e9

# per-program live set must fit scoped VMEM (16M on v5e). The estimate was
# calibrated against the compiler's own reports for the whole-square
# kernels: Mosaic DOUBLE-BUFFERS the operand/output block windows, so the
# backward pass (the larger one) cost ~2×(qkv + do + dqkv) bf16 windows +
# the merged bf16 grad accumulators + ~3 (n, n) f32 score tiles (+ the
# double-buffered int8 mask window). The small config (n=513, h·d=512)
# compiled at ~12M; medium (h·d=1024) was reported at 25.68M. The block
# plan's backward holds less: 2×(qkv + out + do + dqkv) windows (32·n·hd:
# the saved output's window in the accumulators' place, which are gone,
# gradients being stored per slab), the mask window, and one slab's blocks
# (scores, dp, p, ds: float32 and bfloat16 (128, 128) tiles, 10 of 16 of
# the square for the causal table). Compiling for a described v5e with the
# limit bisected: small (n=512) needs 11-12M where the estimate says
# 12.6M, medium (n=513) 12-13M where it says 21.5M. So _bwd_bytes stays an
# upper bound, and the budget below still accepts the former shape under
# the default ceiling and sends the latter to the 32M tier with headroom.
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


def validity_table(n: int, mask, mask_spec) -> np.ndarray:
    """Host-side (n, n) int8 validity (1 = attend), causality pre-ANDed."""
    if use_spec(mask_spec):
        from .flash_attention import elem_fn_from_spec
        ri = np.arange(n)[:, None]
        ci = np.arange(n)[None, :]
        vis = np.asarray(elem_fn_from_spec(mask_spec)(ri, ci), bool)
        return (vis & (ci <= ri)).astype(np.int8)
    if mask is not None:   # tables already include causality; the model's
        return np.asarray(mask, np.int8)[:n, :n]   # are one position longer
    return np.tril(np.ones((n, n), np.int8))


_BLOCK = 128   # rows and keys of one score block: the lanes' width


class BlockPlan(NamedTuple):
    """The score blocks a validity table leaves something in. ``spans``:
    the (start, stop) of each block of rows, which are the blocks of keys
    too; ``extents``: per row block, where the keys its scores are formed
    on end; ``masked``: the (row block, key block) pairs inside the extents
    that the table cuts into, so that a ``where`` runs on them;
    ``computed`` of ``of`` blocks of the square are formed."""
    spans: tuple
    extents: tuple
    masked: frozenset
    computed: int
    of: int

    def width(self, j: int) -> int:
        """Key blocks inside row block ``j``'s extent."""
        return sum(c0 < self.extents[j] for c0, _ in self.spans)

    def reach(self, ci: int) -> list:
        """The row blocks whose extent covers key block ``ci``, as runs
        [first, last] of neighbours: a run's rows go through one product."""
        runs = []
        for j in range(len(self.spans)):
            if self.width(j) <= ci:
                continue
            if runs and runs[-1][1] == j - 1:
                runs[-1][1] = j
            else:
                runs.append([j, j])
        return runs


def block_plan(table) -> BlockPlan:
    """Host-side, static: cut the (n, n) validity table into ``_BLOCK``-wide
    row and key blocks (a ragged last one where that does not divide n). A row block's key extent ends with the last key block that holds a
    valid entry: past it every probability was exp(-1e9 - max) = 0, so the
    kernel leaves those scores out. A key block the table lets through whole
    needs no ``where``. A table that cuts no extent short, or one row block,
    gives one block of the whole square."""
    valid = np.asarray(table) != 0
    n = valid.shape[0]
    edges = list(range(0, n, _BLOCK)) + [n]
    extents = []
    for r0, r1 in zip(edges, edges[1:]):
        rows = valid[r0:r1]
        # a row with no valid key takes its maximum over masked scores: its
        # (meaningless) softmax spans whatever is computed, so keep it whole
        last = (int(np.flatnonzero(rows.any(axis=0))[-1]) + 1
                if rows.any(axis=1).all() else n)
        extents.append(min(-(-last // _BLOCK) * _BLOCK, n))
    if all(e == n for e in extents):
        edges, extents = [0, n], [n]
    spans = tuple(zip(edges, edges[1:]))
    inside = [(j, ci) for j, extent in enumerate(extents)
              for ci, (c0, _) in enumerate(spans) if c0 < extent]
    masked = frozenset(
        (j, ci) for j, ci in inside
        if not valid[slice(*spans[j]), slice(*spans[ci])].all())
    return BlockPlan(spans, tuple(extents), masked, len(inside),
                     len(spans) ** 2)


def _slabs(h: int, d: int):
    """(first lane, lanes, heads) of each slab of head columns in a merged
    (n, h·d) part. Heads whose columns fill whole 128-lane registers
    together (two of 64) are loaded, multiplied and stored as ONE slab:
    slicing a 64-lane head out of a register, and putting two back
    together, is lane rotation and selection that costs more than the
    products it feeds; a head is picked out of its slab by zeroing the
    other heads' lanes of ONE operand of each product instead (``_own``),
    which the MXU pays nothing for: a contraction of 64 or an output 64
    wide half-fills it either way. Where the head width allows no such
    group, a head is its own slab."""
    group = 128 // d if 128 % d == 0 else 1
    if group * d % 128 or h % group:
        group = 1
    return [(g * d, group * d, group) for g in range(0, h, group)]


def _own(x, t: int, heads: int):
    """``x`` with the lanes of every head of the slab but head ``t`` zeroed."""
    if heads == 1:
        return x
    d = x.shape[-1] // heads
    lanes = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.where((lanes >= t * d) & (lanes < (t + 1) * d), x,
                     jnp.zeros_like(x))


def _scaled(q, scale):
    return (q.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def _dot(a, b, contract):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


def _tables(mask_ref, plan):
    """The validity of each masked block, read once a program."""
    return {(j, ci): mask_ref[slice(*plan.spans[j]),
                              slice(*plan.spans[ci])] != 0
            for j, ci in plan.masked}


def _scores(x, w, plan, valid=None):
    """blocks[j][ci] = x[rows j] @ w[keys ci]^T, float32, for the blocks
    of the plan: a key block at a time over all the rows that reach it (the
    products are long and few), ``NEG_INF`` where ``valid`` cuts in."""
    blocks = [[None] * plan.width(j) for j in range(len(plan.spans))]
    for ci, (c0, c1) in enumerate(plan.spans):
        for first, last in plan.reach(ci):
            a = plan.spans[first][0]
            slab = _dot(x[a:plan.spans[last][1]], w[c0:c1], ((1,), (1,)))
            for j in range(first, last + 1):
                r0, r1 = plan.spans[j]
                piece = slab[r0 - a:r1 - a]
                if valid is not None and (j, ci) in plan.masked:
                    piece = jnp.where(valid[j, ci], piece, NEG_INF)
                blocks[j][ci] = piece
    return blocks


def _over_keys(combine, reduce, pieces):
    """``reduce`` over the keys of one row block's pieces: ``combine`` runs
    element by element across the pieces of one width first, so a row block
    pays one reduction across lanes (two where the last key block is
    ragged)."""
    by_width = {}
    for x in pieces:
        w = x.shape[-1]
        by_width[w] = combine(by_width[w], x) if w in by_width else x
    return functools.reduce(combine, [reduce(x, axis=-1, keepdims=True)
                                      for x in by_width.values()])


def _softmax(blocks):
    """Each row block's softmax over its extent, float32. Every key the
    rows may see is inside the extent, so there is no running maximum and
    nothing to rescale."""
    out = []
    for row in blocks:
        m = _over_keys(jnp.maximum, jnp.max, row)
        e = [jnp.exp(s - m) for s in row]
        inv = 1.0 / _over_keys(operator.add, jnp.sum, e)
        out.append([x * inv for x in e])
    return out


def _bf16(blocks):
    return [[x.astype(jnp.bfloat16) for x in row] for row in blocks]


def _cat(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


def _apply(blocks, ws, plan):
    """out[j] = sum over heads t and key blocks of blocks[t][j][ci] @
    ws[t][keys ci]: P V and dq of a slab, a key block at a time over the rows
    that reach it; the heads lie side by side along the contraction, so
    the MXU adds them up."""
    out = [None] * len(plan.spans)
    for ci, (c0, c1) in enumerate(plan.spans):
        for first, last in plan.reach(ci):
            lhs = _cat([_cat([head[j][ci] for j in range(first, last + 1)], 0)
                        for head in blocks], -1)
            slab = _dot(lhs, _cat([w[c0:c1] for w in ws], 0), ((1,), (0,)))
            a = plan.spans[first][0]
            for j in range(first, last + 1):
                r0, r1 = plan.spans[j]
                piece = slab[r0 - a:r1 - a]
                out[j] = piece if out[j] is None else out[j] + piece
    return out


def _down(blocks, others, plan):
    """out[ci] = sum over heads t and row blocks of blocks[t][j][ci]^T @
    others[t][rows j]: dk (from ds and q) and dv (from p and do) of a slab,
    a row block at a time over its extent, the heads stacked along the
    contraction. A key block no row sees gets zeros."""
    out = [None] * len(plan.spans)
    for j, (r0, r1) in enumerate(plan.spans):
        lhs = _cat([_cat(head[j], -1) for head in blocks], 0)
        slab = _dot(lhs, _cat([x[r0:r1] for x in others], 0), ((0,), (0,)))
        for ci in range(plan.width(j)):
            piece = slab[slice(*plan.spans[ci])]
            out[ci] = piece if out[ci] is None else out[ci] + piece
    return [jnp.zeros((c1 - c0, others[0].shape[-1]), jnp.float32)
            if x is None else x for x, (c0, c1) in zip(out, plan.spans)]


def _fwd_kernel(qkv_ref, mask_ref, o_ref, *, scale, plan, h, d):
    hd = h * d
    valid = _tables(mask_ref, plan)
    for lo, w, heads in _slabs(h, d):
        # slice each slab's operands straight from the ref (a whole-block
        # load would hold an extra (n, 3hd) copy on the stack) and store
        # per slab (no merged accumulator stays alive)
        q, k, v = (qkv_ref[0, :, part * hd + lo:part * hd + lo + w]
                   for part in range(3))
        qs = _scaled(q, scale)
        p = [_bf16(_softmax(_scores(qs, _own(k, t, heads), plan, valid)))
             for t in range(heads)]
        o = _apply(p, [_own(v, t, heads) for t in range(heads)], plan)
        for (r0, r1), rows in zip(plan.spans, o):
            o_ref[0, r0:r1, lo:lo + w] = rows.astype(o_ref.dtype)


def _bwd_kernel(qkv_ref, o_ref, do_ref, mask_ref, dqkv_ref, *, scale, plan,
                h, d):
    hd = h * d
    valid = _tables(mask_ref, plan)
    for lo, w, heads in _slabs(h, d):
        q, k, v = (qkv_ref[0, :, part * hd + lo:part * hd + lo + w]
                   for part in range(3))
        do = do_ref[0, :, lo:lo + w]
        qs = _scaled(q, scale)
        # delta = sum(o * do) a head, from the forward's saved output
        odo = (o_ref[0, :, lo:lo + w].astype(jnp.float32)
               * do.astype(jnp.float32))
        q_own, k_own, v_own, do_own = (
            [_own(x, t, heads) for t in range(heads)] for x in (q, k, v, do))
        p16, ds = [], []
        for t, (k_t, v_t) in enumerate(zip(k_own, v_own)):
            delta = jnp.sum(_own(odo, t, heads), axis=-1, keepdims=True)
            p = _softmax(_scores(qs, k_t, plan, valid))
            dp = _scores(do, v_t, plan)
            ds.append([[(x * (y - delta[r0:r1])).astype(jnp.bfloat16)
                        for x, y in zip(p_row, dp_row)]
                       for p_row, dp_row, (r0, r1) in zip(p, dp, plan.spans)])
            p16.append(_bf16(p))
        grads = ((_apply(ds, k_own, plan), scale),
                 (_down(ds, q_own, plan), scale),
                 (_down(p16, do_own, plan), None))
        for part, (grad, by) in enumerate(grads):
            for (r0, r1), rows in zip(plan.spans, grad):
                dqkv_ref[0, r0:r1, part * hd + lo:part * hd + lo + w] = (
                    rows if by is None else rows * by).astype(dqkv_ref.dtype)


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
    A structured ``mask_spec`` (axial/conv — see use_spec) is what the
    table is built from host-side. Either way the kernels form only the
    score blocks ``block_plan`` finds something in."""
    return _fused_fwd(qkv, mask, heads, scale, interpret, mask_spec)[0]


@functools.lru_cache(maxsize=64)
def _calls(table: bytes, n: int, heads: int, scale: float, interpret: bool):
    """(forward, backward): the two ``pallas_call``s for one validity table,
    head count and scale, each under ``jax.jit(inline=True)``. A stack's
    layers share them, so the kernels' bodies (every slab and block
    unrolled) are traced once a step and not once a layer: traced per
    layer they add ten seconds to a DALL·E-small trainer's first step.
    Inlined, each call site still lowers to its own Mosaic call."""
    tbl = np.frombuffer(table, np.int8).reshape(n, n)
    plan = block_plan(tbl)

    def call(kernel, name, out_width, vmem_bytes, *operands):
        b, _, hd3 = operands[0].shape
        hd = hd3 // 3
        wide = pl.BlockSpec((1, n, hd3), lambda ib: (ib, 0, 0))
        narrow = pl.BlockSpec((1, n, hd), lambda ib: (ib, 0, 0))
        return pl.pallas_call(
            functools.partial(kernel, scale=scale, plan=plan, h=heads,
                              d=hd // heads),
            grid=(b,),
            in_specs=[wide] + [narrow] * (len(operands) - 1)
            + [pl.BlockSpec((n, n), lambda ib: (0, 0))],
            out_specs=wide if out_width == hd3 else narrow,
            out_shape=jax.ShapeDtypeStruct((b, n, out_width),
                                           operands[0].dtype),
            compiler_params=_compiler_params(vmem_bytes),
            interpret=interpret,
            name=name,
        )(*(x.astype(jnp.bfloat16) for x in operands), jnp.asarray(tbl))

    def fwd(qkv):
        hd = qkv.shape[-1] // 3
        return call(_fwd_kernel, "fused_attn_fwd", hd,
                    18 * n * hd + 10 * n * n, qkv)

    def bwd(qkv, out, do):
        hd3 = qkv.shape[-1]
        return call(_bwd_kernel, "fused_attn_bwd", hd3,
                    _bwd_bytes(n, hd3 // 3), qkv, out, do)

    return jax.jit(fwd, inline=True), jax.jit(bwd, inline=True)


def _pair(qkv, mask, heads, scale, interpret, mask_spec):
    n = qkv.shape[1]
    if scale is None:
        scale = (qkv.shape[2] // 3 // heads) ** -0.5
    tbl = validity_table(n, mask, mask_spec)
    return _calls(tbl.tobytes(), n, heads, float(scale), _interp(interpret))


def _fused_fwd(qkv, mask, heads, scale, interpret, mask_spec=None):
    out = _pair(qkv, mask, heads, scale, interpret, mask_spec)[0](qkv)
    return out, (qkv, out)


def _fused_bwd(mask, heads, scale, interpret, mask_spec, res, do):
    qkv, out = res
    return (_pair(qkv, mask, heads, scale, interpret, mask_spec)[1](
        qkv, out, do),)


fused_qkv_attention.defvjp(_fused_fwd, _fused_bwd)
