"""Grouped matrix product for routed experts: rows sorted by expert, one
weight matrix per expert, ``out[rows of group g] = lhs[rows of group g] @
rhs[g]``.

On the TPU the three products of a training step are Pallas kernels adapted
from ``jax.experimental.pallas.ops.tpu.megablox`` (the group metadata is
megablox's own; the kernels are cut to what this program needs: no sharded
group range, no accumulation into an existing output) and named on the
device: ``moe_gmm_fwd`` (forward), ``moe_gmm_dlhs`` (the rows' gradient: the
same product against the transposed weights) and ``moe_gmm_drhs`` (the
weights' gradient: per group, lhs^T @ the output's gradient). Their grid
walks only the row tiles that hold rows of some group, so the work follows
the rows that were routed here and not the buffer's size. Off the TPU the
product is ``jax.lax.ragged_dot``.

The row buffer is longer than the rows in it: rows at and past
``sum(group_sizes)`` belong to no group. The kernels never write them, so
the wrapper zeroes them, forward and backward; a caller gets zeros there,
not whatever the buffer held.

``gather_rows`` and ``combine_rows`` move token rows into that buffer and
back. XLA's gather and scatter on the TPU take a row at a time whatever
the row holds (1.4 us a row of 5120: 22 ms for a 15,360-row buffer of which
3,900 rows are real; my chip run, PR 27), so both walk the buffer in chunks
and stop after the last chunk that holds a row: their cost follows the rows,
not the buffer.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata

Tiling = Tuple[int, int, int]          # rows, contraction, columns


def _interp(interpret):
    return jax.default_backend() != "tpu" if interpret is None else interpret


def _largest_divisor(x: int, candidates) -> Optional[int]:
    return next((c for c in candidates if x % c == 0), None)


def default_tiling(m: int, k: int, n: int,
                   max_k: int = 2560) -> Optional[Tiling]:
    """Tiles for a (m, k) x (groups, k, n) product, or None where the shape
    does not tile (the caller then takes ``ragged_dot``). 256 rows: a group
    of a few hundred rows reuses each weight tile over enough rows to sit
    near the v5e's ridge (240 FLOP a byte) without computing many rows of
    its neighbours' tiles. The weight tile (tk x tn, double-buffered, with
    the lhs tile and the float32 accumulator) stays under the 16 MiB of
    scoped VMEM; the weights' gradient, whose accumulator is tk x tn, asks
    for ``max_k`` 1024."""
    tm = _largest_divisor(m, (256, 128))
    tk = _largest_divisor(k, [c for c in (2560, 2048, 1536, 1024, 512, 256,
                                          128) if c <= max_k])
    tn = _largest_divisor(n, (512, 256, 128))
    if None in (tm, tk, tn):
        return None
    return tm, tk, tn


def _row_mask(group_metadata, grid_id, tm: int, width: int):
    """(tm, width) bool: the rows of this tile that belong to this visit's
    group."""
    group_offsets, group_ids, m_tile_ids = group_metadata
    group = group_ids[grid_id]
    rows = (jax.lax.broadcasted_iota(jnp.int32, (tm, width), 0)
            + m_tile_ids[grid_id] * tm)
    return (rows >= group_offsets[group]) & (rows < group_offsets[group + 1])


def _gmm_call(lhs, rhs, group_sizes, *, transpose_rhs: bool, tiling: Tiling,
              name: str, interpret: bool):
    """lhs (m, k) x rhs (groups, k, n), or (groups, n, k) with
    ``transpose_rhs``, -> (m, n) in lhs's dtype. Rows of no group are left
    unwritten."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tk, tn = tiling
    tiles_k, tiles_n = k // tk, n // tn
    metadata, num_active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=rhs.shape[0], visit_empty_groups=False)

    def kernel(metadata_ref, lhs_ref, rhs_ref, out_ref, acc):
        grid_id, k_i = pl.program_id(1), pl.program_id(2)

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        contract = (((1,), (1,)), ((), ())) if transpose_rhs else (
            ((1,), (0,)), ((), ()))
        acc[...] += jax.lax.dot_general(lhs_ref[...], rhs_ref[...], contract,
                                        preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            # a tile on a group's border is visited once by each group in
            # it: keep the rows the other visit wrote
            mask = _row_mask(metadata_ref, grid_id, tm, tn)
            out_ref[...] = jax.lax.select(
                mask, acc[...], out_ref[...].astype(jnp.float32)
            ).astype(out_ref.dtype)

    def lhs_index(n_i, grid_id, k_i, metadata_ref):
        return metadata_ref[2][grid_id], k_i

    def rhs_index(n_i, grid_id, k_i, metadata_ref):
        group = metadata_ref[1][grid_id]
        return (group, n_i, k_i) if transpose_rhs else (group, k_i, n_i)

    def out_index(n_i, grid_id, k_i, metadata_ref):
        return metadata_ref[2][grid_id], n_i

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec(rhs_block, rhs_index)],
            out_specs=pl.BlockSpec((tm, tn), out_index),
            grid=(tiles_n, num_active_tiles, tiles_k),
            scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * tiles_n + m * n) * itemsize
            + rhs.size * rhs.dtype.itemsize),
        interpret=interpret, name=name,
    )(metadata, lhs, rhs)


def _tgmm_call(lhs, grad, group_sizes, *, tiling: Tiling, out_dtype,
               name: str, interpret: bool):
    """Per group g: lhs[rows of g]^T (k, rows) @ grad[rows of g] (rows, n)
    -> (groups, k, n). An empty group's slice is written as zeros."""
    m, k = lhs.shape
    n = grad.shape[1]
    groups = group_sizes.shape[0]
    tm, tk, tn = tiling
    tiles_k, tiles_n = k // tk, n // tn
    metadata, num_active_tiles = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=groups, visit_empty_groups=True)

    def kernel(metadata_ref, lhs_ref, grad_ref, out_ref, acc):
        grid_id = pl.program_id(2)
        group_offsets, group_ids, _ = metadata_ref
        group = group_ids[grid_id]
        prev_group = group_ids[jnp.where(grid_id > 0, grid_id - 1, 0)]
        last = grid_id == pl.num_programs(2) - 1
        next_group = group_ids[jnp.where(last, grid_id, grid_id + 1)]

        @pl.when((grid_id == 0) | (prev_group != group))
        def _zero():
            acc[...] = jnp.zeros_like(acc)

        @pl.when(group_offsets[group + 1] > group_offsets[group])
        def _accumulate():
            # rows of other groups in this tile count as zeros
            # (transposed in float32, as megablox does: Mosaic transposes
            # 32-bit tiles)
            rows = jnp.where(_row_mask(metadata_ref, grid_id, tm, tk),
                             lhs_ref[...].astype(jnp.float32), 0.0)
            acc[...] += jax.lax.dot(
                rows.swapaxes(0, 1).astype(lhs_ref.dtype), grad_ref[...],
                preferred_element_type=jnp.float32)

        @pl.when(last | (next_group != group))
        def _store():
            out_ref[...] = acc[...].astype(out_ref.dtype)

    def lhs_index(n_i, k_i, grid_id, metadata_ref):
        return metadata_ref[2][grid_id], k_i

    def grad_index(n_i, k_i, grid_id, metadata_ref):
        return metadata_ref[2][grid_id], n_i

    def out_index(n_i, k_i, grid_id, metadata_ref):
        return metadata_ref[1][grid_id], k_i, n_i

    itemsize = lhs.dtype.itemsize
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((groups, k, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[pl.BlockSpec((tm, tk), lhs_index),
                      pl.BlockSpec((tm, tn), grad_index)],
            out_specs=pl.BlockSpec((None, tk, tn), out_index),
            grid=(tiles_n, tiles_k, num_active_tiles),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * k * tiles_n + m * n * tiles_k) * itemsize
            + groups * k * n * jnp.dtype(out_dtype).itemsize),
        interpret=interpret, name=name,
    )(metadata, lhs, grad)


def _zero_unowned(x, group_sizes):
    owned = jnp.arange(x.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(owned[:, None], x, jnp.zeros((), x.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _gmm(lhs, rhs, group_sizes, fwd_tiling, bwd_tiling, interpret):
    return _gmm_fwd(lhs, rhs, group_sizes, fwd_tiling, bwd_tiling,
                    interpret)[0]


def _gmm_fwd(lhs, rhs, group_sizes, fwd_tiling, bwd_tiling, interpret):
    out = _gmm_call(lhs, rhs, group_sizes, transpose_rhs=False,
                    tiling=fwd_tiling, name="moe_gmm_fwd",
                    interpret=interpret)
    return _zero_unowned(out, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(fwd_tiling, bwd_tiling, interpret, residual, grad):
    lhs, rhs, group_sizes = residual
    grad = grad.astype(lhs.dtype)
    dlhs_tiling, drhs_tiling = bwd_tiling
    dlhs = _gmm_call(grad, rhs, group_sizes, transpose_rhs=True,
                     tiling=dlhs_tiling, name="moe_gmm_dlhs",
                     interpret=interpret)
    drhs = _tgmm_call(lhs, grad, group_sizes, tiling=drhs_tiling,
                      out_dtype=rhs.dtype, name="moe_gmm_drhs",
                      interpret=interpret)
    return _zero_unowned(dlhs, group_sizes), drhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, *,
                   use_kernel: Optional[bool] = None,
                   tiling: Optional[Tiling] = None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """``lhs`` (m, k), its rows sorted by group; ``rhs`` (groups, k, n);
    ``group_sizes`` (groups,) int32 with sum <= m. Returns (m, n) in
    ``lhs``'s dtype, zeros in the rows past the last group. Differentiable
    in ``lhs`` and ``rhs``.

    ``use_kernel`` None takes the Pallas kernels on the TPU where the shape
    tiles (``default_tiling``) and ``jax.lax.ragged_dot`` elsewhere.
    ``tiling`` (rows, contraction, columns) overrides the forward's tiles,
    and the backward's by the same three numbers (tests, at small shapes)."""
    m, k = lhs.shape
    n = rhs.shape[2]
    fwd = tiling or default_tiling(m, k, n)
    if use_kernel is None:
        use_kernel = jax.default_backend() == "tpu" and fwd is not None
    if not use_kernel:
        out = jax.lax.ragged_dot(lhs, rhs.astype(lhs.dtype),
                                 group_sizes.astype(jnp.int32))
        return _zero_unowned(out, group_sizes)
    if fwd is None:
        raise ValueError(f"grouped_matmul: ({m}, {k}) x (.., {k}, {n}) does "
                         f"not tile for the kernel; give `tiling`")
    if tiling is not None:
        tm, tk, tn = tiling
        bwd = ((tm, tn, tk), (tm, tk, tn))
    else:
        # dlhs is (m, n) x (groups, k, n)^T -> (m, k): contraction n, columns k
        bwd = (default_tiling(m, n, k), default_tiling(m, k, n, max_k=1024))
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes.astype(jnp.int32),
                fwd, bwd, _interp(interpret))


# --------------------------------------------------------------------------
# token rows into the sorted buffer, and back
# --------------------------------------------------------------------------

def _walk(n_rows, size: int, carry, body):
    """``body(carry, part, start, owned)`` over the chunks of a ``size``-row
    buffer that hold one of its first ``n_rows`` rows: ``part(a)`` is the
    chunk's slice of a buffer-long array, ``owned`` (chunk,) says which of
    the chunk's rows are among the first ``n_rows``."""
    chunk = _largest_divisor(size, (1024, 512, 256, 128, 64, 32, 16, 8, 1))

    def step(i, carry):
        start = i * chunk
        return body(
            carry,
            lambda a: jax.lax.dynamic_slice_in_dim(a, start, chunk), start,
            start + jnp.arange(chunk) < n_rows)
    return jax.lax.fori_loop(0, (n_rows + chunk - 1) // chunk, step, carry)


@jax.custom_vjp
def gather_rows(table, index, n_rows):
    """``table[index]`` (len(index), width) for the first ``n_rows`` entries
    of ``index``, zeros after them."""
    return _gather_rows(table, index, n_rows)


def _gather_rows(table, index, n_rows):
    def body(out, part, start, owned):
        rows = jnp.where(owned[:, None], table[part(index)],
                         jnp.zeros((), table.dtype))
        return jax.lax.dynamic_update_slice_in_dim(out, rows, start, 0)
    return _walk(n_rows, index.shape[0],
                 jnp.zeros((index.shape[0], table.shape[1]), table.dtype),
                 body)


def _scatter_add_rows(rows, index, n_rows, table_rows: int):
    """zeros((table_rows, width)).at[index].add(rows) over the first
    ``n_rows`` rows: ``gather_rows``'s transpose."""
    def body(table, part, start, owned):
        # neither hint holds: a token's choices repeat it, and a chunk
        # crosses from one expert's (ascending) tokens into the next's
        return table.at[part(index)].add(  # graftlint: disable=scatter-missing-hints
            jnp.where(owned[:, None], part(rows), jnp.zeros((), rows.dtype)))
    return _walk(n_rows, index.shape[0],
                 jnp.zeros((table_rows, rows.shape[1]), rows.dtype), body)


gather_rows.defvjp(
    lambda table, index, n_rows: (_gather_rows(table, index, n_rows),
                                  (index, n_rows, table.shape[0])),
    lambda res, g: (_scatter_add_rows(g, res[0], res[1], res[2]), None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def combine_rows(rows, weight, index, n_rows, table_rows: int):
    """The buffer's rows back to their tokens, each times its routing weight:
    zeros((table_rows, width)).at[index].add(rows * weight[:, None]) over the
    first ``n_rows`` rows, summed in ``weight``'s dtype (float32). The
    product exists a chunk at a time, forward and backward."""
    return _combine_rows(rows, weight, index, n_rows, table_rows)


def _combine_rows(rows, weight, index, n_rows, table_rows: int):
    def body(table, part, start, owned):
        weighted = (part(rows).astype(weight.dtype)
                    * jnp.where(owned, part(weight), 0.0)[:, None])
        # (no hint holds: see _scatter_add_rows)
        return table.at[part(index)].add(weighted)  # graftlint: disable=scatter-missing-hints
    return _walk(n_rows, index.shape[0],
                 jnp.zeros((table_rows, rows.shape[1]), weight.dtype), body)


def _combine_rows_bwd(table_rows, res, g):
    rows, weight, index, n_rows = res
    def body(carry, part, start, owned):
        d_rows, d_weight = carry
        back = jnp.where(owned[:, None], g[part(index)], 0.0)
        d_rows = jax.lax.dynamic_update_slice_in_dim(
            d_rows, (back * part(weight)[:, None]).astype(rows.dtype),
            start, 0)
        d_weight = jax.lax.dynamic_update_slice_in_dim(
            d_weight, jnp.sum(back * part(rows).astype(back.dtype), axis=-1),
            start, 0)
        return d_rows, d_weight
    d_rows, d_weight = _walk(
        n_rows, index.shape[0],
        (jnp.zeros_like(rows), jnp.zeros_like(weight)), body)
    return d_rows, d_weight, None, None


combine_rows.defvjp(
    lambda rows, weight, index, n_rows, table_rows: (
        _combine_rows(rows, weight, index, n_rows, table_rows),
        (rows, weight, index, n_rows)),
    _combine_rows_bwd)
