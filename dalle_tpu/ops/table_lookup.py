"""Rows of a token table by id, for a forward that is differentiated.

``take_rows(table, ids)`` is ``jnp.take(table, ids, axis=0)``. What differs
is the table's gradient, ``zeros(table).at[ids].add(g)``. XLA's scatter on
the TPU is steady up to 4096 elements a row (0.03-0.21 us an id) and
erratic past that: 0.27-1.0 us an id by the width, and at 5120 wide 1.9 us
for every row *of the table* whatever the ids, so the two tables of a
5120-wide model cost 24 ms a step where their bytes say 0.3 ms (my chip
runs, PR 29; PERF.md section 6). There the gradient is taken as a product
on the MXU instead, ``one_hot(ids)^T @ g`` accumulated in float32 and
rounded once. Its time follows from its shapes (2 x rows x ids x width
operations at 164 TFLOP/s measured), XLA fuses the comparison that makes
the one-hot into the product's operand, so nothing of ids x rows reaches
memory, and the sum is exact where the scatter adds repeated ids in the
table's dtype. Narrow or long tables keep the scatter, the cheaper there.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# Where the product is taken (one v5e chip, bfloat16, 8192 ids; my chip
# runs, PR 29). Width: at 8192 rows the scatter wins 2x up to 4224 wide
# (1.4-1.5 ms against 3.0-3.1) and from 4608 up it ties or loses (0.9x at
# 4608, 8192, 10240 and 12288, 0.65x at 4864, 0.25x at 5120; it wins 1.4-1.8x
# at 5376 and 6144). Rows: the product grows with them and the scatter does
# not: at 16,384 rows the scatter wins 1.75x at 6144 and 8192 wide.
WIDE_ROW = 4096
MAX_ROWS = 8192


def grad_path(rows: int, width: int, dtype) -> str:
    """Which backward ``take_rows`` gives a ``(rows, width)`` table of
    ``dtype``: ``"product"`` or ``"scatter"``. Shapes and dtypes only, so
    the choice is made once per compile. The product is one MXU pass, exact
    for a one-hot operand, only in a 16-bit float: a float32 table would
    need six and keeps the scatter (16.0 ms against 11.5 at 5120 wide, and
    1.6 against 9.3 at 4096)."""
    dtype = jnp.dtype(dtype)
    one_pass = jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize == 2
    wide_and_short = width > WIDE_ROW and rows <= MAX_ROWS
    return "product" if one_pass and wide_and_short else "scatter"


def take_rows(table, ids):
    """``jnp.take(table, ids, axis=0)``: the same rows in the table's dtype,
    with the backward ``grad_path`` names."""
    if grad_path(*table.shape, table.dtype) == "scatter":
        return jnp.take(table, ids, axis=0)
    return _take_rows(table, ids)


@jax.custom_vjp
def _take_rows(table, ids):
    return jnp.take(table, ids, axis=0)


def _take_rows_fwd(table, ids):
    return jnp.take(table, ids, axis=0), (ids, table.shape[0])


def _take_rows_bwd(res, g):
    ids, rows = res
    with jax.named_scope("embed/grad"):
        ids = ids.reshape(-1)
        ids = jnp.where(ids < 0, ids + rows, ids)        # as jnp.take wraps
        # an id outside the table matches no column: dropped, as take's is
        hot = jax.nn.one_hot(ids, rows, dtype=g.dtype)
        d_table = jax.lax.dot_general(
            hot, g.reshape(-1, g.shape[-1]), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return d_table.astype(g.dtype), None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)
