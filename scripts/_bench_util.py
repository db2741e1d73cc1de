"""Shared dispatched-scan timing harness for the bench/profile scripts.

Methodology: a candidate is timed as K executions inside ONE jitted
`lax.scan`, so the kernels under test run back to back on the device with
no host dispatch between them. Two traps this helper exists to avoid (they
bit real tables):

  * Loop hoisting — every *floating* argument is perturbed by the scan carry
    so XLA cannot compute the body once outside the loop. Integer args can't
    be perturbed: anything whose gradient/recompute matters must be passed
    as a floating argument, not closed over (closures are jit constants).
  * Dead-code elimination of backward work — grad wrt a subset of inputs
    lets XLA drop the other cotangents' matmuls (e.g. dk/dv of dense
    attention), biasing comparisons against opaque custom_vjp kernels that
    always compute the full backward. ``grad_argnums`` defaults to ALL
    floating arguments.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp


def _is_float(x):
    return jnp.issubdtype(jnp.result_type(x), jnp.floating)


def timed_scan(fn, args, k: int = 8, grad: bool = False, grad_argnums=None):
    """Seconds per execution of ``fn(*args)`` (or its full backward when
    ``grad=True``), amortized over k in-program iterations.

    ``fn`` must return an array or pytree; the loss for grad mode is the
    sum of squares of all output leaves (f32). ``grad_argnums`` defaults to
    every floating positional argument — pass a tuple to restrict.
    """
    if grad:
        if grad_argnums is None:
            grad_argnums = tuple(i for i, a in enumerate(args)
                                 if jax.tree.all(jax.tree.map(_is_float, a)))

        def scalar_loss(*a):
            out = fn(*a)
            return sum(jnp.sum(leaf.astype(jnp.float32) ** 2)
                       for leaf in jax.tree.leaves(out))

        base = jax.grad(scalar_loss, argnums=grad_argnums)
    else:
        base = fn

    @jax.jit
    def many(args):
        def body(c, _):
            perturbed = tuple(
                jax.tree.map(
                    lambda x: x + jnp.asarray(1e-12 * c, x.dtype)
                    if _is_float(x) else x, a)
                for a in args)
            out = base(*perturbed)
            s = sum(jnp.sum(leaf.astype(jnp.float32))
                    for leaf in jax.tree.leaves(out))
            return c + 1e-30 * s, None

        c, _ = jax.lax.scan(body, jnp.float32(0.0), None, length=k)
        return c

    float(jax.device_get(many(args)))       # compile + hard sync
    t0 = time.perf_counter()
    float(jax.device_get(many(args)))
    return (time.perf_counter() - t0) / k
