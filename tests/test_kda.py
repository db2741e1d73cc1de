"""The chunked core of Kimi delta attention (ops/kda.py) against the delta
rule as it is defined, one position at a time, on the CPU in float32."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.obs.device import scope_layer
from dalle_tpu.ops.kda import (CHUNK, KDA_SAVED, SUB, _diagonal_squares,
                               _merged, _unit_lower_inverse, chunks_of,
                               kda_chunked)

HI = jax.lax.Precision.HIGHEST
EPS = 1e-5


def log_decays(f, a_log, bias):
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(f + bias)


def delta_rule(q, k, v, f, beta, a_log, bias):
    """The layer's heads as they are defined, one position at a time: q and
    k over their norms, the decay from its pre-activation, the delta rule;
    what the state returns, before the head's norm."""
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = log_decays(f, a_log, bias)

    def position(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhd,bhdv->bhv", k_t, state,
                                               precision=HI))
        state = state + jnp.einsum("bhd,bhv->bhdv", k_t, u, precision=HI)
        return state, jnp.einsum("bhd,bhdv->bhv", q_t, state, precision=HI)

    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    start = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    return jnp.moveaxis(jax.lax.scan(position, start, xs)[1], 0, 1)


def rms_of(o):
    return jnp.sqrt(jnp.mean(o * o, -1, keepdims=True) + EPS)


def recurrent(*args):
    """... and the head's RMS norm of it."""
    o = delta_rule(*args[:-1])
    return o / rms_of(o) * args[-1]


def chunked(*args):
    return kda_chunked(*args[:5], a_log=args[5], bias=args[6],
                       norm_scale=args[7], eps=EPS)


def core_inputs(n: int, gate: float, h: int = 3, d: int = 8, dv: int = 5):
    """What the layer hands its core, and the heads' own leaves. Gates of
    ``-gate x softplus(normal)`` a position: 1.6 is the most negative the
    initialisation gives (A = 16, a step of 0.1), 80 a trained model's
    A = 16 behind a saturated softplus; one head's A a quarter of that, and
    a bias a channel."""
    ks = jax.random.split(jax.random.PRNGKey(n), 7)
    q, k, f = (jax.random.normal(key, (2, n, h, d)) for key in ks[:3])
    v = jax.random.normal(ks[3], (2, n, h, dv))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (2, n, h)))
    a_log = jnp.log(gate * jnp.array([1.0, 0.25, 1.0]))
    bias = 0.3 * jax.random.normal(ks[5], (h, d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[6], (dv,))
    return q, k, v, f, beta, a_log, bias, scale


@pytest.mark.parametrize(
    "n,gate,d",
    [(n, gate, 8) for n in (150, 37) for gate in (0.01, 1.6, 80.0)]
    + [(150, 80.0, 128)],
    ids=[f"{n}-{gate}" for n in (150, 37) for gate in (0.01, 1.6, 80.0)]
    + ["150-80.0-d128"])
def test_the_chunked_core_is_the_recurrent_form(n, gate, d):
    """Lengths that are no multiple of the chunk (three chunks, and less
    than one), gates from almost none to a trained model's most negative,
    keys of 8 and of the configurations' 128 (a key a vector register's
    lanes): no inf, no nan, and the recurrence's numbers to 1e-5, forward
    and (of an input's largest gradient) under ``jax.grad`` with respect to
    every input and every leaf, through the diagonal squares' own
    backward."""
    args = core_inputs(n, gate, d=d)
    out, low = chunked(*args)
    want = recurrent(*args)
    assert bool(jnp.all(jnp.isfinite(out)))
    # the recurrence's own numbers (what the norm divided by put back), then
    # the normalised ones, where a head's output near 0 is a division by
    # little
    raw = delta_rule(*args[:-1])
    np.testing.assert_allclose(out / args[-1] * rms_of(raw), raw, atol=1e-5)
    np.testing.assert_allclose(out, want, atol=1e-4)
    # the counter: the most negative sum of a chunk's gates
    pad = chunks_of(n) * CHUNK - n
    g = log_decays(args[3], args[5], args[6])
    sums = jnp.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        2, -1, CHUNK, 3, d).sum(2)
    assert float(low) == pytest.approx(float(sums.min()), rel=1e-5)
    if gate > 1 and n > CHUNK:
        assert float(low) < -88.0     # exp(-low) is past float32's range

    def loss(fn):
        return lambda *a: jnp.sum(jnp.sin(fn(*a)))
    every = tuple(range(len(args)))
    ours = jax.grad(loss(lambda *a: chunked(*a)[0]), argnums=every)(*args)
    theirs = jax.grad(loss(recurrent), argnums=every)(*args)
    # (through the norm a gradient reaches 10 where an output is near 0.) A
    # chunk's cumulative sum of gates reaches ``low`` (-150 at the
    # initialisation's most negative gates here, -5,900 at a trained
    # model's), and a decay between two positions carries the float32
    # spacing of that sum, in any chunked form: the tolerance follows it
    within = max(2e-5, 4 * float(jnp.finfo(jnp.float32).eps) * -float(low))
    names = ("q", "k", "v", "f", "beta", "a_log", "bias", "scale")
    for name, a, b in zip(names, ours, theirs):
        assert bool(jnp.all(jnp.isfinite(a))), name
        np.testing.assert_allclose(
            a, b, err_msg=name,
            atol=within * max(1.0, float(jnp.abs(b).max())))


@pytest.mark.parametrize("d,deepest", [(128, -400.0), (8, -400.0),
                                        (128, -4.0)])
def test_the_diagonal_squares_backward_is_their_autodiff(d, deepest):
    """The hand-written backward of the diagonal sub-blocks' squares against
    ``jax.vjp`` of the same forward without it, in float32: keys of the
    configurations' 128 and of 8, cumulative decays down to ``deepest``
    (-400: the least ``kda_logdecay_min`` reads in training, so that most
    decays between two positions of a sub-block underflow; -4: none do), a
    cotangent over the whole square (above the diagonal the squares are 0
    whatever the inputs)."""
    rng = np.random.default_rng(d)
    ns = 4
    x = jnp.asarray(rng.normal(size=(2, 2, ns, SUB, d)), jnp.float32)
    ks = jnp.asarray(rng.normal(size=(2, ns, SUB, d)), jnp.float32)
    gates = rng.uniform(0, 2 * -deepest / (ns * SUB), size=(2, ns * SUB, d))
    cs = jnp.asarray(-np.cumsum(gates, axis=1).reshape(2, ns, SUB, d),
                     jnp.float32)
    g = jnp.asarray(rng.normal(size=(2, 2, ns, SUB, SUB)), jnp.float32)
    assert float(cs.min()) < 0.75 * deepest
    plain = jax.jit(lambda *a: _diagonal_squares.fun(*a, jnp.float32))
    ours = jax.jit(lambda *a: _diagonal_squares(*a, jnp.float32))
    np.testing.assert_array_equal(ours(x, ks, cs), plain(x, ks, cs))
    got = jax.jit(lambda *a: jax.vjp(ours, *a)[1](g))(x, ks, cs)
    want = jax.jit(lambda *a: jax.vjp(plain, *a)[1](g))(x, ks, cs)
    for name, a, b in zip(("x", "ks", "cs"), got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(
            a, b, err_msg=name, rtol=0,
            atol=1e-5 * float(jnp.abs(b).max()))


def test_the_core_in_bfloat16_stays_near_its_float32_self():
    """The compute type of training: operands of the products in bfloat16,
    gates, sums, the triangular inverse and the state in float32. Finite at
    the most negative gates, and within bfloat16's rounding of the float32
    numbers."""
    args = core_inputs(150, 80.0)
    want, _ = chunked(*args)
    low = tuple(a.astype(jnp.bfloat16) for a in args[:4]) + args[4:]
    got, _ = chunked(*low)
    assert got.dtype == jnp.bfloat16
    assert bool(jnp.all(jnp.isfinite(got)))
    back = tuple(a.astype(jnp.float32) for a in low[:4]) + args[4:]
    near, _ = chunked(*back)
    assert float(jnp.abs(got.astype(jnp.float32) - near).mean()) < 0.03
    assert float(jnp.abs(near - want).mean()) < 0.03


def pieces(a):
    """A (..., c, c) lower-triangular matrix as ``_merged`` takes it."""
    c = a.shape[-1]
    diag = jnp.stack([a[..., i:i + SUB, i:i + SUB]
                      for i in range(0, c, SUB)], axis=-3)
    lows, s = [], SUB
    while s < c:
        lows.append(jnp.stack([a[..., i + s:i + 2 * s, i:i + s]
                               for i in range(0, c, 2 * s)], axis=-3))
        s *= 2
    return diag, lows


def test_the_triangular_inverse_holds_where_powers_of_the_matrix_do_not():
    """Alike keys, beta 1, no decay: I + A is the all-ones lower triangle,
    whose inverse is 1 on the diagonal and -1 under it, while A^32 holds
    numbers of 1e17: the reason no Neumann product is formed."""
    a = jnp.tril(jnp.ones((64, 64), jnp.float32), -1)
    np.testing.assert_array_equal(_merged(*pieces(a)), a)
    want = jnp.eye(64) - jnp.eye(64, k=-1)
    np.testing.assert_allclose(_unit_lower_inverse(*pieces(a)), want,
                               atol=1e-6)
    # entries of a unit key's products, and the gradient's two products
    # against the substitution's own transpose
    rng = np.random.default_rng(0)
    b = jnp.asarray(np.tril(rng.uniform(-0.4, 0.4, (3, 64, 64)), -1),
                    jnp.float32)
    inverse = lambda m: _unit_lower_inverse(*pieces(m))
    np.testing.assert_allclose(
        jnp.einsum("bij,bjk->bik", jnp.eye(64) + b, inverse(b), precision=HI),
        jnp.broadcast_to(jnp.eye(64), (3, 64, 64)), atol=1e-4)
    weights = jnp.asarray(rng.normal(size=(3, 64, 64)), jnp.float32)
    ours = jax.grad(lambda m: jnp.sum(weights * inverse(m)))(b)
    plain = jax.grad(lambda m: jnp.sum(weights * jnp.linalg.inv(
        jnp.eye(64) + jnp.tril(m, -1))))(b)
    np.testing.assert_allclose(ours, plain, atol=2e-3, rtol=1e-3)


def layer_pair(remat):
    """The gradient of two stacked cores, the first one's output the second
    one's queries, each under ``jax.checkpoint(**remat)`` (``None``: none),
    with respect to every input and leaf."""
    def layer(q, k, v, f, beta, a_log, bias, scale):
        return chunked(q, k, v, f, beta, a_log, bias, scale)[0]
    if remat is not None:
        layer = jax.checkpoint(layer, **remat)

    def loss(q, *rest):
        o = layer(layer(q, *rest).astype(q.dtype), *rest)
        return jnp.sum(jnp.sin(o.astype(jnp.float32)))
    return jax.jit(jax.grad(loss, argnums=tuple(range(8))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_a_layer_rematerialised_with_the_saved_names_is_the_plain_core(dtype):
    """Four chunks in groups of two, the last padded: two layers under
    ``jax.checkpoint`` with ``KDA_SAVED`` give the gradients of the same
    layers not rematerialised, float32 to the bit, bfloat16 to its own
    rounding (the same arithmetic in the same order either way)."""
    args = core_inputs(250, 1.6, dv=8)
    args = tuple(a.astype(dtype) for a in args[:4]) + args[4:]
    plain = layer_pair(None)(*args)
    saved = layer_pair({"policy": KDA_SAVED})(*args)
    names = ("q", "k", "v", "f", "beta", "a_log", "bias", "scale")
    for name, a, b in zip(names, saved, plain):
        assert a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert bool(jnp.all(jnp.isfinite(a))), name
        if dtype == jnp.float32:
            np.testing.assert_array_equal(a, b, err_msg=name)
        else:
            np.testing.assert_allclose(
                a, b, err_msg=name, rtol=0,
                atol=float(jnp.finfo(dtype).eps) * float(jnp.abs(b).max()))


def _outside_transpose(op_name: str) -> str:
    """An ``op_name`` path with every ``transpose(...)`` cut out: what is
    left names the scopes of a forward computation, the backward pass's
    recomputes among them."""
    while (i := op_name.find("transpose(")) >= 0:
        depth, j = 0, i + len("transpose")
        for j in range(j, len(op_name)):
            depth += {"(": 1, ")": -1}.get(op_name[j], 0)
            if depth == 0:
                break
        op_name = op_name[:i] + op_name[j + 1:]
    return op_name


def forward_chunk_loops(hlo_text: str) -> int:
    """The ``while`` loops of an optimized module whose bodies reach, through
    what they call, an instruction of ``attn/kda_chunk`` outside
    ``transpose(``: the loops that run a group's within-chunk forward."""
    computations, body = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            body = computations.setdefault(head[1], [])
        elif body is not None:
            body.append(line)

    def reaches(name):
        lines = computations.get(name, [])
        return any(
            scope_layer(_outside_transpose(path))[0] == "kda_chunk"
            for line in lines for path in re.findall(r'op_name="([^"]*)"',
                                                     line)) or any(
            reaches(called) for line in lines
            for called in re.findall(r"(?:calls|to_apply|body)=%?([\w.\-]+)",
                                     line))
    return sum(reaches(name) for lines in computations.values()
               for line in lines
               for name in re.findall(r" while\(.*body=%?([\w.\-]+)", line))


@pytest.mark.parametrize("remat,per_layer", [({"policy": KDA_SAVED}, 2),
                                              ({}, 3)])
def test_a_rematerialised_layer_runs_its_within_chunk_forward_twice(
        remat, per_layer):
    """The compiled gradient of two rematerialised layers: with
    ``KDA_SAVED`` each layer's groups run forward in the forward pass and
    once more inside the loop that transposes them; without the names the
    layer's recompute runs them a third time."""
    args = core_inputs(250, 1.6, dv=8)
    text = layer_pair(remat).lower(*args).compile().as_text()
    assert forward_chunk_loops(text) == 2 * per_layer
