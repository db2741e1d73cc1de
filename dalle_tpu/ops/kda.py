"""The gated delta rule in chunked (WY) form, in two forms of its decay:
Kimi delta attention's (arXiv:2510.26692, section 3), one decay per key
channel, and Gated DeltaNet's (arXiv:2412.06464), one decay per head.

Per head, with a state ``S`` of (d_k, d_v), ``S_0 = 0``, decay
``a_t = exp(g_t)`` (``g_t <= 0``, one per key channel or one per head) and
``b_t`` in (0, 2):

    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

(with one decay a head ``Diag(a_t)`` is the scalar ``a_t`` and commutes with
the rest: ``S_t = a_t (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T``).

**The chunked form.** Within a chunk whose state at entry is ``S``, with
``G_t`` the cumulative sum of ``g`` from the chunk's start, the update is
``S_t = Diag(a_t) S_{t-1} + k_t u_t^T`` for pseudo-values ``u`` that solve a
unit lower-triangular system:

    A[t, j]   = b_t sum_c k_tc k_jc exp(G_tc - G_jc)        (j < t)
    Aqk[t, j] =     sum_c q_tc k_jc exp(G_tc - G_jc)        (j <= t)
    T  = (I + A)^-1,  U0 = T (b v),  W = T (b exp(G) k)
    u  = U0 - W S
    o  = (exp(G) q) S + Aqk u
    S' = Diag(exp(G_C)) S + (k exp(G_C - G))^T u

``kda_chunked`` is one scan over groups of ``GROUP`` chunks carrying only
``S``: a group computes everything of its chunks that does not depend on
``S`` (scope ``attn/kda_chunk``), then steps ``S`` through them (scope
``attn/kda_state``), and its backward pass recomputes it once from the
state at its entry. A layer rematerialised with ``KDA_SAVED`` keeps those
entry states and the output, so its own recompute runs no group.

**Never the exponential of a positive sum of gates.** ``exp(-G_j)`` over 64
positions overflows float32 for gates the initialisation produces, so no
decay is ever split into ``exp(G_t) exp(-G_j)`` across a chunk. A chunk's
triangle is cut by halves down to sub-blocks of ``SUB`` positions: the
square of a later half's rows against an earlier half's keys splits at the
cumulative sum ``r`` at the later half's entry, ``exp(G_t - r)
exp(r - G_j)``, both exponents at most 0; a sub-block on the diagonal forms
``exp(G_t - G_j)`` directly for ``t >= j``. ``A``, ``Aqk`` and ``T`` are
kept as those pieces and merged at the end (``_merged``), every square of
one size in one product. Gates, cumulative sums, the triangular inverse and
the state are float32; the operands of the matrix products take the inputs'
type (bfloat16 in training), accumulated in float32.

**The diagonal sub-blocks' backward is written by hand**
(``_diagonal_squares``' ``custom_vjp``). It keeps the squares' inputs
alone, ``x``, ``ks`` and ``cs``, and forms the decays and terms of the
sub-blocks again inside each fusion that sums them: for each key the sum
over later rows, for each row and slot of ``x`` the sum over earlier keys.
Those sums are float32 reductions of (t, j, c) terms over a vector
register's rows or across registers (with keys of 128 a key's channels are
one register's lanes), so no product with 0 / 1 columns is needed where the
forward needs ``pick``, and no (t, j, c) tensor, 8 x the size of ``x``,
reaches memory. The diagonal itself, exp(0), enters only as a cotangent's
diagonal times a key or a row.

**One decay a head** (``f`` of shape (b, n, h)): the decay between two
positions of a chunk is one number, ``Gamma[t, j] = exp(G_t - G_j)`` for
``j <= t`` (at most 1), so the grams are two plain products under one
(CHUNK, CHUNK) matrix, ``A = tril(diag(b) (K K^T) * Gamma, -1)``, ``Aqk =
tril((Q K^T) * Gamma)``, with none of the halves and sub-blocks the
channel-wise decay needs; the inverse, the state's step and the read-out
are shared. That form runs under the scopes ``attn/gdn_chunk`` and
``attn/gdn_state``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

CHUNK = 64      # positions a chunk
# positions a sub-block of a chunk (CHUNK / SUB a power of 2): the sublanes
# of one float32 tile, so that ``_diagonal_squares``' two views of its terms
# are the same bytes. At 16 they are not and the backward pass copies them:
# one layer's core, forward, remat and backward on the v5e, 171 ms at 8 and
# 435 ms at 16 (PERF.md, PR 32)
SUB = 8
GROUP = 2       # chunks whose state-free work is alive at once

# what a layer rematerialised around ``kda_chunked`` keeps for its backward
# pass: the state at each group's entry and the core's output
KDA_SAVED = jax.checkpoint_policies.save_only_these_names("kda_entry",
                                                          "kda_out")

HIGHEST = jax.lax.Precision.HIGHEST


def chunks_of(n: int) -> int:
    return -(-n // CHUNK)


def _mm(eq: str, a, b, dtype):
    """A product whose operands take the compute type and whose sum is
    float32 (``highest`` so that a float32 compute type stays float32 on
    the TPU)."""
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32, precision=HIGHEST)


def _halves(x, s: int):
    """(..., c, w) as (..., blocks of 2s positions, 2 halves, s, w)."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] // (2 * s), 2, s)
                     + x.shape[-1:])


def _merged(diag, lows, corner=lambda ta, low, tb: low):
    """A lower-triangular (..., c, c) from its pieces: ``diag`` (..., c / s,
    s, s), the squares on the diagonal, and ``lows``, one (..., c / 2s', s',
    s') for s' = s, 2s, ..., c / 2: the squares below them, level by level.
    Two neighbours and the square between them become [[ta, 0], [corner(ta,
    low, tb), tb]]."""
    m = diag
    for low in lows:
        pair = m.reshape(m.shape[:-3] + (m.shape[-3] // 2, 2) + m.shape[-2:])
        ta, tb = pair[..., 0, :, :], pair[..., 1, :, :]
        low = corner(ta, low, tb)
        m = jnp.concatenate(
            [jnp.concatenate([ta, jnp.zeros_like(low)], -1),
             jnp.concatenate([low, tb], -1)], -2)
    return m[..., 0, :, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _diagonal_squares(x, ks, cs, dtype):
    """sum_c x_tc k_jc exp(cs_tc - cs_jc) for j <= t within a sub-block, 0
    for j > t: (..., 2, ns, SUB, SUB) float32 from ``x`` (..., 2, ns, SUB,
    d) and ``ks``, ``cs`` (..., ns, SUB, d). The decay between two positions
    of a sub-block is formed directly, where it is at most 1 and nowhere
    else. The (t, j, c) terms lie as (t, j d + c), a key and a channel along
    the lanes (with SUB rows one tile's sublanes, that view costs no copy),
    and their sum over the channels is a product with 0 / 1 columns that
    pick a key's lanes: the MXU adds the lanes, and no tensor has a last
    dimension of 1."""
    sub, d = ks.shape[-2:]
    along = lambda a: a.reshape(a.shape[:-2] + (1, sub * d))  # (j, c): lanes
    again = lambda a: jnp.tile(a, sub)                          # c -> (j, c)
    t_ge_j = jnp.repeat(jnp.tril(jnp.ones((sub, sub), bool)), d, axis=1)
    decay = jnp.exp(jnp.where(t_ge_j, again(cs) - along(cs), -jnp.inf))
    terms = again(x) * (along(ks) * decay)[..., None, :, :, :]
    pick = jnp.repeat(jnp.eye(sub, dtype=dtype), d, axis=0)     # (j d + c, j)
    return _mm("...k,kj->...j", terms, pick, dtype)


def _diagonal_squares_fwd(x, ks, cs, dtype):
    return _diagonal_squares(x, ks, cs, dtype), (x, ks, cs)


def _diagonal_squares_bwd(dtype, res, g):
    """The squares' cotangent ``g`` (..., 2, ns, SUB, SUB) back to ``x``,
    ``ks`` and ``cs``, float32. With D[t, j, c] = exp(cs_tc - cs_jc):

        dx[t, c] = g[t, t] k_tc + sum_{j < t} g[t, j] k_jc D
        dk[j, c] = sum_x g[j, j] x_jc + later[j, c]
        later    = sum_x sum_{t > j} g[t, j] x_tc D
        dcs      = sum_x x (dx - g[t, t] k) - k later

    (cs enters as x e^cs and k e^-cs). Only the pairs j < t carry a decay:
    the diagonal's exp(0) has no gradient. Sums that kept it would cancel
    it between the two sides of dcs, and what rounding leaves of it reaches
    the gates' gradient weighted by cumulative sums down to -400. Each sum
    forms D inside its own fusion, and no (t, j, c) tensor, 8 x ``x``, is
    written to memory (``tests/test_chip_compile.py``); one D shared by the
    two slots of ``x`` through a broadcast is what XLA would write."""
    del dtype           # no operand is rounded: every sum is float32
    x, ks, cs = res
    sub = ks.shape[-2]
    slots = range(x.shape[-4])
    below = jnp.tril(jnp.ones((sub, sub), bool), -1)[:, :, None]

    def decay():        # (..., t, j, c)
        return jnp.exp(jnp.where(below, cs[..., :, None, :]
                                 - cs[..., None, :, :], -jnp.inf))

    # for each slot of x and each row, the sum over the keys j < t
    dx = jnp.stack([jnp.sum(g[..., i, :, :, :, None] * ks[..., None, :, :]
                            * decay(), axis=-2) for i in slots], axis=-4)
    # for each key, the sum over the rows t > j and the slots
    later = jnp.sum(decay() * sum(g[..., i, :, :, :, None]
                                  * x[..., i, :, :, None, :] for i in slots),
                    axis=-3)
    on_diagonal = jnp.diagonal(g, axis1=-2, axis2=-1)[..., None]
    dcs = jnp.sum(x * dx, axis=-4) - ks * later
    dx = dx + on_diagonal * ks[..., None, :, :, :]
    dk = later + jnp.sum(on_diagonal * x, axis=-4)
    return dx.astype(x.dtype), dk.astype(ks.dtype), dcs.astype(cs.dtype)


_diagonal_squares.defvjp(_diagonal_squares_fwd, _diagonal_squares_bwd)


def _decayed_grams(q, k, cum, dtype):
    """The pieces (``_merged``) of sum_c x_tc k_jc exp(cum_tc - cum_jc) over
    j <= t, for x = q and x = k side by side on an axis of 2 before the
    pieces' own three. ``q``, ``k``, ``cum``: (..., c, d) float32."""
    c, d = q.shape[-2:]
    x = jnp.stack([q, k], axis=-3)
    subs = lambda a: a.reshape(a.shape[:-2] + (c // SUB, SUB, d))
    diag = _diagonal_squares(subs(x), subs(k), subs(cum), dtype)
    lows, s = [], SUB
    while s < c:
        # blocks of 2s positions: the later half's rows against the earlier
        # half's keys, split at the later half's entry; both exponents are
        # sums of gates, so at most 0
        xs, ks, cs = _halves(x, s), _halves(k, s), _halves(cum, s)
        r = cs[..., 0, s - 1, :][..., None, :]
        left = (xs[..., 1, :, :]
                * jnp.exp(cs[..., 1, :, :] - r)[..., None, :, :, :])
        right = ks[..., 0, :, :] * jnp.exp(r - cs[..., 0, :, :])
        lows.append(_mm("...xptd,...pjd->...xptj", left, right, dtype))
        s *= 2
    return diag, lows


@jax.custom_vjp
def _square_inverse(a):
    """(I + a)^-1 for strictly lower-triangular ``a`` (..., s, s), float32,
    by forward substitution, row by row. No power of ``a`` is formed: the
    Neumann products lose every digit when the keys of a chunk are alike."""
    s = a.shape[-1]
    eye = jnp.eye(s, dtype=a.dtype)
    rows = []
    for i in range(s):
        row = jnp.broadcast_to(eye[i], a.shape[:-1])
        if i:
            row = row - jnp.einsum("...j,...jk->...k", a[..., i, :i],
                                   jnp.stack(rows, axis=-2), precision=HIGHEST)
        rows.append(row)
    return jnp.stack(rows, axis=-2)


def _square_inverse_fwd(a):
    t = _square_inverse(a)
    return t, t


def _square_inverse_bwd(t, g):
    # d(I + a)^-1 = -T da T: two products, not the substitution's transpose
    return (-jnp.tril(jnp.einsum("...ji,...jk,...lk->...il", t, g, t,
                                 precision=HIGHEST), -1),)


_square_inverse.defvjp(_square_inverse_fwd, _square_inverse_bwd)


def _unit_lower_inverse(diag, lows):
    """(I + a)^-1, float32, for the strictly lower-triangular ``a`` whose
    pieces (``_merged``) are ``diag`` and ``lows``: the squares on the
    diagonal by forward substitution, two inverted neighbours merged as
    [[Ta, 0], [-Tb a_ba Ta, Tb]]."""
    return _merged(
        _square_inverse(diag), lows,
        lambda ta, low, tb: -jnp.einsum("...ij,...jk,...kl->...il", tb, low,
                                        ta, precision=HIGHEST))


def _l2_normalise(x, eps: float = 1e-6):
    """x / sqrt(|x|^2 + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def _log_decay(f, a_log, bias, lower_bound: float = 0.0):
    """The log-decay in float32 from ``f`` (..., h, d), ``a_log`` (h,) and
    ``bias`` (h, d): g = -exp(A_h) softplus(f + b), or, with a
    ``lower_bound`` < 0, the public implementation's bounded gate
    g = lower_bound * sigmoid(exp(A_h) (f + b)), in (lower_bound, 0): a
    chunk's cumulative sum is then at least ``CHUNK * lower_bound``. One
    decay a head: ``f`` (..., h) and ``bias`` (h,)."""
    f32 = jnp.float32
    if bias.ndim == 1:
        return (-jnp.exp(a_log.astype(f32))
                * jax.nn.softplus(f.astype(f32) + bias.astype(f32)))
    if lower_bound < 0:
        return lower_bound * jax.nn.sigmoid(
            jnp.exp(a_log.astype(f32))[:, None]
            * (f.astype(f32) + bias.astype(f32)))
    return (-jnp.exp(a_log.astype(f32))[:, None]
            * jax.nn.softplus(f.astype(f32) + bias.astype(f32)))


def _within_chunks(q, k, v, f, beta, a_log, bias, lower_bound):
    """Everything of some chunks that does not depend on the state: what the
    scan over the chunks' states reads, and the chunks' most negative
    cumulative log-decay. Inputs (chunks, b, h, CHUNK, ...)."""
    f32, dtype = jnp.float32, v.dtype
    # the barriers keep the casts inside the loop over the groups: hoisted
    # out of it they would run on all chunks at once and leave float32
    # copies of every input and output alive beside the layer's state
    q, k, v, f, beta = jax.lax.optimization_barrier((q, k, v, f, beta))
    q = _l2_normalise(q) * q.shape[-1] ** -0.5
    k, beta = _l2_normalise(k), beta.astype(f32)[..., None]
    # (CHUNK, d) behind the heads for the head's own A and bias
    g = jnp.swapaxes(_log_decay(jnp.swapaxes(f, -3, -2), a_log, bias,
                                lower_bound), -3, -2)
    cum = jnp.cumsum(g, axis=-2)
    last = cum[..., -1:, :]
    diag, lows = _decayed_grams(q, k, cum, dtype)
    aqk = _merged(diag[..., 0, :, :, :],
                  [low[..., 0, :, :, :] for low in lows])
    # A: the keys' own grams under the diagonal, each row times its beta
    by_row = lambda s: _halves(beta, s)[..., 1, :, :]
    t = _unit_lower_inverse(
        beta.reshape(diag.shape[:-4] + diag.shape[-3:-1] + (1,))
        * jnp.tril(diag[..., 1, :, :, :], -1),
        [by_row(low.shape[-1]) * low[..., 1, :, :, :] for low in lows])
    u0 = _mm("...tj,...jv->...tv", t, beta * v.astype(f32), dtype)
    w = _mm("...tj,...jd->...td", t, beta * jnp.exp(cum) * k, dtype)
    return jax.lax.optimization_barrier((
        (u0.astype(dtype), w.astype(dtype), (jnp.exp(cum) * q).astype(dtype),
         aqk.astype(dtype), (k * jnp.exp(last - cum)).astype(dtype),
         jnp.exp(last[..., 0, :])), jnp.min(last)))


def _lower_pieces(m):
    """The pieces (``_merged``) of a lower-triangular (..., c, c): the
    squares of ``SUB`` on its diagonal and, level by level, the squares
    below them."""
    c = m.shape[-1]
    diag = jnp.stack([m[..., i:i + SUB, i:i + SUB]
                      for i in range(0, c, SUB)], -3)
    lows, s = [], SUB
    while s < c:
        lows.append(jnp.stack([m[..., i + s:i + 2 * s, i:i + s]
                               for i in range(0, c, 2 * s)], -3))
        s *= 2
    return diag, lows


def _within_chunks_per_head(q, k, v, f, beta, a_log, bias):
    """``_within_chunks`` for one decay a head: ``f`` and ``beta`` (chunks,
    b, h, CHUNK). The decay between two positions, ``Gamma[t, j] = exp(G_t -
    G_j)`` for ``j <= t``, is formed directly and is at most 1."""
    f32, dtype = jnp.float32, v.dtype
    q, k, v, f, beta = jax.lax.optimization_barrier((q, k, v, f, beta))
    q = _l2_normalise(q) * q.shape[-1] ** -0.5
    k, beta = _l2_normalise(k), beta.astype(f32)[..., None]
    # (CHUNK, h) behind the heads for the head's own A and bias
    cum = jnp.cumsum(jnp.swapaxes(_log_decay(jnp.swapaxes(f, -2, -1), a_log,
                                             bias), -2, -1), axis=-1)
    last = cum[..., -1:]
    causal = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    gamma = jnp.exp(jnp.where(causal, cum[..., :, None] - cum[..., None, :],
                              -jnp.inf))
    aqk = _mm("...td,...jd->...tj", q, k, dtype) * gamma
    a = beta * jnp.tril(_mm("...td,...jd->...tj", k, k, dtype) * gamma, -1)
    t = _unit_lower_inverse(*_lower_pieces(a))
    decay = jnp.exp(cum)[..., None]
    u0 = _mm("...tj,...jv->...tv", t, beta * v.astype(f32), dtype)
    w = _mm("...tj,...jd->...td", t, beta * decay * k, dtype)
    return jax.lax.optimization_barrier((
        (u0.astype(dtype), w.astype(dtype), (decay * q).astype(dtype),
         aqk.astype(dtype), (k * jnp.exp(last - cum)[..., None]).astype(dtype),
         jnp.exp(last)), jnp.min(last)))


def kda_chunked(q, k, v, f, beta, *, a_log, bias, norm_scale, eps,
                lower_bound: float = 0.0):
    """The layer's heads over whole sequences, from the projections' outputs
    to the normalised read-out. ``q``, ``k``: (b, n, h, d_k), not yet
    normalised (here each is divided by its norm, ``q`` times d_k^-1/2);
    ``v``: (b, n, h, d_v); ``f``: (b, n, h, d_k), the decay's pre-activation
    (the log-decay is ``_log_decay(f, a_log, bias, lower_bound)``, ``a_log``
    (h,), ``bias`` (h, d_k), ``lower_bound`` the decay's form); ``beta``:
    (b, n, h). One decay a head (Gated DeltaNet's form): ``f`` (b, n, h)
    and ``bias`` (h,), the log-decay -exp(A_h) softplus(f + b), the work
    under the scopes ``attn/gdn_chunk`` and ``attn/gdn_state``. Returns
    ``o`` (b, n, h, d_v) in ``v``'s
    type, RMS-normalised over a head's width in float32 (``norm_scale``
    (d_v,), ``eps``), and the most negative cumulative log-decay over a
    chunk (a float32 scalar: how far the chunked form is from float32's
    range). ``n`` need not be a multiple of ``CHUNK``: positions padded
    behind the end have ``k = v = 0`` and a decay of 1, and change no state.

    The heads' own arithmetic on either side of the recurrence is done here,
    inside the loops, a group of chunks at a time (as the public kernels'
    ``use_qk_l2norm_in_kernel`` / ``use_gate_in_kernel`` do): in the layer it
    would be float32 work on whole (b, n, h, d) tensors on the far side of a
    relayout from (b, n, h d), and every such tensor is a quarter of a GB at
    2 x 4352 x 64 x 128.

    The chunks run ``GROUP`` at a time in one scan, each group
    rematerialised in the backward pass: the decays between the positions of
    a sub-block are (chunks, b, h, CHUNK, SUB, d_k) float32 numbers, 2.3 GB
    a layer at 2 x 4352 positions of 64 heads were all chunks alive at once,
    and the terms they multiply twice that. The scan saves the state at each
    group's entry (``kda_entry``) and the output (``kda_out``); a layer
    rematerialised with ``KDA_SAVED`` keeps those two and nothing else of
    the core, so that its backward pass runs a group's forward once, for
    the group's own transpose, and not a second time to rebuild the layer."""
    b, n, h, dk = q.shape
    dtype = v.dtype
    per_head = f.ndim == 3
    name = "gdn" if per_head else "kda"
    nc = chunks_of(n)
    group = max(d for d in range(1, GROUP + 1) if nc % d == 0)

    def chunked(x, behind=0):
        """(b, n, h, ...) -> (groups, group, b, h, CHUNK, ...), ``behind``
        behind n."""
        x = jnp.pad(x, ((0, 0), (0, nc * CHUNK - n))
                    + ((0, 0),) * (x.ndim - 2), constant_values=behind)
        x = x.reshape((b, nc, CHUNK) + x.shape[2:])
        x = jnp.moveaxis(jnp.moveaxis(x, 1, 0), 2, 3)
        return x.reshape((nc // group, group) + x.shape[1:])

    def group_steps(state, x):
        """One group: its chunks' state-free work, then its chunks' steps of
        the state, in order."""
        with jax.named_scope(f"attn/{name}_chunk"):
            xs, low = (_within_chunks_per_head(*x, a_log, bias) if per_head
                       else _within_chunks(*x, a_log, bias, lower_bound))
        outs = []
        with jax.named_scope(f"attn/{name}_state"):
            for u0, w, q_in, aqk, k_out, keep in zip(*xs):
                u = u0 - _mm("bhtd,bhdv->bhtv", w, state, dtype)
                o = (_mm("bhtd,bhdv->bhtv", q_in, state, dtype)
                     + _mm("bhtj,bhjv->bhtv", aqk, u, dtype))
                state = keep[..., None] * state + _mm(
                    "bhtd,bhtv->bhdv", k_out, u, dtype)
                o = (o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                                       + eps)
                     * norm_scale.astype(jnp.float32))
                outs.append(o.astype(dtype))
        return state, (jnp.stack(outs), low)

    def body(state, x):
        # the two things a rematerialised layer keeps for its backward pass
        # (``KDA_SAVED``): the state at a group's entry, from which the
        # group's backward recomputes the group, and the group's output,
        # which the layer's gate and output product read
        state = checkpoint_name(state, "kda_entry")
        state, (o, low) = jax.checkpoint(group_steps)(state, x)
        return state, (checkpoint_name(o, "kda_out"), low)

    with jax.named_scope(f"attn/{name}_chunk"):
        # behind the end no gate: softplus (or the bounded form's sigmoid)
        # of the least number is 0
        xs = (chunked(q), chunked(k), chunked(v),
              chunked(f, jnp.finfo(f.dtype).min), chunked(beta))
    with jax.named_scope(f"attn/{name}_state"):
        _, (o, lows) = jax.lax.scan(
            body, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    # (groups, group, b, h, CHUNK, d_v) -> (b, n, h, d_v)
    o = jnp.moveaxis(jnp.moveaxis(o.reshape((nc,) + o.shape[2:]), 3, 2), 0, 1)
    return o.reshape(b, nc * CHUNK, h, -1)[:, :n], jnp.min(lows)
