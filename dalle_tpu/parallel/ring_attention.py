"""Ring attention — sequence/context parallelism over the ``sp`` mesh axis.

The reference has NO sequence parallelism (SURVEY.md §5.7): its sequence-
scaling levers are sparse masks and reversible layers. For the TPU framework
long-context is first-class: activations shard along the sequence dimension
over the mesh's ``sp`` axis, each device holds its q chunk permanently, and
k/v chunks rotate around the ring via `lax.ppermute` (one ICI hop per step)
while a flash-style online softmax accumulates partial results — attention
over sequences P× longer than one chip's memory, with communication fully
overlappable with the chunk matmuls (XLA schedules the ppermute DMA against
the chunk work).

Two inner-loop implementations share the ring schedule:

  * ``kernel=True`` (default on TPU for chunks ≥ 512): each (q-chunk,
    k-chunk) pair runs the offset-parameterized Pallas flash kernels
    (ops/chunk_attention.py) — scores never materialize, per-device memory
    is O(n_local · d), and a whole-ring `jax.custom_vjp` recomputes chunks
    in a second ring pass for backward, saving only (q, k, v, o, lse).
    k/v rotate in their input dtype (bf16 halves ICI bytes vs the dense
    body's f32 rotation).
  * ``kernel=False``: the original dense einsum online-softmax body —
    reference semantics for tiny/odd chunk sizes and a cross-check oracle.

Causality is enforced by *global* position comparison (chunk origin × chunk
size + local offset), so the math is exact for any P. The ``zigzag`` layout
places sub-chunks (i, 2P-1-i) on device i: every device owns one early and
one late chunk, making the causal workload uniform; wholly-future quadrants
are skipped (dense: `lax.cond`; kernel: zero-trip in-kernel block bounds).

Structured sparse masks (axial/conv — pure functions of global (qpos, kpos),
ops/flash_attention.elem_fn_from_spec) compose with the ring in both bodies,
extending sequence parallelism beyond the full-causal pattern.

Collectives ride the mesh exactly like the scaling-book recipe: shard_map
gives per-device code, ppermute lowers to ICI neighbor exchange.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.chunk_attention import (chunk_flash_dkv, chunk_flash_dq,
                                   chunk_flash_fwd, merge_chunk, pick_block)
from ..ops.flash_attention import elem_fn_from_spec

NEG_INF = -1e9


def _ring_body(q, k, v, *, axis: str, nper: int, causal: bool, scale: float,
               n_valid: int, elem_fn=None):
    """Per-device program: q stays, k/v rotate. q/k/v: (b, h, n_local, d).
    ``n_valid``: true sequence length — keys at padded positions ≥ n_valid are
    masked (under causal masking valid queries already exclude them, but the
    non-causal path needs the explicit test)."""
    P_size = jax.lax.psum(1, axis)
    idx = jax.lax.axis_index(axis)
    n_local = q.shape[2]
    qf = q.astype(jnp.float32) * scale
    qpos = idx * n_local + jnp.arange(n_local)                     # global q pos

    acc = jnp.zeros(q.shape, jnp.float32)
    m = jnp.full((*q.shape[:3], 1), -1e9, jnp.float32)
    l = jnp.zeros((*q.shape[:3], 1), jnp.float32)
    perm = [(i, (i + 1) % nper) for i in range(nper)]

    k_cur, v_cur = k.astype(jnp.float32), v.astype(jnp.float32)
    for t in range(nper):
        src = (idx - t) % P_size            # ring origin of the current chunk
        s = jnp.einsum("bhid,bhjd->bhij", qf, k_cur)
        kpos = src * n_local + jnp.arange(n_local)
        vis = kpos[None, :] < n_valid
        if causal:
            vis &= kpos[None, :] <= qpos[:, None]                  # (i, j)
        if elem_fn is not None:
            vis &= elem_fn(qpos[:, None], kpos[None, :])
        s = jnp.where(vis[None, None], s, -1e9)   # (1,1,i|1,j) broadcasts
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > -0.5e9, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bhij,bhjd->bhid", p, v_cur)
        m = m_new
        if t + 1 < nper:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)
    safe_l = jnp.where(l > 0, l, 1.0)
    return (acc / safe_l).astype(q.dtype)


def _ring_body_zigzag(q, k, v, *, axis: str, nper: int, scale: float,
                      n_valid: int, elem_fn=None):
    """Causal ring with zigzag chunk assignment: the sequence is split into
    2P sub-chunks of m rows and device i holds sub-chunks (i, 2P-1-i), so
    every device owns one early and one late chunk — the causal workload is
    uniform instead of triangular. Each (q-sub, k-sub) quadrant whose k
    origin is wholly in the q sub's future is skipped via ``lax.cond``;
    because the early/late mix is the same on every device, the skipped work
    is ~half of every device's steps (in the plain layout device 0 would
    idle while device P-1 never skips — no critical-path win)."""
    idx = jax.lax.axis_index(axis)
    m = q.shape[2] // 2
    qf = q.astype(jnp.float32) * scale
    origins_here = (idx, 2 * nper - 1 - idx)                  # sub-chunk ids
    perm = [(i, (i + 1) % nper) for i in range(nper)]

    def quadrant(acc, mx, l, q_sub, qpos, k_sub, v_sub, kpos):
        s = jnp.einsum("bhid,bhjd->bhij", q_sub, k_sub)
        vis = (kpos[None, :] < n_valid) & (kpos[None, :] <= qpos[:, None])
        if elem_fn is not None:
            vis &= elem_fn(qpos[:, None], kpos[None, :])
        s = jnp.where(vis[None, None], s, -1e9)
        m_new = jnp.maximum(mx, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(s > -0.5e9, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(mx - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + jnp.einsum("bhij,bhjd->bhid", p, v_sub)
        return acc, m_new, l

    # per-q-sub accumulators, derived from q so they carry the same
    # varying-over-axis type as the cond's true branch (plain constants are
    # unvarying and shard_map rejects the branch mismatch)
    state = []
    for r in range(2):
        z = qf[:, :, r * m:(r + 1) * m] * 0.0
        state.append((z, z[..., :1] - 1e9, z[..., :1]))

    k_cur, v_cur = k.astype(jnp.float32), v.astype(jnp.float32)
    for t in range(nper):
        src = (idx - t) % nper
        k_origins = (src, 2 * nper - 1 - src)
        for s_i in range(2):
            o_k = k_origins[s_i]
            k_sub = k_cur[:, :, s_i * m:(s_i + 1) * m]
            v_sub = v_cur[:, :, s_i * m:(s_i + 1) * m]
            kpos = o_k * m + jnp.arange(m)
            for r in range(2):
                o_q = origins_here[r]
                q_sub = qf[:, :, r * m:(r + 1) * m]
                qpos = o_q * m + jnp.arange(m)
                acc, mx, l = state[r]
                state[r] = jax.lax.cond(
                    o_k <= o_q,              # any visible entry in quadrant
                    lambda a, b, c: quadrant(a, b, c, q_sub, qpos,
                                             k_sub, v_sub, kpos),
                    lambda a, b, c: (a, b, c),
                    acc, mx, l)
        if t + 1 < nper:
            k_cur = jax.lax.ppermute(k_cur, axis, perm)
            v_cur = jax.lax.ppermute(v_cur, axis, perm)

    outs = []
    for acc, mx, l in state:
        safe_l = jnp.where(l > 0, l, 1.0)
        outs.append((acc / safe_l).astype(q.dtype))
    return jnp.concatenate(outs, axis=2)


# ---------------------------------------------------------------------------
# kernelized ring: Pallas chunk kernels inside the ring schedule, whole-ring
# custom_vjp (backward = second ring pass, recomputing chunks flash-style)
# ---------------------------------------------------------------------------

def _make_flash_ring_body(axis: str, nper: int, causal: bool, scale: float,
                          n_valid: int, block: int, interpret: bool,
                          mask_spec, zigzag: bool):
    """Per-device ring program using the chunk kernels. Saves only
    (q, k, v, o, lse) for backward — the O(n_local) residual footprint that
    the dense body (autodiff through the unrolled loop) cannot give."""
    elem_fn = elem_fn_from_spec(mask_spec)
    kw = dict(scale=scale, n_valid=n_valid, causal=causal, block_q=block,
              block_k=block, elem_fn=elem_fn, interpret=interpret)
    perm = [(i, (i + 1) % nper) for i in range(nper)]

    def fwd_math(q, k, v):
        idx = jax.lax.axis_index(axis)
        n_local = q.shape[2]
        if zigzag:
            m = n_local // 2
            q_origins = (idx, 2 * nper - 1 - idx)
            state = [(jnp.zeros((*q.shape[:2], m, q.shape[3]), jnp.float32),
                      jnp.full((*q.shape[:2], m), NEG_INF, jnp.float32))
                     for _ in range(2)]
            k_cur, v_cur = k, v
            for t in range(nper):
                src = (idx - t) % nper
                k_origins = (src, 2 * nper - 1 - src)
                for s_i in range(2):
                    k_sub = k_cur[:, :, s_i * m:(s_i + 1) * m]
                    v_sub = v_cur[:, :, s_i * m:(s_i + 1) * m]
                    for r in range(2):
                        q_sub = q[:, :, r * m:(r + 1) * m]
                        o_t, lse_t = chunk_flash_fwd(
                            q_sub, k_sub, v_sub, q_origins[r] * m,
                            k_origins[s_i] * m, **kw)
                        state[r] = merge_chunk(*state[r], o_t, lse_t)
                if t + 1 < nper:
                    k_cur = jax.lax.ppermute(k_cur, axis, perm)
                    v_cur = jax.lax.ppermute(v_cur, axis, perm)
            o = jnp.concatenate([s[0] for s in state], axis=2)
            lse = jnp.concatenate([s[1] for s in state], axis=2)
        else:
            o = jnp.zeros(q.shape, jnp.float32)
            lse = jnp.full(q.shape[:3], NEG_INF, jnp.float32)
            k_cur, v_cur = k, v
            for t in range(nper):
                src = (idx - t) % nper
                o_t, lse_t = chunk_flash_fwd(q, k_cur, v_cur, idx * n_local,
                                             src * n_local, **kw)
                o, lse = merge_chunk(o, lse, o_t, lse_t)
                if t + 1 < nper:
                    k_cur = jax.lax.ppermute(k_cur, axis, perm)
                    v_cur = jax.lax.ppermute(v_cur, axis, perm)
        return o, lse

    @jax.custom_vjp
    def f(q, k, v):
        o, _ = fwd_math(q, k, v)
        return o.astype(q.dtype)

    def f_fwd(q, k, v):
        o, lse = fwd_math(q, k, v)
        o = o.astype(q.dtype)
        # empty rows: -1e9 (merge weight 0) → +1e9 so backward's
        # p = exp(s - lse) is exactly 0 (matches ops/flash_attention.py)
        lse = jnp.where(lse <= 0.5 * NEG_INF, -NEG_INF, lse)
        return o, (q, k, v, o, lse)

    def f_bwd(res, do):
        q, k, v, o, lse = res
        idx = jax.lax.axis_index(axis)
        n_local = q.shape[2]
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), -1)
        k_cur, v_cur = k, v
        dk_cur = jnp.zeros(k.shape, jnp.float32)
        dv_cur = jnp.zeros_like(dk_cur)
        if zigzag:
            m = n_local // 2
            q_origins = (idx, 2 * nper - 1 - idx)
            dq_subs = [jnp.zeros((*q.shape[:2], m, q.shape[3]), jnp.float32)
                       for _ in range(2)]
            for t in range(nper):
                src = (idx - t) % nper
                k_origins = (src, 2 * nper - 1 - src)
                dk_parts, dv_parts = [], []
                for s_i in range(2):
                    k_sub = k_cur[:, :, s_i * m:(s_i + 1) * m]
                    v_sub = v_cur[:, :, s_i * m:(s_i + 1) * m]
                    dk_inc = jnp.zeros((*q.shape[:2], m, q.shape[3]),
                                       jnp.float32)
                    dv_inc = jnp.zeros_like(dk_inc)
                    for r in range(2):
                        sl = slice(r * m, (r + 1) * m)
                        args = (q[:, :, sl], k_sub, v_sub, do[:, :, sl],
                                lse[:, :, sl], delta[:, :, sl],
                                q_origins[r] * m, k_origins[s_i] * m)
                        dq_subs[r] = dq_subs[r] + chunk_flash_dq(*args, **kw)
                        dkc, dvc = chunk_flash_dkv(*args, **kw)
                        dk_inc = dk_inc + dkc
                        dv_inc = dv_inc + dvc
                    dk_parts.append(dk_inc)
                    dv_parts.append(dv_inc)
                dk_cur = dk_cur + jnp.concatenate(dk_parts, axis=2)
                dv_cur = dv_cur + jnp.concatenate(dv_parts, axis=2)
                if t + 1 < nper:
                    k_cur = jax.lax.ppermute(k_cur, axis, perm)
                    v_cur = jax.lax.ppermute(v_cur, axis, perm)
                # dk/dv ride every hop (nper total) so each chunk's gradient
                # finishes the full circle back to its home device
                dk_cur = jax.lax.ppermute(dk_cur, axis, perm)
                dv_cur = jax.lax.ppermute(dv_cur, axis, perm)
            dq = jnp.concatenate(dq_subs, axis=2)
        else:
            dq = jnp.zeros(q.shape, jnp.float32)
            for t in range(nper):
                src = (idx - t) % nper
                args = (q, k_cur, v_cur, do, lse, delta,
                        idx * n_local, src * n_local)
                dq = dq + chunk_flash_dq(*args, **kw)
                dkc, dvc = chunk_flash_dkv(*args, **kw)
                dk_cur = dk_cur + dkc
                dv_cur = dv_cur + dvc
                if t + 1 < nper:
                    k_cur = jax.lax.ppermute(k_cur, axis, perm)
                    v_cur = jax.lax.ppermute(v_cur, axis, perm)
                dk_cur = jax.lax.ppermute(dk_cur, axis, perm)
                dv_cur = jax.lax.ppermute(dv_cur, axis, perm)
        return (dq.astype(q.dtype), dk_cur.astype(k.dtype),
                dv_cur.astype(v.dtype))

    f.defvjp(f_fwd, f_bwd)
    return f


def zigzag_perm(nper: int, m: int) -> "np.ndarray":
    """Sequence permutation placing sub-chunks (i, 2P-1-i) on device i."""
    import numpy as np
    parts = []
    for i in range(nper):
        parts.append(np.arange(i * m, (i + 1) * m))
        j = 2 * nper - 1 - i
        parts.append(np.arange(j * m, (j + 1) * m))
    return np.concatenate(parts)


@functools.lru_cache(maxsize=32)
def _make_ring_fn(mesh: Mesh, axis: str, causal: bool, nper: int, scale: float,
                  n_valid: int, zigzag: bool, kernel: bool, block: int,
                  interpret: bool, mask_spec):
    spec = P(None, None, axis, None)
    if kernel:
        body = _make_flash_ring_body(axis, nper, causal, scale, n_valid,
                                     block, interpret, mask_spec, zigzag)
        # pallas_call out_shapes carry no varying-manual-axes metadata;
        # correctness is covered by the numerics tests against the dense body
        return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)
    if zigzag:
        body = functools.partial(_ring_body_zigzag, axis=axis, nper=nper,
                                 scale=scale, n_valid=n_valid,
                                 elem_fn=elem_fn_from_spec(mask_spec))
    else:
        body = functools.partial(_ring_body, axis=axis, nper=nper,
                                 causal=causal, scale=scale, n_valid=n_valid,
                                 elem_fn=elem_fn_from_spec(mask_spec))
    return jax.shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec)


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                   mesh: Mesh, axis: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   zigzag: bool = False,
                   kernel: Optional[bool] = None,
                   block: Optional[int] = None,
                   mask_spec=None,
                   interpret: Optional[bool] = None) -> jnp.ndarray:
    """Sequence-parallel attention over (b, h, n, d) arrays whose sequence dim
    is (or will be) sharded along ``mesh[axis]``. Sequences that don't divide
    the axis are zero-padded; padded keys are masked, padded query rows are
    sliced off.

    ``zigzag`` (causal only) balances the causal workload by interleaving
    early/late sub-chunks per device and skipping wholly-invisible quadrants —
    exact, ~2x less attention compute at the critical path for large P.

    ``kernel``: run each chunk pair through the Pallas flash chunk kernels
    (O(n_local·d) memory, whole-ring custom_vjp) instead of the dense einsum
    body. Default: auto — on for TPU when the chunk size tiles cleanly and is
    ≥ 512 (below that the dense body's single fused einsum wins).

    ``mask_spec``: structured sparse pattern (axial/conv tuples accepted by
    ops/flash_attention.elem_fn_from_spec) applied on top of causal masking —
    evaluated on global positions, so sp composes with the DALL·E sparse
    attention mix. Block-aligned ('block') and arbitrary tabled masks are not
    supported under the ring (they need host-side block lists).
    """
    nper = mesh.shape[axis]
    n = q.shape[2]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if mask_spec is not None:
        assert mask_spec[0] in ("axial", "conv"), (
            "ring attention supports structured (axial/conv) mask specs only")
    if zigzag:
        assert causal, "zigzag is a causal-balancing layout"
        n_pad = -(-n // (2 * nper)) * (2 * nper)
        chunk = n_pad // (2 * nper)
    else:
        n_pad = -(-n // nper) * nper
        chunk = n_pad // nper
    blk = pick_block(chunk) if block is None else block
    if kernel is None:
        kernel = (blk is not None and chunk >= 512
                  and jax.default_backend() == "tpu")
    if kernel and blk is None:
        raise ValueError(f"chunk size {chunk} has no valid kernel tiling; "
                         "use kernel=False")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if n_pad != n:
        pad = ((0, 0), (0, 0), (0, n_pad - n), (0, 0))
        q, k, v = (jnp.pad(t, pad) for t in (q, k, v))
    fn = _make_ring_fn(mesh, axis, causal, nper, float(scale), n, zigzag,
                       bool(kernel), blk or 0, bool(interpret), mask_spec)
    if zigzag:
        import numpy as np
        perm = zigzag_perm(nper, n_pad // (2 * nper))
        inv = np.argsort(perm)
        qz, kz, vz = (jnp.take(t, perm, axis=2) for t in (q, k, v))
        out = jnp.take(fn(qz, kz, vz), inv, axis=2)
    else:
        out = fn(q, k, v)
    return out[:, :, :n] if n_pad != n else out


def shard_seq(mesh: Mesh, x, axis: str = "sp"):
    """Place (b, h, n, d) with the sequence dim sharded over ``axis``."""
    return jax.device_put(x, NamedSharding(mesh, P(None, None, axis, None)))
