"""Attention math — functional core shared by every attention layer.

Reference: dalle_pytorch/attention.py:39-99 (dense causal `Attention` with
stable softmax, key-padding mask, static mask, KV cache). The sparse variants
(attention.py:103-398) are realized as static masks over this same core — see
ops/attn_masks.py for the rationale — or via the Pallas kernels in
ops/flash_attention.py / ops/block_sparse.py.

TPU notes:
  * qk/av contractions are einsums on (b, h, n, d) — MXU-shaped, bf16-friendly.
  * masking is `jnp.where` folded into the softmax epilogue by XLA.
  * the decode cache is a *preallocated* (b, h, max_seq, d) buffer updated with
    `lax.dynamic_update_slice` and a scalar length — static shapes under jit,
    replacing the reference's growing-concat cache (attention.py:71-76).
"""

from __future__ import annotations

from typing import Optional

import flax.struct
import jax
import jax.numpy as jnp

# plain Python float, NOT jnp.float32(...): a module-level jnp constant would
# initialize the XLA backend at import time, which breaks
# jax.distributed.initialize in any process that imports dalle_tpu.parallel
# before connecting to the coordinator (weak-typed, so it never promotes
# bf16 score tensors either)
NEG_INF = -1e9


def stable_softmax(t: jnp.ndarray, axis: int = -1, alpha: float = 32.0 ** 2) -> jnp.ndarray:
    """Softmax with pre-division by alpha and detached-max subtraction
    (reference attention.py:27-30)."""
    t = t / alpha
    t = t - jax.lax.stop_gradient(jnp.max(t, axis=axis, keepdims=True))
    return jax.nn.softmax(t * alpha, axis=axis)


def attend(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
           causal: bool = True,
           key_mask: Optional[jnp.ndarray] = None,      # (b, j) True=valid
           static_mask: Optional[jnp.ndarray] = None,   # (i, j) True=may attend
           stable: bool = False,
           softmax_f32: bool = True,
           scale: Optional[float] = None) -> jnp.ndarray:
    """Dense attention. q: (b,h,i,d), k/v: (b,h,j,d) → (b,h,i,d).

    When i < j (cached decode), causality aligns the query block to the *end* of
    the key sequence, matching the reference's `triu_(j - i + 1)` convention.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q = q * scale
    dots = jnp.einsum("bhid,bhjd->bhij", q, k)
    i, j = dots.shape[-2], dots.shape[-1]
    # fill in the score dtype: an f32 constant would silently promote a bf16
    # score tensor back to full width
    neg = jnp.asarray(NEG_INF, dots.dtype)

    if key_mask is not None:
        dots = jnp.where(key_mask[:, None, None, :], dots, neg)
    if causal:
        qpos = jnp.arange(i) + (j - i)
        kpos = jnp.arange(j)
        dots = jnp.where(kpos[None, :] <= qpos[:, None], dots, neg)
    if static_mask is not None:
        # queries occupy key positions j-i..j-1 (same alignment as the causal
        # branch above), so index mask rows by key position, not from the end
        dots = jnp.where(static_mask[j - i:j, :j], dots, neg)

    softmax = stable_softmax if stable else jax.nn.softmax
    # f32 softmax is the safe default; bf16 keeps the (i, j) score tensor in
    # half width — it is the dominant HBM tensor of the whole model (the
    # softmax is still max-subtracted internally, so it cannot overflow)
    sm_dtype = jnp.float32 if softmax_f32 else dots.dtype
    attn = softmax(dots.astype(sm_dtype), axis=-1).astype(v.dtype)
    return jnp.einsum("bhij,bhjd->bhid", attn, v)


# the configured length from which the block-grid flash kernels
# (ops/flash_attention.py) take over from dense on the TPU
FLASH_MIN_SEQ = 2048


def attention_tier(use_pallas, seq_len: int, heads: int, dim_head: int,
                   backend: Optional[str] = None) -> str:
    """The training-attention tier of a multi-head model: "dense" (``attend``
    above), "fused" (ops/fused_attention.py) or "flash"
    (ops/flash_attention.py). The one place that decides; called once per
    model (``Transformer.setup``) with the CONFIGURED length, so a model
    keeps its tier at every runtime length.

    ``use_pallas`` is the config's field. "auto" chooses from what it
    observes: dense off the TPU (the kernels run in interpret mode there),
    flash from ``FLASH_MIN_SEQ`` up, fused where its backward fits the
    raised scoped-VMEM ceiling (``fused_fits``), dense otherwise. "off" or
    ``False`` is dense everywhere — the reference the kernels are tested
    against. Both thresholds are readings from before the benchmark
    (PERF.md §7 names the cells that would confirm or move them)."""
    if use_pallas is False or use_pallas == "off":
        return "dense"
    if use_pallas != "auto":
        raise ValueError(f'use_pallas must be "auto" or "off" (False), got '
                         f"{use_pallas!r}: the tier is chosen from the "
                         f"model's shape and the backend, not named")
    # asked only here: reading "off" must not start the XLA client
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu":
        return "dense"
    if seq_len >= FLASH_MIN_SEQ:
        return "flash"
    from .fused_attention import fused_fits
    return "fused" if fused_fits(seq_len, dim_head, heads) else "dense"


def _quantize_int8(x):
    """Per-(b, h, position) symmetric int8 quantization over the head dim.
    Returns (q int8, scale f32 with a trailing singleton dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


@flax.struct.dataclass
class KVCache:
    """Preallocated decode cache for one attention layer.

    Storage is ONE merged buffer (b, max_seq, 2*h*d) — sequence-major with
    K in the first h*d lanes and V in the rest — so the Pallas decode kernel
    (ops/decode_attention.py) streams a single contiguous block per batch
    row, and each decoded position appends with a single
    dynamic-update-slice (separate K/V buffers measured 2x the per-step
    update cost in the b64 decode profile). ``read_kv`` presents the
    conventional (b, h, S, d) view for the dense paths.

    ``dtype=jnp.int8`` stores quantized rows with per-(b, h, position) f32
    scales in a merged (b, 2h, max_seq) array (K scales rows 0..h) —
    halving the cache-read bandwidth that dominates batched decode.
    f32/bf16 dtypes store exactly.
    """
    kv: jnp.ndarray      # (b, max_seq, 2*h*d) — storage dtype
    scale: Optional[jnp.ndarray] = None   # (b, 2h, max_seq) f32; int8 only
    heads: int = flax.struct.field(pytree_node=False, default=1)

    @property
    def max_seq(self) -> int:
        """Sequence capacity == the park offset. A property (not a field)
        so the dense slab and the paged pool (ops/paged_kv.PagedKVCache,
        where capacity is NOT a storage dim) answer the same question
        through one attribute."""
        return self.kv.shape[1]

    @classmethod
    def init(cls, batch: int, heads: int, max_seq: int, dim_head: int,
             dtype=jnp.float32) -> "KVCache":
        z = jnp.zeros((batch, max_seq, 2 * heads * dim_head), dtype=dtype)
        if dtype == jnp.int8:
            s = jnp.zeros((batch, 2 * heads, max_seq), jnp.float32)
            return cls(z, s, heads=heads)
        return cls(z, heads=heads)

    @staticmethod
    def _flatten(x):
        """(b,h,n,d) → (b,n,h*d) rows."""
        b, h, n, d = x.shape
        return x.transpose(0, 2, 1, 3).reshape(b, n, h * d)

    def append(self, k_new: jnp.ndarray, v_new: jnp.ndarray, offset) -> "KVCache":
        """Write (b,h,n,d) new keys/values at position ``offset`` (scalar)."""
        if self.kv.dtype == jnp.int8:
            kq, ks = _quantize_int8(k_new)
            vq, vs = _quantize_int8(v_new)
            rows = jnp.concatenate([self._flatten(kq), self._flatten(vq)],
                                   axis=2)
            sc = jnp.concatenate([ks[..., 0], vs[..., 0]], axis=1)  # (b,2h,n)
            return self.replace(
                kv=jax.lax.dynamic_update_slice(self.kv, rows,
                                                (0, offset, 0)),
                scale=jax.lax.dynamic_update_slice(self.scale, sc,
                                                   (0, 0, offset)))
        rows = jnp.concatenate(
            [self._flatten(k_new.astype(self.kv.dtype)),
             self._flatten(v_new.astype(self.kv.dtype))], axis=2)
        return self.replace(
            kv=jax.lax.dynamic_update_slice(self.kv, rows, (0, offset, 0)))

    def append_rows(self, k_new: jnp.ndarray, v_new: jnp.ndarray,
                    offsets: jnp.ndarray) -> "KVCache":
        """Write (b,h,w,d) new keys/values at PER-ROW positions ``offsets``
        (b,) — the speculative-decode append, where batch rows have
        diverged (different rows accepted different draft lengths).

        Formulation matters enormously on TPU: a vmapped
        dynamic-update-slice lowers to a scatter the compiler treats as
        unsorted/aliasing and the b64 speculative loop measured 2.2x slower
        END-TO-END than the sequential path from this op alone. The shipped
        form — explicit (b, w) indices with unique_indices +
        indices_are_sorted, and the int8 scale scatter transposed to
        sequence-major so it never scatters along the minormost dim —
        removed the entire gap (0.88 s → 0.31 s at b64, r5 ablation)."""
        b, _, w, _ = k_new.shape
        ab = jnp.arange(b)
        idx = offsets[:, None] + jnp.arange(w)[None, :]          # (b, w)
        if self.kv.dtype == jnp.int8:
            kq, ks = _quantize_int8(k_new)
            vq, vs = _quantize_int8(v_new)
            rows = jnp.concatenate([self._flatten(kq), self._flatten(vq)],
                                   axis=2)
            sc = jnp.concatenate([ks[..., 0], vs[..., 0]], axis=1)  # (b,2h,w)
            kv = self.kv.at[ab[:, None], idx].set(
                rows, unique_indices=True, indices_are_sorted=True)
            scale = self.scale.transpose(0, 2, 1).at[ab[:, None], idx].set(
                sc.transpose(0, 2, 1), unique_indices=True,
                indices_are_sorted=True).transpose(0, 2, 1)
            return self.replace(kv=kv, scale=scale)
        rows = jnp.concatenate(
            [self._flatten(k_new.astype(self.kv.dtype)),
             self._flatten(v_new.astype(self.kv.dtype))], axis=2)
        kv = self.kv.at[ab[:, None], idx].set(
            rows, unique_indices=True, indices_are_sorted=True)
        return self.replace(kv=kv)

    def read_kv(self, dtype=None):
        """(k, v) as (b, h, S, d), dequantized when stored int8.
        ``dtype``: compute dtype of the dequantized values (default bf16 for
        int8 storage; pass the query dtype to match the matmul)."""
        b, S, hd2 = self.kv.shape
        h = self.heads
        kv = self.kv.reshape(b, S, 2, h, hd2 // (2 * h))
        k = kv[:, :, 0].transpose(0, 2, 1, 3)
        v = kv[:, :, 1].transpose(0, 2, 1, 3)
        if self.kv.dtype == jnp.int8:
            dt = dtype or jnp.bfloat16
            ks = self.scale[:, :h, :, None]        # (b,h,S,1)
            vs = self.scale[:, h:, :, None]
            return (k.astype(dt) * ks.astype(dt),
                    v.astype(dt) * vs.astype(dt))
        return k, v


def cached_attend(q: jnp.ndarray, cache: KVCache, length, *,
                  static_mask: Optional[jnp.ndarray] = None,
                  stable: bool = False,
                  qpos=None,
                  scale: Optional[float] = None,
                  use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """Single-step decode: q is (b,h,1,d); attends to cache[:length].

    ``length`` is a traced scalar — the full (b,h,max,d) cache participates in the
    matmul and positions ≥ length are masked, keeping shapes static under scan.
    ``qpos`` (defaults to length-1) indexes the static_mask row.

    On TPU with lane-tiled shapes this runs the Pallas decode kernel
    (ops/decode_attention.py — XLA's lowering of this op is the decode
    loop's dominant cost at ~2.3x the HBM roofline); ``use_kernel``
    overrides the auto-selection.
    """
    from .decode_attention import decode_attend_kernel, decode_kernel_supported
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and decode_kernel_supported(q, cache, stable=stable))
    if use_kernel:
        row = None
        if static_mask is not None:
            if qpos is None:
                qpos = length - 1
            row = jax.lax.dynamic_index_in_dim(static_mask, qpos, axis=0,
                                               keepdims=False)[: cache.kv.shape[1]]
        return decode_attend_kernel(q, cache, length, mask_row=row,
                                    scale=scale, out_dtype=q.dtype)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q = q * scale
    ck, cv = cache.read_kv(dtype=q.dtype)
    dots = jnp.einsum("bhid,bhjd->bhij", q, ck)             # (b,h,1,max)
    jpos = jnp.arange(ck.shape[2])
    valid = jpos[None, None, None, :] < length
    if static_mask is not None:
        if qpos is None:
            qpos = length - 1
        row = jax.lax.dynamic_index_in_dim(static_mask, qpos, axis=0, keepdims=False)
        # the mask may cover more positions than the cache holds (e.g. the final
        # sequence slot that is sampled but never fed back) — trim to cache size
        valid = valid & row[: ck.shape[2]][None, None, None, :]
    dots = jnp.where(valid, dots, NEG_INF)
    softmax = stable_softmax if stable else jax.nn.softmax
    attn = softmax(dots.astype(jnp.float32), axis=-1).astype(cv.dtype)
    return jnp.einsum("bhij,bhjd->bhid", attn, cv)


def cached_attend_window(q: jnp.ndarray, cache: KVCache, starts, *,
                         stable: bool = False,
                         scale: Optional[float] = None,
                         use_kernel: Optional[bool] = None) -> jnp.ndarray:
    """Multi-token cached decode with PER-ROW positions — the speculative
    verify step (models/dalle.py generate_images_tokens_speculative) and the
    serving engine's per-row decode + multi-row refill prefill
    (dalle_tpu/serve/engine.py).

    q: (b, h, w, d) — w window queries per row, row ``b`` occupying absolute
    positions ``starts[b] .. starts[b]+w-1`` (``starts``: (b,) traced). Query
    j of row b attends cache positions ≤ starts[b]+j; slots beyond that are
    masked, so stale entries from a previous round's rejected drafts are
    invisible (they get overwritten by later windows). Full causal attention
    only — static sparse masks would need per-row row gathers and no
    generation config uses them.

    On TPU with lane-tiled shapes this runs the windowed Pallas kernel
    (ops/decode_attention.decode_attend_window_kernel — per-row starts ride
    a prefetched scalar vector, w query rows share one launch);
    ``use_kernel`` overrides the auto-selection, which re-checks the RUNTIME
    shapes (like fused_fits) so an unfit shape always falls to this dense
    path, never a failing compile.
    """
    from .decode_attention import (decode_attend_window_kernel,
                                   decode_window_kernel_supported)
    if hasattr(cache, "pool"):
        # graftpage: paged block-pool cache — gather the page-table view
        # back into the exact dense slab layout, then run the IDENTICAL
        # math below (bitwise exactness by construction: same lanes, same
        # reduce widths, same masks). The TPU kernel path gathers first
        # too (decode_attention.decode_attend_window_paged) — the gather
        # is one take per dispatch vs the O(B) private-slab HBM the pool
        # replaces.
        dense = cache.gather_dense()
        if use_kernel is None:
            use_kernel = (jax.default_backend() == "tpu"
                          and decode_window_kernel_supported(q, dense,
                                                             stable=stable))
        if use_kernel:
            from .decode_attention import decode_attend_window_paged
            return decode_attend_window_paged(q, cache, starts, scale=scale,
                                              out_dtype=q.dtype)
        return cached_attend_window(q, dense, starts, stable=stable,
                                    scale=scale, use_kernel=False)
    if use_kernel is None:
        use_kernel = (jax.default_backend() == "tpu"
                      and decode_window_kernel_supported(q, cache,
                                                         stable=stable))
    if use_kernel:
        return decode_attend_window_kernel(q, cache, starts, scale=scale,
                                           out_dtype=q.dtype)
    if scale is None:
        scale = q.shape[-1] ** -0.5
    q = q * scale
    ck, cv = cache.read_kv(dtype=q.dtype)
    dots = jnp.einsum("bhid,bhjd->bhij", q, ck)             # (b,h,w,max)
    w = q.shape[2]
    jpos = jnp.arange(ck.shape[2])
    qabs = starts[:, None] + jnp.arange(w)[None, :]          # (b, w)
    valid = jpos[None, None, None, :] <= qabs[:, None, :, None]
    dots = jnp.where(valid, dots, NEG_INF)
    softmax = stable_softmax if stable else jax.nn.softmax
    attn = softmax(dots.astype(jnp.float32), axis=-1).astype(cv.dtype)
    return jnp.einsum("bhij,bhjd->bhid", attn, cv)
