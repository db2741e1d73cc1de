"""The transformer stack — training forward + cached incremental decode.

Reference: dalle_pytorch/transformer.py (Transformer :204-350, LayerScale :74-88,
PreNorm :92-102, GEGLU/FeedForward :106-122, PreShiftToken :126-200, DivideMax
:29-36, cache adapters :38-71) and attention.py (full/axial/conv/sparse variants).

TPU-first redesign decisions:
  * Every sparse attention variant is the dense MXU kernel + a compile-time
    static mask (ops/attn_masks.py). The reference itself proves mask-equivalence
    via `optimize_for_inference` (transformer.py:333-350). Pallas kernels slot in
    behind the same interface (ops.attention.attention_tier chooses).
  * The decode cache is a pytree of preallocated buffers threaded functionally
    (static shapes under jit/scan) — replacing the reference's mutated dicts,
    growing concats, and deques (transformer.py:38-71,138-153; attention.py:71-76).
  * Token-shift ring buffers store *pre-shift* chunks in both prefill and decode.
    (The reference's prefill stores post-shift chunks (transformer.py:193-197) —
    inconsistent with its own decode path (:144) — a latent bug that only
    manifests with image priming + shift_tokens; not replicated.)
  * Layer sharing (shared_attn_ids/shared_ff_ids) is flax module reuse: calling
    one module instance at several depths shares its params. Caches stay
    per-depth, matching the reference's per-index cache keys (:280-287).
  * Dropout keys are explicit; reversible blocks don't need the reference's RNG
    save/restore dance (reversible.py:20-50).
"""

from __future__ import annotations

import contextlib
from itertools import cycle, islice
from typing import Any, Dict, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..config import TransformerConfig
from ..ops.attention import (KVCache, attend, attention_tier, cached_attend,
                             cached_attend_window)
from ..ops.attn_masks import build_mask
from ..ops.kda import CHUNK, KDA_SAVED, chunks_of
from ..ops.quantize_weights import QDense
from ..ops.rotary import (apply_rotary, dalle_pos_emb, seq_yarn_table,
                          yarn_mscale)
from .hybrid_attention import GatedGQAttention, KimiDeltaAttention
from .latent_moe import (MLAttention, MoEFeedForward, RMSNorm,
                         SwiGLUFeedForward)

# how a counter of the layers that count is reduced over depth (the rest
# are summed)
_WORST_LAYER = {"moe_load_max_over_mean": jnp.max, "kda_logdecay_min": jnp.min}


def reduce_counters(counted) -> dict:
    """The counters of several layers (or stacks) as one dict: summed, but
    for those of ``_WORST_LAYER``, which keep the worst."""
    names = dict.fromkeys(k for layer in counted for k in layer)
    return {k: _WORST_LAYER.get(k, jnp.sum)(
        jnp.stack([layer[k] for layer in counted if k in layer]))
        for k in names}


def _block_body(mdl, x, key_mask, ind: int, deterministic: bool):
    """One attn+ff residual pair — module-first so ``nn.remat`` can lift it
    (flax replays dropout rngs inside the recompute automatically, replacing
    the reference's manual RNG save/restore, reversible.py:20-50)."""
    t = mdl.mask_keys[ind]
    y = mdl.attn_layers[ind](x, key_mask=key_mask, rotary=mdl.rotary,
                             np_mask=mdl.np_masks[t],
                             mask_spec=mdl.mask_specs[t],
                             deterministic=deterministic)
    # a layer that counts (KimiDeltaAttention, MoEFeedForward) returns
    # (output, counters)
    y, counters = y if isinstance(y, tuple) else (y, {})
    x = x + y
    y = mdl.ff_layers[ind](x, deterministic=deterministic)
    if isinstance(y, tuple):
        y, counters = y[0], {**counters, **y[1]}
    return x + y, counters


def _text_len(c: TransformerConfig) -> int:
    return c.seq_len + 1 - c.image_fmap_size ** 2 if c.causal else 0


def layer_masks(c: TransformerConfig):
    """Per layer the key of its static mask, and per key the mask (None for
    'full': plain causal, handled in attend) and its structured spec.

    Masks are kept as NUMPY (the pallas path needs host-side masks for
    block-list construction; the dense path converts per-trace, folded by
    XLA). Deterministic mask types share one entry per type; 'sparse' gets
    a per-LAYER entry with seed = sparse_mask_seed + layer_index, so each
    sparse layer draws its own random-block pattern (DeepSpeed
    VariableSparsityConfig parity — one shared pattern would silently
    narrow the reference semantics)."""
    text_len, fmap = _text_len(c), c.image_fmap_size
    type_per_layer = list(islice(cycle(tuple(c.attn_types) or ("full",)),
                                 c.depth))
    mask_keys = [f"sparse_{ind}" if t == "sparse" else t
                 for ind, t in enumerate(type_per_layer)]
    masks: Dict[str, Optional[np.ndarray]] = {}
    specs: Dict[str, Optional[tuple]] = {}
    for ind, (mk, t) in enumerate(zip(mask_keys, type_per_layer)):
        if mk in masks:
            continue
        if t == "full" or not c.causal:
            masks[mk], specs[mk] = None, None
            continue
        masks[mk] = build_mask(
            t, text_len, fmap, kernel_size=c.sparse_attn_kernel,
            block=c.sparse_block_size,
            num_random_blocks=c.sparse_num_random_blocks,
            seed=c.sparse_mask_seed + ind)
        # structured-mask specs: the pallas kernels compute axial/conv
        # element visibility from iotas instead of loading a mask table
        # (ops/flash_attention.py elem_fn_from_spec)
        if t in ("axial_row", "axial_col"):
            specs[mk] = ("axial", text_len, fmap,
                         0 if t == "axial_row" else 1)
        elif t == "conv_like":
            specs[mk] = ("conv", text_len, fmap, c.sparse_attn_kernel, 1)
        elif t == "sparse":
            # block-aligned random-block pattern: kernel tiles coincide
            # with the pattern's block grid, no element mask needed
            specs[mk] = ("block", c.sparse_block_size)
        else:
            specs[mk] = None
    return mask_keys, masks, specs


def stack_layers(c: TransformerConfig) -> dict:
    """What the stack is built from, from the configuration alone: every
    layer's attention kind, the tier chosen for the softmax layers, and per
    kind its heads (``kv_heads`` for grouped keys and values; ``chunk`` and
    ``chunks`` a sequence for the chunked recurrence; ``qk_dim`` and
    ``v_dim`` for latent attention's two head widths). Under ``fused``, where
    that is the tier: how many score blocks of the square the kernel forms
    (``ops.fused_attention.block_plan`` of each distinct table of the stack,
    summed) and the products of its backward. Under ``flash``, where that is
    the tier: per softmax layer the score blocks its kernels walk
    (``ops.flash_attention.flash_block_counts``: ``visited``, of them
    ``full`` that no mask cuts, of ``total`` in the square)."""
    blk = c.block
    kinds = list(islice(cycle(blk.attention_kinds), c.depth))
    out = {"kinds": kinds, "tier": (
        attention_tier(c.use_pallas, c.seq_len, c.heads, c.dim_head)
        if {"mha", "gqa_gated", "mla"} & set(kinds) else "dense")}
    if out["tier"] == "fused" and "mha" not in kinds:
        out["tier"] = "dense"    # the fused kernel takes mha's merged qkv

    for kind in dict.fromkeys(kinds):
        if kind == "kda":
            out[kind] = {"heads": blk.linear_num_heads,
                         "head_dim": blk.linear_head_dim, "chunk": CHUNK,
                         "chunks": chunks_of(c.seq_len)}
        elif kind == "gqa_gated":
            out[kind] = {"heads": c.heads, "head_dim": c.dim_head,
                         "kv_heads": blk.num_key_value_heads or c.heads}
        elif kind == "mla":
            out[kind] = {"heads": c.heads_held or c.heads,
                         "qk_dim": (blk.qk_nope_head_dim
                                    + blk.qk_rope_head_dim),
                         "v_dim": blk.v_head_dim}
        else:
            out[kind] = {"heads": c.heads_held or c.heads,
                         "head_dim": c.dim_head}
    if out["tier"] == "fused":
        from ..ops.fused_attention import block_plan, validity_table
        _, masks, specs = layer_masks(c)
        plans = [block_plan(validity_table(c.seq_len, masks[k], specs[k]))
                 for k in masks]
        out["fused"] = {"score_blocks": [sum(p.computed for p in plans),
                                         sum(p.of for p in plans)],
                        "products_bwd": 5}
    if out["tier"] == "flash":
        from ..ops.flash_attention import flash_block_counts
        keys, masks, specs = layer_masks(c)
        # only mha hands the kernels its static mask; the other softmax
        # kinds are plain causal. One count per distinct table
        tables = {i: keys[i] if kind == "mha" else None
                  for i, kind in enumerate(kinds)
                  if kind in ("mha", "gqa_gated", "mla")}
        counts = {key: flash_block_counts(
            c.seq_len, causal=c.causal, mask=masks.get(key),
            mask_spec=specs.get(key)) for key in set(tables.values())}
        out["flash"] = [{"layer": i, **counts[key]}
                        for i, key in tables.items()]
    return out


def layerscale_init_eps(layer_index_1based: int) -> float:
    """Per-layer LayerScale init (reference transformer.py:74-83: 0.1 up to
    depth 18, 1e-5 to 24, 1e-6 beyond — keyed on the 1-based layer index)."""
    if layer_index_1based <= 18:
        return 0.1
    if layer_index_1based <= 24:
        return 1e-5
    return 1e-6


class DivideMax(nn.Module):
    """Divide by detached max — stable-output trick (reference :29-36)."""
    axis: int = -1

    def __call__(self, x):
        maxes = jax.lax.stop_gradient(jnp.max(x, axis=self.axis, keepdims=True))
        return x / maxes


class GEGLUFeedForward(nn.Module):
    """Linear(dim→dim·mult·2) → GEGLU → Dropout → Linear(dim·mult→dim)
    (reference :106-122)."""
    dim: int
    mult: int = 4
    dropout: float = 0.0

    def setup(self):
        # QDense ≡ nn.Dense until handed an int8 kernel (decode weight
        # quantization, ops/quantize_weights.py)
        self.w1 = QDense(self.dim * self.mult * 2, name="w1")
        self.w2 = QDense(self.dim, name="w2")
        self.drop = nn.Dropout(self.dropout)

    def __call__(self, x, deterministic: bool = True):
        x, gates = jnp.split(self.w1(x), 2, axis=-1)
        x = x * jax.nn.gelu(gates)
        x = self.drop(x, deterministic=deterministic)
        return self.w2(x)


class Attention(nn.Module):
    """Multi-head attention over the shared dense core (reference attention.py:39-99).
    Rotary is applied to q, k AND v — preserved reference behavior (:66-67).

    ``tier`` is what ``ops.attention.attention_tier`` chose for the model:
    "dense", "fused" (ops/fused_attention.py) or "flash"
    (ops/flash_attention.py, which also block-skips any static sparse mask —
    the TPU-native successor of the DeepSpeed SparseSelfAttention path,
    attention.py:339-398). Flash is inherently max-subtracting, so the
    ``stable`` softmax variant is subsumed. Decode keeps the dense cached core
    (single-token steps are bandwidth-, not matmul-bound)."""
    dim: int
    heads: int
    dim_head: int
    dropout: float = 0.0
    causal: bool = True
    stable: bool = False
    tier: str = "dense"
    softmax_f32: bool = True
    # sequence parallelism: a Mesh with an 'sp' axis routes the full-causal
    # training forward through ring attention (parallel/ring_attention.py) —
    # activations shard along the sequence, k/v rotate over ICI. Static
    # module metadata (hashable), not a traced value.
    sp_mesh: Any = None

    def setup(self):
        inner = self.heads * self.dim_head
        self.to_qkv = QDense(inner * 3, use_bias=False, name="to_qkv")
        self.to_out = QDense(self.dim, name="to_out")
        self.drop = nn.Dropout(self.dropout)

    def _split(self, qkv, n):
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = (-1, n, self.heads, self.dim_head)
        return [t.reshape(shape).transpose(0, 2, 1, 3) for t in (q, k, v)]

    def __call__(self, x, *, key_mask=None, rotary=None, np_mask=None,
                 mask_spec=None, deterministic: bool = True):
        """``np_mask`` is the ONE mask parameter (host-side numpy, compile-time
        constant): the pallas path lowers it to block lists, the dense path
        converts it to a jnp constant — a single source of truth so the two
        backends can never disagree."""
        b, n, _ = x.shape
        # init runs the dense path: the params are identical and eager pallas
        # execution during un-jitted init is needlessly slow
        ready = not self.is_initializing()
        ring = self.sp_mesh is not None and ready
        kernel = (self.tier if ready and not ring and key_mask is None
                  else "dense")
        if kernel == "fused":
            from ..ops.fused_attention import fused_fits, fused_qkv_attention
            # not a second policy: the tier was chosen from cfg.seq_len, and
            # the RUNTIME n must fit too or the Mosaic compile fails
            if (self.causal and not self.stable
                    and fused_fits(n, self.dim_head, self.heads)):
                # the operand is the qkv projection's own (b, n, 3·h·d)
                # layout; head split/merge live inside the kernel. Rotary
                # rides the same layout: applied on the (b, n, 3h, d) VIEW —
                # a reshape, not the head-split transpose the dense path pays
                with jax.named_scope("attn/qkv"):
                    qkv = self.to_qkv(x)
                    if rotary is not None:
                        rot = rotary[:n][:, None]          # (n, 1, rot_dim)
                        qkv = apply_rotary(
                            rot,
                            qkv.reshape(b, n, 3 * self.heads, self.dim_head)
                        ).reshape(b, n, -1)
                with jax.named_scope("attn_core"):
                    out = fused_qkv_attention(qkv, np_mask, self.heads, None,
                                              None, mask_spec).astype(x.dtype)
                with jax.named_scope("attn/out"):
                    out = self.to_out(out)
                return self.drop(out, deterministic=deterministic)
        with jax.named_scope("attn/qkv"):
            q, k, v = self._split(self.to_qkv(x), n)
            if rotary is not None:
                rot = rotary[:n][None, None]
                q, k, v = (apply_rotary(rot, t) for t in (q, k, v))
        # whichever tier runs, its call is the layer's ``attn_core`` on the
        # device trace (obs/device.py ``scope_layer``)
        if ring:
            # sequence-parallel ring attention: full causal plus structured
            # (axial/conv) sparse masks, whose element test is a pure function
            # of global (qpos, kpos) the ring evaluates per chunk pair —
            # tabled masks ('sparse' random blocks) have no such function and
            # stay single-chip
            assert key_mask is None and self.causal, (
                "sequence parallelism requires causal attention, no key_mask")
            assert np_mask is None or (
                mask_spec is not None and mask_spec[0] in ("axial", "conv")), (
                "sequence parallelism supports full/axial/conv attention only")
            from ..parallel.ring_attention import ring_attention
            # zigzag: balanced causal layout + quadrant skipping (exact);
            # kernel='auto' → Pallas chunk kernels on TPU for chunks ≥ 512
            with jax.named_scope("attn_core"):
                out = ring_attention(q, k, v, mesh=self.sp_mesh, causal=True,
                                     zigzag=True,
                                     mask_spec=mask_spec if np_mask is not None
                                     else None)
        elif kernel == "flash":
            from ..ops.flash_attention import flash_attention
            with jax.named_scope("attn_core"):
                out = flash_attention(q, k, v, mask=np_mask,
                                      mask_spec=mask_spec, causal=self.causal)
        else:
            static = None if np_mask is None else jnp.asarray(np_mask)
            with jax.named_scope("attn_core"):
                out = attend(q, k, v, causal=self.causal, key_mask=key_mask,
                             static_mask=static, stable=self.stable,
                             softmax_f32=self.softmax_f32)
        with jax.named_scope("attn/out"):
            out = self.to_out(out.transpose(0, 2, 1, 3).reshape(b, n, -1))
        return self.drop(out, deterministic=deterministic)

    def prefill(self, x, cache: KVCache, *, rotary=None, static_mask=None):
        """Full-prefix forward that also fills the KV cache from position 0."""
        b, n, _ = x.shape
        q, k, v = self._split(self.to_qkv(x), n)
        if rotary is not None:
            rot = rotary[:n][None, None]
            q, k, v = (apply_rotary(rot, t) for t in (q, k, v))
        cache = cache.append(k, v, 0)
        out = attend(q, k, v, causal=self.causal, static_mask=static_mask,
                     stable=self.stable)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, -1)
        return self.to_out(out), cache

    def decode(self, x_t, cache: KVCache, offset, *, rotary=None, static_mask=None,
               use_kernel=None):
        """One-token step at position ``offset`` (traced scalar).
        ``use_kernel`` pins the Pallas decode-kernel selection (None = auto)
        — see cached_attend; plumbed so parity-critical callers can force
        the same attend implementation on every path."""
        b = x_t.shape[0]
        q, k, v = self._split(self.to_qkv(x_t), 1)
        if rotary is not None:
            rot = jax.lax.dynamic_slice_in_dim(rotary, offset, 1, axis=0)[None, None]
            q, k, v = (apply_rotary(rot, t) for t in (q, k, v))
        cache = cache.append(k, v, offset)
        out = cached_attend(q, cache, offset + 1, static_mask=static_mask,
                            stable=self.stable, qpos=offset,
                            use_kernel=use_kernel)
        out = out.transpose(0, 2, 1, 3).reshape(b, 1, -1)
        return self.to_out(out), cache

    def decode_window(self, x_w, cache: KVCache, offsets, *, rotary=None,
                      use_kernel=None):
        """Speculative verify step: ``w`` tokens per row at PER-ROW absolute
        positions ``offsets[b] .. offsets[b]+w-1`` (offsets: (b,) traced) —
        batch rows diverge because they accept different draft lengths.
        Causality within the window + against the per-row cache prefix is
        enforced by cached_attend_window; rotary rows are gathered per
        (row, slot). Full attention only (no static masks — see
        cached_attend_window)."""
        b, w, _ = x_w.shape
        q, k, v = self._split(self.to_qkv(x_w), w)
        if rotary is not None:
            # clamp: a window starting at the final position overshoots the
            # table by up to w-1 slots (jnp.take's fill mode would NaN them);
            # overshoot slots only ever hold rejected/never-committed drafts
            pos = jnp.clip(offsets[:, None] + jnp.arange(w)[None, :],
                           0, rotary.shape[0] - 1)               # (b, w)
            rot = jnp.take(rotary, pos, axis=0)[:, None]         # (b,1,w,rot)
            q, k, v = (apply_rotary(rot, t) for t in (q, k, v))
        cache = cache.append_rows(k, v, offsets)
        out = cached_attend_window(q, cache, offsets, stable=self.stable,
                                   use_kernel=use_kernel)
        out = out.transpose(0, 2, 1, 3).reshape(b, w, -1)
        return self.to_out(out), cache


class ShiftState(NamedTuple):
    """Ring buffers for cached token-shift decode: the (top, left) quarter-chunks
    of the last ``image_size`` *pre-shift* inputs (reference deque,
    transformer.py:138-153), plus the previous token's first-half channels for
    text-position decode (text shift = ½ channels from position t−1)."""
    top: jnp.ndarray    # (b, image_size, d4)
    left: jnp.ndarray   # (b, image_size, d4)
    prev: jnp.ndarray   # (b, d2) pre-shift first half of the latest token

    @classmethod
    def init(cls, batch: int, image_size: int, d4: int, dtype=jnp.float32):
        z = jnp.zeros((batch, image_size, d4), dtype)
        return cls(z, z, jnp.zeros((batch, 2 * d4), dtype))


def shift_tokens_full(x, text_len: int, image_size: int):
    """Token-shift over a full sequence (reference PreShiftToken :155-186):
    text: first ½ of channels from position t−1; image: first ¼ from the top
    grid-neighbor, next ¼ from the left grid-neighbor."""
    b, n, d = x.shape
    if n < text_len:  # no image tokens yet — shift text only (ref :160-161)
        half, rest = jnp.split(x, 2, axis=-1)
        half = jnp.pad(half, ((0, 0), (1, 0), (0, 0)))[:, :n]
        return jnp.concatenate((half, rest), axis=-1)

    img_len = n - text_len
    x_text, x_img = x[:, :text_len], x[:, text_len:]

    t_shift, t_pass = jnp.split(x_text, 2, axis=-1)
    t_shift = jnp.pad(t_shift, ((0, 0), (1, 0), (0, 0)))[:, :text_len]
    x_text = jnp.concatenate((t_shift, t_pass), axis=-1)

    pad_to = image_size * image_size - img_len
    xi = jnp.pad(x_img, ((0, 0), (0, pad_to), (0, 0)))
    xi = xi.reshape(b, image_size, image_size, d)
    d4 = d // 4
    top, left, rest = xi[..., :d4], xi[..., d4:2 * d4], xi[..., 2 * d4:]
    top = jnp.pad(top, ((0, 0), (1, 0), (0, 0), (0, 0)))[:, :image_size]
    left = jnp.pad(left, ((0, 0), (0, 0), (1, 0), (0, 0)))[:, :, :image_size]
    xi = jnp.concatenate((top, left, rest), axis=-1)
    x_img = xi.reshape(b, image_size * image_size, d)[:, :img_len]
    return jnp.concatenate((x_text, x_img), axis=1)


def shift_prefill_state(x, text_len: int, image_size: int,
                        state: ShiftState) -> ShiftState:
    """Fill the ring buffers after a full-prefix forward: slots for image
    positions get their pre-shift chunks; text slots stay zero (matching the
    reference's dummy-padded deque init, :192-197, but pre-shift — see module
    docstring)."""
    b, n, d = x.shape
    d4 = d // 4
    # writes cast to the buffer dtype (the buffers may be narrower than the
    # activations, e.g. bf16 ring buffers alongside an int8 KV cache)
    prev = x[:, -1, :2 * d4].astype(state.prev.dtype)
    img_len = max(n - text_len, 0)
    if img_len == 0:
        return ShiftState(state.top, state.left, prev)
    take = min(img_len, image_size)
    chunk = x[:, n - take:n]
    # positions n-take..n-1 → ring slots (pos - text_len) % image_size
    pos = jnp.arange(n - take, n) - text_len
    slots = pos % image_size
    top = state.top.at[:, slots].set(chunk[..., :d4].astype(state.top.dtype))
    left = state.left.at[:, slots].set(
        chunk[..., d4:2 * d4].astype(state.left.dtype))
    return ShiftState(top, left, prev)


def shift_decode_step(x_t, state: ShiftState, offset, text_len: int,
                      image_size: int):
    """Cached one-token shift (reference :138-153) at traced position
    ``offset``. Text positions (offset < text_len) take the previous token's
    first-half channels; image positions take the (top, left) grid-neighbor
    quarter-chunks from the ring buffers. Returns (shifted x_t, new state)."""
    b, _, d = x_t.shape
    d4 = d // 4
    d2 = 2 * d4
    cur = x_t[:, 0]
    cur_top, cur_left = cur[..., :d4], cur[..., d4:d2]
    img_pos = offset - text_len
    is_text = offset < text_len
    ptr = img_pos % image_size  # nonneg also while img_pos < 0 (text phase)
    # top neighbor = value written image_size steps ago = current ring slot
    top_n = jax.lax.dynamic_index_in_dim(state.top, ptr, axis=1, keepdims=False)
    prev_ptr = (ptr - 1) % image_size
    left_n = jax.lax.dynamic_index_in_dim(state.left, prev_ptr, axis=1, keepdims=False)
    # zero top for the first image row; zero left at column 0 (ref :149-150 +
    # the full path's zero padding)
    top_n = jnp.where(img_pos < image_size, 0.0, top_n)
    left_n = jnp.where(img_pos % image_size == 0, 0.0, left_n)
    img_shift = jnp.concatenate((top_n, left_n, cur[..., d2:]), axis=-1)
    txt_shift = jnp.concatenate((state.prev, cur[..., d2:]), axis=-1)
    shifted = jnp.where(is_text, txt_shift, img_shift)[:, None]
    new_top = jax.lax.dynamic_update_slice_in_dim(
        state.top, cur_top[:, None].astype(state.top.dtype), ptr, axis=1)
    new_left = jax.lax.dynamic_update_slice_in_dim(
        state.left, cur_left[:, None].astype(state.left.dtype), ptr, axis=1)
    # text-phase steps must not write into the image ring buffers
    state = ShiftState(jnp.where(is_text, state.top, new_top),
                       jnp.where(is_text, state.left, new_left),
                       cur[..., :d2].astype(state.prev.dtype))
    return shifted, state


class TransformerLayer(nn.Module):
    """PreNorm(+sandwich) → optional token-shift → fn, scaled by LayerScale,
    residual added by the caller. One instance each for attn and ff roles."""
    dim: int
    index: int                     # 1-based, for LayerScale init
    fn: nn.Module
    sandwich: bool = False
    shift: bool = False
    text_len: int = 0
    image_size: int = 0
    norm_kind: str = "layernorm"   # layernorm | rmsnorm (config.BlockConfig)
    norm_eps: float = 1e-6
    layerscale: bool = True

    def setup(self):
        self.norm = (RMSNorm(self.norm_eps, name="norm")
                     if self.norm_kind == "rmsnorm"
                     else nn.LayerNorm(name="norm"))
        self.norm_out = nn.LayerNorm(name="norm_out") if self.sandwich else None
        if not self.layerscale:
            self.scale = None
            return
        eps = layerscale_init_eps(self.index)
        # explicit dtype: jnp.full of a Python float is WEAK-typed, and a
        # weak-typed param flips to strong after one pass through a jitted
        # step (outputs are strong), changing the input signature — every
        # train_step call then recompiles the whole program (graftlint
        # weak-type-promotion; graftir caught this as a per-step retrace).
        # The f32 pin is deliberate: params are created full-width by repo
        # policy (precision modes cast derived trees, never initializers)
        self.scale = self.param(  # graftlint: disable=hardcoded-dtype
            "scale", lambda k: jnp.full((1, 1, self.dim), eps, jnp.float32))

    def _post(self, y):
        if self.norm_out is not None:
            y = self.norm_out(y)
        return y if self.scale is None else y * self.scale

    def __call__(self, x, **kw):
        y = self.norm(x)
        if self.shift:
            y = shift_tokens_full(y, self.text_len, self.image_size)
        # the dense MLPs run under the scope ``ff``; every other layer kind
        # names its own parts (the shared expert, an MLP of the same class
        # inside ``moe/shared``, stays the routed layer's)
        dense_mlp = isinstance(self.fn, (GEGLUFeedForward, SwiGLUFeedForward))
        with (jax.named_scope("ff") if dense_mlp
              else contextlib.nullcontext()):
            y = self.fn(y, **kw)
        if isinstance(y, tuple):       # (output, counters): see _block_body
            return self._post(y[0]), y[1]
        return self._post(y)

    def prefill(self, x, kv: Optional[KVCache], shift_state: Optional[ShiftState],
                **kw):
        y = self.norm(x)
        if self.shift:
            pre = y
            y = shift_tokens_full(y, self.text_len, self.image_size)
            shift_state = shift_prefill_state(pre, self.text_len, self.image_size,
                                              shift_state)
        if isinstance(self.fn, Attention):
            y, kv = self.fn.prefill(y, kv, **kw)
        else:
            y = self.fn(y)
        return self._post(y), kv, shift_state

    def decode(self, x_t, kv: Optional[KVCache], shift_state: Optional[ShiftState],
               offset, **kw):
        y = self.norm(x_t)
        if self.shift:
            y, shift_state = shift_decode_step(y, shift_state, offset,
                                               self.text_len, self.image_size)
        if isinstance(self.fn, Attention):
            y, kv = self.fn.decode(y, kv, offset, **kw)
        else:
            y = self.fn(y)
        return self._post(y), kv, shift_state

    def decode_window(self, x_w, kv: Optional[KVCache], offsets, **kw):
        """w-token speculative step (no token-shift: the ring buffers are
        inherently one-token-sequential — gated at the Transformer level)."""
        y = self.norm(x_w)
        if isinstance(self.fn, Attention):
            y, kv = self.fn.decode_window(y, kv, offsets, **kw)
        else:
            y = self.fn(y)
        return self._post(y), kv


class Transformer(nn.Module):
    """depth × (attn, ff) with per-layer attention kind from the cyclic
    ``attn_types`` tuple, layer sharing, rotary table, static sparse masks.
    (reference Transformer ctor :204-328)"""
    cfg: TransformerConfig
    sp_mesh: Any = None    # sequence-parallel mesh (see Attention.sp_mesh)

    def setup(self):
        c = self.cfg
        fmap = c.image_fmap_size
        self.text_len = _text_len(c)
        blk = c.block
        # chosen once, from the configured length, for the softmax layers:
        # the model keeps its tier at every runtime length. Linear attention
        # has no scores and is built without asking.
        layers = stack_layers(c)
        self.attn_kinds, tier = layers["kinds"], layers["tier"]

        attn_types = tuple(c.attn_types) or ("full",)
        type_per_layer = list(islice(cycle(attn_types), c.depth))
        attn_ids = list(islice(cycle(c.shared_attn_ids or range(c.depth)), c.depth))
        ff_ids = list(islice(cycle(c.shared_ff_ids or range(c.depth)), c.depth))
        mask_keys, masks, specs = layer_masks(c)
        self.np_masks = masks
        self.mask_specs = specs
        self.mask_keys = mask_keys

        self.rotary = None
        if blk.positions == "seq_yarn":
            angles, cos_sin_scale = seq_yarn_table(
                c.seq_len + 1, blk.qk_rope_head_dim, blk.rope_theta,
                {"factor": blk.yarn_factor,
                 "original_max_position": blk.yarn_original_max_position,
                 "beta_fast": blk.yarn_beta_fast,
                 "beta_slow": blk.yarn_beta_slow, "mscale": blk.yarn_mscale,
                 "mscale_all_dim": blk.yarn_mscale_all_dim})
            if cos_sin_scale != 1.0:
                raise NotImplementedError(
                    f"seq_yarn with yarn_mscale {blk.yarn_mscale} != "
                    f"yarn_mscale_all_dim {blk.yarn_mscale_all_dim}: cos and "
                    f"sin would be scaled by {cos_sin_scale}, which "
                    f"apply_rotary does not do")
            self.rotary = jnp.asarray(angles)
        elif blk.positions == "dalle_axial" and c.rotary_emb and c.causal:
            self.rotary = jnp.asarray(
                dalle_pos_emb(self.text_len, fmap, c.dim_head))

        shared_attn: Dict[Any, Tuple[Attention, str]] = {}
        shared_ff: Dict[Any, GEGLUFeedForward] = {}
        attn_layers, ff_layers = [], []
        layer_types = []
        for ind in range(c.depth):
            t = type_per_layer[ind]
            aid, fid = attn_ids[ind], ff_ids[ind]
            if aid in shared_attn:
                attn, prev_t = shared_attn[aid]
                if prev_t != t:
                    raise ValueError(
                        f"attn_types do not match shared_attn_ids (ind={ind}, "
                        f'attn_type="{t}", reused="{prev_t}")')
            else:
                attn = self._make_attention(f"attn_{aid}", tier,
                                            self.attn_kinds[ind])
                shared_attn[aid] = (attn, t)
            if fid in shared_ff:
                ff = shared_ff[fid]
            else:
                ff = self._make_feed_forward(f"ff_{fid}", ind)
                shared_ff[fid] = ff
            layer_kw = dict(sandwich=c.sandwich_norm, shift=c.shift_tokens,
                            text_len=self.text_len, image_size=fmap,
                            norm_kind=blk.norm, norm_eps=blk.rms_norm_eps,
                            layerscale=blk.layerscale)
            attn_layers.append(TransformerLayer(
                c.dim, ind + 1, attn, name=f"layer_attn_{ind}", **layer_kw))
            ff_layers.append(TransformerLayer(
                c.dim, ind + 1, ff, name=f"layer_ff_{ind}", **layer_kw))
            layer_types.append(t)
        self.layer_types = layer_types
        self.attn_layers = attn_layers
        self.ff_layers = ff_layers

    # -- the block's kinds (config.BlockConfig) -----------------------------
    def _make_attention(self, name: str, tier: str, kind: str):
        c, blk = self.cfg, self.cfg.block
        if kind in ("gqa_gated", "kda"):
            # a linear layer beside latent ones takes no notice of their
            # rotary table
            positions = {"none", "seq_yarn"} if (
                kind == "kda" and "mla" in blk.attention_kinds) else {"none"}
            if blk.positions not in positions or not c.causal or self.sp_mesh:
                raise ValueError(f"{kind} is causal, takes no positional "
                                 f"term (positions: none) and has no "
                                 f"sequence-parallel path")
            if kind == "kda":
                return KimiDeltaAttention(
                    c.dim, blk.linear_num_heads, blk.linear_head_dim,
                    conv_size=blk.short_conv_kernel_size,
                    gate_rank=blk.linear_gate_rank, eps=blk.rms_norm_eps,
                    lower_bound=blk.kda_lower_bound,
                    beta_max=blk.kda_beta_max, name=name)
            return GatedGQAttention(
                c.dim, c.heads, blk.num_key_value_heads or c.heads,
                c.dim_head, tier=tier, softmax_f32=c.attn_softmax_f32,
                name=name)
        if kind == "mha":
            if blk.positions != "dalle_axial":
                raise ValueError("mha takes the dalle_axial rotary table")
            return Attention(c.dim, c.heads, c.dim_head, c.attn_dropout,
                             causal=c.causal, stable=c.stable,
                             tier=tier,
                             softmax_f32=c.attn_softmax_f32,
                             sp_mesh=self.sp_mesh, name=name)
        if blk.positions != "seq_yarn" or not c.causal or self.sp_mesh:
            raise ValueError("mla is causal, takes seq_yarn positions and "
                             "has no sequence-parallel path")
        # YaRN stretches the softmax by mscale(factor, mscale_all_dim)^2
        m = yarn_mscale(blk.yarn_factor, blk.yarn_mscale_all_dim)
        return MLAttention(
            c.dim, c.heads_held or c.heads, c.heads, blk.q_lora_rank,
            blk.kv_lora_rank, blk.qk_nope_head_dim, blk.qk_rope_head_dim,
            blk.v_head_dim,
            softmax_scale=(blk.qk_nope_head_dim
                           + blk.qk_rope_head_dim) ** -0.5 * m * m,
            eps=blk.rms_norm_eps,
            softmax_f32=c.attn_softmax_f32, qk_norm=blk.qk_norm,
            gate=blk.attention_gate, tier=tier, name=name)

    def _make_feed_forward(self, name: str, ind: int):
        c, blk = self.cfg, self.cfg.block
        if blk.feed_forward == "geglu":
            return GEGLUFeedForward(c.dim, c.ff_mult, c.ff_dropout, name=name)
        if blk.feed_forward == "swiglu" or ind < blk.first_dense_layers:
            return SwiGLUFeedForward(c.dim, blk.intermediate_size, name=name)
        return MoEFeedForward(
            c.dim, blk.moe_intermediate_size,
            experts_held=c.experts_held or blk.n_routed_experts,
            n_routed_experts=blk.n_routed_experts, n_group=blk.n_group,
            topk_group=blk.topk_group, top_k=blk.num_experts_per_tok,
            routed_scale=blk.routed_scaling_factor,
            n_shared=blk.n_shared_experts, scoring=blk.scoring_func,
            norm_topk=blk.norm_topk_prob, topk_method=blk.topk_method,
            name=name)

    def _refuse_cached(self, what: str):
        """The cached paths are written for multi-head keys and values in a
        ``KVCache`` / ``PagedKVCache``; the block kinds without that layout
        are refused by name, not run wrongly."""
        blk = self.cfg.block
        if set(blk.attention_kinds) != {"mha"} or blk.feed_forward == "moe":
            raise NotImplementedError(
                f"{what}: the {blk.name} block has no cached decode path "
                f"(latent keys and values, grouped key/value heads, a "
                f"recurrent state and its convolution tail have no KVCache "
                f"layout, and a routed layer returns counters); it trains "
                f"through Transformer.__call__ only")

    def _dense_mask(self, t):
        m = self.np_masks[t]
        return None if m is None else jnp.asarray(m)


    # -- training / full forward ------------------------------------------
    def __call__(self, x, key_mask=None, deterministic: bool = True,
                 return_aux: bool = False):
        """Sequential execution by default; ``cfg.reversible`` switches to the
        O(1)-activation custom_vjp path (models/reversible.py) — the TPU
        equivalent of the reference's ReversibleSequence. `jax.checkpoint` at
        the train-step level is the complementary remat lever.

        ``return_aux``: also return the layers' counters, reduced over depth
        (``moe_rows_held`` and ``moe_rows_dropped`` summed,
        ``moe_load_max_over_mean`` and ``kda_logdecay_min`` of the worst
        layer); {} for a stack whose layers count nothing."""
        c = self.cfg
        if c.reversible:
            out = self._call_reversible(x, key_mask, deterministic)
            return (out, {}) if return_aux else out
        use_remat = c.use_remat and not self.is_initializing()
        counted = []
        for ind in range(c.depth):
            if use_remat:
                # real jax.checkpoint per block pair: activations inside the
                # block are recomputed in backward — the memory lever that
                # lets batch/depth scale past HBM (complements `reversible`,
                # which is O(1) in depth rather than O(depth) checkpoints).
                # A linear-attention block keeps what its core names
                # (``KDA_SAVED``): the recompute then runs none of the core
                saved = ({"policy": KDA_SAVED}
                         if self.attn_kinds[ind] == "kda" else {})
                blk = nn.remat(_block_body, prevent_cse=False,
                               static_argnums=(3, 4), **saved)
                x, counters = blk(self, x, key_mask, ind, deterministic)
            else:
                x, counters = _block_body(self, x, key_mask, ind,
                                          deterministic)
            if counters:
                counted.append(counters)
        if not return_aux:
            return x
        return x, reduce_counters(counted)

    def _call_reversible(self, x, key_mask, deterministic: bool):
        """Unbind each layer into (pure fn, params) pairs and run the
        reversible coupling. Dropout works through explicit key replay: every
        block fn carries its dropout key in the params pytree, so the
        custom_vjp backward's recompute uses bit-identical masks — the
        TPU-native version of the reference's RNG save/restore dance
        (reversible.py:20-50). Each block gets the base key with its depth
        index folded in: layers reused via shared_attn_ids/shared_ff_ids live
        at the same module path, so without the fold every reuse would draw
        the identical dropout mask (the sequential path decorrelates repeats
        through flax's rng call counter)."""
        from .reversible import run_reversible
        c = self.cfg
        use_dropout = (not deterministic
                       and (c.attn_dropout > 0 or c.ff_dropout > 0))
        if self.is_initializing():
            # bound calls so flax creates the params; same coupled computation
            x1 = x2 = x
            for ind in range(c.depth):
                x1 = x1 + self._apply_attn_layer(x2, ind, key_mask)
                x2 = x2 + self._apply_ff_layer(x1, ind)
            return (x1 + x2) / 2.0
        drop_key = self.make_rng("dropout") if use_dropout else None
        # Unbind the WHOLE stack once: shared layers live in their first
        # adopter's flax scope, so per-layer unbinding would lose their params.
        # Each block fn takes the full variable tree; unused-leaf cotangents
        # are symbolic zeros that XLA folds away.
        tm, variables = self.unbind()
        fns, params = [], []
        for ind in range(c.depth):
            blk_key = (None if drop_key is None
                       else jax.random.fold_in(drop_key, ind))

            def f(p, h, _ind=ind):
                var, key = p
                rngs = None if key is None else {"dropout": key}
                return tm.apply(var, h, _ind, key_mask, key is None,
                                method=Transformer._apply_attn_layer,
                                rngs=rngs)

            def g(p, h, _ind=ind):
                var, key = p
                rngs = None if key is None else {"dropout": key}
                return tm.apply(var, h, _ind, key is None,
                                method=Transformer._apply_ff_layer, rngs=rngs)

            fns.append((f, g))
            params.append(((variables, blk_key), (variables, blk_key)))
        return run_reversible(fns, params, x)

    def _apply_attn_layer(self, h, ind: int, key_mask=None,
                          deterministic: bool = True):
        t = self.mask_keys[ind]
        return self.attn_layers[ind](h, key_mask=key_mask, rotary=self.rotary,
                                     np_mask=self.np_masks[t],
                                     mask_spec=self.mask_specs[t],
                                     deterministic=deterministic)

    def _apply_ff_layer(self, h, ind: int, deterministic: bool = True):
        return self.ff_layers[ind](h, deterministic=deterministic)

    # -- cached decode -----------------------------------------------------
    def init_cache(self, batch: int, max_seq: Optional[int] = None,
                   dtype=jnp.float32) -> Dict[str, Any]:
        c = self.cfg
        self._refuse_cached("init_cache")
        max_seq = max_seq or c.seq_len + 1
        cache: Dict[str, Any] = {}
        d4 = c.dim // 4
        # int8 selects *quantized KV storage* (KVCache handles scales); the
        # token-shift ring buffers hold raw hidden slices and stay bf16
        shift_dtype = jnp.bfloat16 if dtype == jnp.int8 else dtype
        for ind in range(c.depth):
            cache[f"kv_{ind}"] = KVCache.init(batch, c.heads, max_seq,
                                              c.dim_head, dtype)
            if c.shift_tokens:
                cache[f"shift_attn_{ind}"] = ShiftState.init(
                    batch, c.image_fmap_size, d4, shift_dtype)
                cache[f"shift_ff_{ind}"] = ShiftState.init(
                    batch, c.image_fmap_size, d4, shift_dtype)
        return cache

    def init_cache_paged(self, num_blocks: int, block_tokens: int,
                         max_seq: int, dtype=jnp.float32) -> Dict[str, Any]:
        """Paged twin of ``init_cache``: per-layer block pools instead of
        per-slot slabs. The page table is NOT allocated here — the engine
        owns exactly one ``(B, max_blocks)`` table as a state leaf and
        injects it into every layer per dispatch (a per-layer copy would
        donate the same buffer depth times). Serve mode requires
        shift_tokens off (Transformer.decode_window asserts it), so no
        shift states."""
        c = self.cfg
        self._refuse_cached("init_cache_paged")
        assert not c.shift_tokens, "paged serve cache requires shift_tokens off"
        from ..ops.paged_kv import PagedKVCache
        return {f"kv_{ind}": PagedKVCache.init(num_blocks, block_tokens,
                                               c.heads, max_seq, c.dim_head,
                                               dtype)
                for ind in range(c.depth)}

    def prefill(self, x, cache: Dict[str, Any]):
        """Run the full prefix, filling every layer's caches. Returns (y, cache)."""
        c = self.cfg
        self._refuse_cached("prefill")
        cache = dict(cache)
        for ind in range(c.depth):
            attn_l, ff_l, t = self.attn_layers[ind], self.ff_layers[ind], self.mask_keys[ind]
            y, kv, ss = attn_l.prefill(x, cache[f"kv_{ind}"],
                                       cache.get(f"shift_attn_{ind}"),
                                       rotary=self.rotary,
                                       static_mask=self._dense_mask(t))
            cache[f"kv_{ind}"] = kv
            if ss is not None:
                cache[f"shift_attn_{ind}"] = ss
            x = x + y
            y, _, ss = ff_l.prefill(x, None, cache.get(f"shift_ff_{ind}"))
            if ss is not None:
                cache[f"shift_ff_{ind}"] = ss
            x = x + y
        return x, cache

    def decode_window(self, x_w, cache: Dict[str, Any], offsets, *,
                      use_kernel=None):
        """w tokens per row at per-row positions ``offsets`` (b,) — the
        speculative verify forward (models/dalle.py). Requires full
        attention and no token-shift (both hold for every generation config
        the samplers build; sparse masks would need per-row mask gathers and
        shift ring buffers are one-token-sequential by construction)."""
        c = self.cfg
        self._refuse_cached("decode_window")
        assert not c.shift_tokens, (
            "speculative decode does not support shift_tokens")
        assert all(k == "full" for k in self.mask_keys), (
            "speculative decode supports full attention only, got "
            f"{set(self.mask_keys)}")
        cache = dict(cache)
        for ind in range(c.depth):
            attn_l, ff_l = self.attn_layers[ind], self.ff_layers[ind]
            y, kv = attn_l.decode_window(x_w, cache[f"kv_{ind}"], offsets,
                                         rotary=self.rotary,
                                         use_kernel=use_kernel)
            cache[f"kv_{ind}"] = kv
            x_w = x_w + y
            y, _ = ff_l.decode_window(x_w, None, offsets)
            x_w = x_w + y
        return x_w, cache

    def decode_step(self, x_t, cache: Dict[str, Any], offset, *,
                    use_kernel=None):
        """One token at traced position ``offset``. Returns (y_t, cache).
        Sparse masks apply via their offset row; causality is implicit
        (reference attention.py:86 'causality is naturally enforced')."""
        c = self.cfg
        self._refuse_cached("decode_step")
        cache = dict(cache)
        for ind in range(c.depth):
            attn_l, ff_l, t = self.attn_layers[ind], self.ff_layers[ind], self.mask_keys[ind]
            y, kv, ss = attn_l.decode(x_t, cache[f"kv_{ind}"],
                                      cache.get(f"shift_attn_{ind}"), offset,
                                      rotary=self.rotary,
                                      static_mask=self._dense_mask(t),
                                      use_kernel=use_kernel)
            cache[f"kv_{ind}"] = kv
            if ss is not None:
                cache[f"shift_attn_{ind}"] = ss
            x_t = x_t + y
            y, _, ss = ff_l.decode(x_t, None, cache.get(f"shift_ff_{ind}"), offset)
            if ss is not None:
                cache[f"shift_ff_{ind}"] = ss
            x_t = x_t + y
        return x_t, cache
