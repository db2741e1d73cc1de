"""Rotary position embeddings — functional, precomputed as a static table.

Reproduces the reference's vendored rotary-embedding-torch semantics
(dalle_pytorch/rotary_embedding_torch/rotary_embedding_torch.py:61-112) and the
DALLE-specific combined text+2D-image frequency table
(dalle_pytorch/transformer.py:302-328):

  * ``lang`` freqs: 1/theta^(2i/dim); ``pixel`` freqs: linspace(1, max_freq/2, dim//2)*pi.
  * Each frequency repeated twice adjacently; rotation acts on adjacent pairs.
  * Text token positions 0..text_len over the lang bank; image tokens pinned at
    lang-position 8192. Image tokens get 2D axial pixel freqs over linspace(-1,1)
    per row/col; text tokens pinned at axial position -10.
  * The combined table has last-dim 3·2·(dim_head//3//2) and rotates only the
    leading slice of each head dim (the rest passes through).

Everything here is a compile-time constant table — XLA folds it — so there is no
runtime cost beyond the fused multiply-adds of ``apply_rotary``.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


def lang_freqs(dim: int, theta: float = 10000.0) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, dim, 2)[: dim // 2].astype(np.float32) / dim))


def pixel_freqs(dim: int, max_freq: float = 10.0) -> np.ndarray:
    return np.linspace(1.0, max_freq / 2, dim // 2).astype(np.float32) * math.pi


def freqs_table(positions: np.ndarray, freqs: np.ndarray) -> np.ndarray:
    """outer(positions, freqs) with each column doubled adjacently → (n, 2*(dim//2))."""
    table = np.einsum("i,j->ij", positions.astype(np.float32), freqs)
    return np.repeat(table, 2, axis=-1)


def rotate_half(x: jnp.ndarray) -> jnp.ndarray:
    """Pairwise (x1,x2) → (-x2,x1) on adjacent feature pairs."""
    x = x.reshape(*x.shape[:-1], -1, 2)
    x1, x2 = x[..., 0], x[..., 1]
    return jnp.stack((-x2, x1), axis=-1).reshape(*x.shape[:-2], -1)


def apply_rotary(freqs: jnp.ndarray, t: jnp.ndarray) -> jnp.ndarray:
    """Rotate the leading ``freqs.shape[-1]`` features of ``t``; pass the rest through.
    (reference apply_rotary_emb, rotary_embedding_torch.py:40-47)"""
    rot_dim = freqs.shape[-1]
    freqs = freqs.astype(t.dtype)
    t_rot, t_pass = t[..., :rot_dim], t[..., rot_dim:]
    t_rot = t_rot * jnp.cos(freqs) + rotate_half(t_rot) * jnp.sin(freqs)
    return jnp.concatenate((t_rot, t_pass), axis=-1)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor (Peng et al. 2023, eq. 22 with DeepSeek-V2's
    coefficient): 0.1 * mscale * ln(factor) + 1 beyond the original context."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def seq_yarn_table(n: int, dim: int, theta: float = 10000.0,
                   scaling: Optional[dict] = None) -> Tuple[np.ndarray, float]:
    """Rotary angles over sequence positions 0..n-1 for ``dim`` features
    (each frequency doubled adjacently, as ``apply_rotary`` pairs them), with
    YaRN's blend of frequencies when ``scaling`` gives ``factor`` > 1:
    a frequency that turns more than ``beta_fast`` times within
    ``original_max_position`` positions is kept, one that turns fewer than
    ``beta_slow`` times is divided by ``factor``, a linear ramp between.
    Returns (angles (n, dim), the factor cos and sin are scaled by:
    mscale(factor, mscale) / mscale(factor, mscale_all_dim))."""
    inv = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    cos_sin_scale = 1.0
    factor = float((scaling or {}).get("factor", 1.0))
    if factor > 1:
        sc = scaling

        def correction_dim(rotations):
            return (dim * math.log(sc["original_max_position"]
                                   / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(correction_dim(sc["beta_fast"])), 0)
        high = min(math.ceil(correction_dim(sc["beta_slow"])), dim - 1)
        if low == high:
            high += 0.001
        ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        cos_sin_scale = (yarn_mscale(factor, sc.get("mscale", 1.0))
                         / yarn_mscale(factor, sc.get("mscale_all_dim", 0.0)))
    angles = np.repeat(np.outer(np.arange(n, dtype=np.float64), inv), 2, -1)
    return angles.astype(np.float32), cos_sin_scale


def dalle_pos_emb(text_len: int, image_fmap_size: int, dim_head: int) -> np.ndarray:
    """The DALLE combined rotary table, shape (text_len + fmap², 3·2·(rot//2)).

    ``text_len`` includes the <bos> slot (reference passes seq_len-img_seq+1,
    transformer.py:308). Built in numpy: it is a constant.
    """
    rot_dim = dim_head // 3
    img_seq_len = image_fmap_size ** 2
    lang = lang_freqs(rot_dim)
    pixel = pixel_freqs(rot_dim)

    # 1D lang-band: text positions 0..text_len-1; images pinned far away at 8192
    text_freqs = freqs_table(np.arange(text_len), lang)
    img_to_text = freqs_table(np.full((img_seq_len,), 8192.0), lang)
    band1 = np.concatenate((text_freqs, img_to_text), axis=0)

    # 2D pixel-band: rows/cols over linspace(-1,1); text pinned at -10 on both axes
    axial = freqs_table(np.linspace(-1.0, 1.0, image_fmap_size), pixel)  # (f, d)
    rows = np.broadcast_to(axial[:, None, :], (image_fmap_size, image_fmap_size, axial.shape[-1]))
    cols = np.broadcast_to(axial[None, :, :], (image_fmap_size, image_fmap_size, axial.shape[-1]))
    img2d = np.concatenate((rows, cols), axis=-1).reshape(img_seq_len, -1)
    text_axial = freqs_table(np.full((text_len,), -10.0), pixel)
    text_axial = np.concatenate((text_axial, text_axial), axis=-1)
    band2 = np.concatenate((text_axial, img2d), axis=0)

    return np.concatenate((band1, band2), axis=-1)
