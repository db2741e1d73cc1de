"""Sampling primitives — pure XLA, jit/scan friendly.

Reference analogues: ``top_k`` (dalle_pytorch/dalle_pytorch.py:63-69),
``gumbel_sample`` (:60-61), ``prob_mask_like`` (:47-49, the CFG dropout mask),
``masked_mean`` (:43-45).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -jnp.inf


def top_k_filter(logits: jnp.ndarray, thres: float = 0.5,
                 approx: bool = False) -> jnp.ndarray:
    """Keep the top ceil((1-thres)*vocab) logits, set the rest to -inf.

    Static-shape formulation: k is computed from the (static) vocab size so the
    op lowers to a single jax.lax.top_k — no dynamic shapes under jit.

    ``approx=True`` finds the k-th threshold with ``jax.lax.approx_max_k``
    (TPU's hardware-accelerated approximate top-k) instead of the exact sort:
    ~20x faster at vocab 8k on v5e, where the exact sort is ~17% of the whole
    decode loop. Approximation only blurs WHICH near-threshold logits are
    kept; those carry the lowest kept probabilities, so sampling is nearly
    unaffected (validated on a trained model by
    scripts/eval_decode_precisions.py)."""
    num = logits.shape[-1]
    k = max(int((1.0 - thres) * num), 1)
    if approx:
        kth = jax.lax.approx_max_k(logits, k)[0][..., -1:]
    else:
        kth = jax.lax.top_k(logits, k)[0][..., -1:]
    return jnp.where(logits < kth, NEG_INF, logits)


def top_p_filter(logits: jnp.ndarray, top_p: float = 0.9) -> jnp.ndarray:
    """Nucleus filtering (additive capability; the reference exposes top-k only)."""
    sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_logits, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens until cumulative prob exceeds top_p (always keep the first)
    keep_sorted = jnp.concatenate(
        [jnp.ones_like(cum[..., :1], dtype=bool), cum[..., :-1] < top_p], axis=-1)
    kth = jnp.min(jnp.where(keep_sorted, sorted_logits, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < kth, NEG_INF, logits)


def gumbel_sample(key: jax.Array, logits: jnp.ndarray, temperature: float = 1.0,
                  axis: int = -1) -> jnp.ndarray:
    """argmax(logits/T + Gumbel noise) — identical semantics to the reference's
    gumbel trick (dalle_pytorch.py:54-61)."""
    g = jax.random.gumbel(key, logits.shape, dtype=jnp.float32)
    return jnp.argmax(logits.astype(jnp.float32) / max(temperature, 1e-10) + g, axis=axis)


def gumbel_sample_rows(keys: jax.Array, logits: jnp.ndarray, *,
                       thres: float = 0.5, temperature: float = 1.0,
                       approx: bool = False) -> jnp.ndarray:
    """Per-row filtered gumbel-argmax: one PRNG key PER ROW of (b, V)
    logits — the batched form of ``top_k_filter`` + ``gumbel_sample`` whose
    recipe the serve engine and the speculative verify step both rely on
    for token-exactness, kept in one place so the two paths cannot drift.
    The per-row (V,) gumbel draw is bitwise identical to a sequential
    (1, V) draw under the same key (threefry bits depend only on the flat
    element count), so a row sampled here equals that row sampled alone."""
    filt = top_k_filter(logits, thres=thres, approx=approx)
    g = jax.vmap(lambda k: jax.random.gumbel(
        k, (logits.shape[-1],), jnp.float32))(keys)
    scaled = filt.astype(jnp.float32) / max(temperature, 1e-10)
    return jnp.argmax(scaled + g, axis=-1).astype(jnp.int32)


def prob_mask_like(key: jax.Array, shape, prob: float) -> jnp.ndarray:
    """Bernoulli(prob) boolean mask — used for classifier-free-guidance dropout of
    the text condition (reference dalle_pytorch.py:47-49, used at :570-574)."""
    if prob <= 0:
        return jnp.zeros(shape, dtype=bool)
    if prob >= 1:
        return jnp.ones(shape, dtype=bool)
    return jax.random.uniform(key, shape) < prob


def masked_mean(t: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean over axis 1 counting only mask==True positions (reference :43-45)."""
    t = jnp.where(mask[..., None], t, 0.0)
    denom = jnp.clip(mask.sum(axis=1, keepdims=True), 1, None)
    return t.sum(axis=1) / denom
