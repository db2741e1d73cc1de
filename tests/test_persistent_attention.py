"""VMEM-persistent whole-sequence attention (ops/persistent_attention.py):
forward and custom_vjp backward ≡ dense attend + autodiff (interpret mode on
CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.attention import attend
from dalle_tpu.ops.persistent_attention import (persistent_attention,
                                                persistent_fits)


def _qkv(rng, b=2, h=2, n=48, d=16):
    return [jnp.asarray(rng.standard_normal((b, h, n, d)), jnp.float32)
            for _ in range(3)]


def test_forward_matches_dense_causal():
    rng = np.random.RandomState(0)
    q, k, v = _qkv(rng)
    out = persistent_attention(q, k, v, None, None, True)
    ref = attend(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_forward_matches_dense_with_mask():
    from dalle_tpu.ops.attn_masks import axial_mask
    rng = np.random.RandomState(1)
    n = 4 + 16
    q, k, v = _qkv(rng, n=n)
    mask = axial_mask(4, 4, axis=0)
    out = persistent_attention(q, k, v, mask, None, True)
    ref = attend(q, k, v, causal=True, static_mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-2, atol=2e-2)


def test_backward_matches_autodiff():
    rng = np.random.RandomState(2)
    q, k, v = _qkv(rng)
    do = jnp.asarray(rng.standard_normal(q.shape), jnp.float32)

    def loss_kernel(q, k, v):
        return jnp.sum(persistent_attention(q, k, v, None, None, True) * do)

    def loss_dense(q, k, v):
        return jnp.sum(attend(q, k, v, causal=True) * do)

    gk = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gk, gd):
        # bf16 in-kernel dots vs f32 dense autodiff
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-2, atol=5e-2)


def test_auto_policy_tiers():
    from dalle_tpu.ops.flash_attention import resolve_use_pallas
    assert resolve_use_pallas("auto", 4096, backend="tpu") == "flash"
    # persist measured SLOWER end-to-end (docs/PERF_SMALL.md r4); its r5
    # fused-boundary successor WINS (0.458 vs 0.391 MFU) and auto now
    # selects it at mid lengths where it fits; "persist" stays opt-in
    assert resolve_use_pallas("auto", 513, backend="tpu") == "fused"
    assert resolve_use_pallas("auto", 128, backend="tpu") == "fused"
    assert resolve_use_pallas("persist", 513, backend="tpu") == "persist"
    # on the TPU an explicit tier that cannot be honoured names its gate
    with pytest.raises(ValueError, match="persistent_fits"):
        resolve_use_pallas("persist", 1280, backend="tpu")
    assert resolve_use_pallas("persist", 513, backend="cpu") is False
    assert resolve_use_pallas("on", 128, backend="cpu") == "flash"
    assert resolve_use_pallas(False, 4096, backend="tpu") is False
    assert persistent_fits(513, 64) and not persistent_fits(1280, 64)


def test_transformer_persist_mode_runs():
    """use_pallas='persist' routes the training forward through the kernel
    (interpret on CPU) and matches the dense default."""
    from dalle_tpu.config import TransformerConfig
    from dalle_tpu.models.transformer import Transformer

    kw = dict(seq_len=24, dim=32, depth=2, heads=2, dim_head=16,
              image_fmap_size=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 25, 32))
    m1 = Transformer(TransformerConfig(use_pallas=False, **kw))
    params = m1.init(jax.random.PRNGKey(1), x)
    ref = m1.apply(params, x)
    m2 = Transformer(TransformerConfig(use_pallas="persist", **kw))
    # on CPU "persist" resolves to dense; force the mode via resolved field
    import dalle_tpu.ops.flash_attention as fa
    orig = fa.resolve_use_pallas
    fa.resolve_use_pallas = lambda *a, **k2: "persist"
    try:
        out = m2.apply(params, x)
    finally:
        fa.resolve_use_pallas = orig
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)
