"""Fused-boundary attention (ops/fused_attention.py): forward and custom_vjp
backward ≡ split + dense attend + autodiff, straight off the (b, n, 3·h·d)
qkv layout (interpret mode on CPU; the Mosaic build is compiled for a
described v5e in tests/test_chip_compile.py and run on the chip by
chip_smoke.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.attention import attend
from dalle_tpu.ops.fused_attention import fused_fits, fused_qkv_attention


def _split(qkv, heads):
    b, n, hd3 = qkv.shape
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (b, n, heads, hd3 // 3 // heads)
    return [t.reshape(shape).transpose(0, 2, 1, 3) for t in (q, k, v)]


def _merge(out):
    b, h, n, d = out.shape
    return out.transpose(0, 2, 1, 3).reshape(b, n, h * d)


def _dense(qkv, heads, mask=None):
    q, k, v = _split(qkv, heads)
    static = None if mask is None else jnp.asarray(mask)
    return _merge(attend(q, k, v, causal=True, static_mask=static))


def test_forward_matches_dense_causal():
    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.standard_normal((2, 48, 3 * 2 * 16)), jnp.float32)
    out = fused_qkv_attention(qkv, None, 2, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(qkv, 2)),
                               rtol=2e-2, atol=2e-2)


def test_forward_matches_dense_with_mask():
    from dalle_tpu.ops.attn_masks import axial_mask
    rng = np.random.RandomState(1)
    n = 4 + 16
    qkv = jnp.asarray(rng.standard_normal((2, n, 3 * 2 * 16)), jnp.float32)
    mask = axial_mask(4, 4, axis=0)
    out = fused_qkv_attention(qkv, mask, 2, None, True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_dense(qkv, 2, mask)),
                               rtol=2e-2, atol=2e-2)


def test_spec_path_matches_table_path():
    """Structured axial/conv specs compute visibility from iotas in-kernel
    (no table operand) and must agree with the shipped-table path AND dense,
    fwd and bwd."""
    from dalle_tpu.ops.attn_masks import build_mask
    rng = np.random.RandomState(3)
    text_len, fmap = 4, 4
    n = text_len + fmap * fmap
    qkv = jnp.asarray(rng.standard_normal((2, n, 3 * 2 * 16)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((2, n, 2 * 16)), jnp.float32)
    for kind, spec in [
            ("axial_row", ("axial", text_len, fmap, 0)),
            ("axial_col", ("axial", text_len, fmap, 1)),
            ("conv_like", ("conv", text_len, fmap, 3, 1))]:
        mask = build_mask(kind, text_len, fmap, kernel_size=3)
        via_table = fused_qkv_attention(qkv, mask, 2, None, True)
        via_spec = fused_qkv_attention(qkv, mask, 2, None, True, spec)
        np.testing.assert_allclose(np.asarray(via_spec),
                                   np.asarray(via_table),
                                   rtol=2e-2, atol=2e-2, err_msg=kind)
        np.testing.assert_allclose(np.asarray(via_spec),
                                   np.asarray(_dense(qkv, 2, mask)),
                                   rtol=2e-2, atol=2e-2, err_msg=kind)
        gs = jax.grad(lambda a: jnp.sum(
            fused_qkv_attention(a, mask, 2, None, True, spec) * do))(qkv)
        gd = jax.grad(lambda a: jnp.sum(_dense(a, 2, mask) * do))(qkv)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(gd),
                                   rtol=5e-2, atol=5e-2, err_msg=kind)


def test_backward_matches_autodiff():
    rng = np.random.RandomState(2)
    qkv = jnp.asarray(rng.standard_normal((2, 48, 3 * 2 * 16)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((2, 48, 2 * 16)), jnp.float32)

    gk = jax.grad(lambda a: jnp.sum(
        fused_qkv_attention(a, None, 2, None, True) * do))(qkv)
    gd = jax.grad(lambda a: jnp.sum(_dense(a, 2) * do))(qkv)
    # bf16 in-kernel dots vs f32 dense autodiff
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gd),
                               rtol=5e-2, atol=5e-2)


def test_grouped_store_path():
    """h=4, d=64 drives group=2 (128-lane paired stores, the medium-shape
    VMEM lever) in interpret mode — the other tests' h=2/d=16 shapes fall
    back to the single-concat write."""
    rng = np.random.RandomState(5)
    h, d, n = 4, 64, 32
    qkv = jnp.asarray(rng.standard_normal((2, n, 3 * h * d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((2, n, h * d)), jnp.float32)
    out = fused_qkv_attention(qkv, None, h, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(_dense(qkv, h)),
                               rtol=2e-2, atol=2e-2)
    gk = jax.grad(lambda a: jnp.sum(
        fused_qkv_attention(a, None, h, None, True) * do))(qkv)
    gd = jax.grad(lambda a: jnp.sum(_dense(a, h) * do))(qkv)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gd),
                               rtol=5e-2, atol=5e-2)


def test_resolve_tiers():
    from dalle_tpu.ops.attention import attention_tier
    # auto selects fused where the merged backward fits under the RAISED
    # Mosaic vmem ceiling: small (8 x 64) and medium (16 x 64, the 32M-limit
    # backward); 14 x 128 does not fit and stays dense; flash from 2048 up
    assert attention_tier("auto", 513, 8, 64, backend="tpu") == "fused"
    assert attention_tier("auto", 513, 16, 64, backend="tpu") == "fused"
    assert attention_tier("auto", 513, 14, 128, backend="tpu") == "dense"
    assert attention_tier("auto", 4096, 8, 64, backend="tpu") == "flash"
    assert attention_tier("auto", 513, 8, 64, backend="cpu") == "dense"
    assert fused_fits(513, 64, 8) and not fused_fits(2048, 64, 8)
    assert fused_fits(513, 64, 16) and not fused_fits(513, 128, 14)


def test_transformer_fused_mode_matches_dense(monkeypatch):
    """The fused tier routes the training forward (rotary ON — the
    (b, n, 3h, d)-view rotary application) through the kernel and matches
    the dense default. Off the TPU the chooser answers dense, so the test
    substitutes it."""
    from dalle_tpu.config import TransformerConfig
    from dalle_tpu.models.transformer import Transformer

    kw = dict(seq_len=24, dim=32, depth=2, heads=2, dim_head=16,
              image_fmap_size=4, rotary_emb=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 25, 32))
    m1 = Transformer(TransformerConfig(use_pallas=False, **kw))
    params = m1.init(jax.random.PRNGKey(1), x)
    ref = m1.apply(params, x)
    m2 = Transformer(TransformerConfig(**kw))
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **k2: "fused")
    out = m2.apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-2, atol=3e-2)


def test_transformer_fused_grads_match_dense(monkeypatch):
    """End-to-end grads through the fused kernel ≡ dense autodiff (the
    integration contract VERDICT r4 #1 names)."""
    from dalle_tpu.config import TransformerConfig
    from dalle_tpu.models.transformer import Transformer

    kw = dict(seq_len=24, dim=32, depth=1, heads=2, dim_head=16,
              image_fmap_size=4, rotary_emb=True)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 25, 32))
    m1 = Transformer(TransformerConfig(use_pallas=False, **kw))
    params = m1.init(jax.random.PRNGKey(1), x)

    def loss(mod):
        return lambda p: jnp.sum(mod.apply(p, x) ** 2)

    gd = jax.grad(loss(m1))(params)
    m2 = Transformer(TransformerConfig(**kw))
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **k2: "fused")
    gk = jax.grad(loss(m2))(params)
    for a, b in zip(jax.tree.leaves(gk), jax.tree.leaves(gd)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=6e-2, atol=6e-2)
