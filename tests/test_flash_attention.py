"""Flash/block-sparse Pallas kernels vs the dense reference `attend`.

Runs in interpret mode on CPU (conftest forces JAX_PLATFORMS=cpu)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.attention import attend
from dalle_tpu.ops.attn_masks import (axial_mask, build_mask,
                                      conv_like_mask)
from dalle_tpu.ops.flash_attention import (build_block_lists, flash_attention,
                                           sparsity_fraction)

B, H, D = 2, 3, 16


def _qkv(n, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, H, n, D)
    return tuple(jax.random.normal(k, shape, dtype) for k in ks)


def test_block_lists_causal():
    lists = build_block_lists(128, 32, 32, mask=None, causal=True)
    # row i attends to blocks 0..i
    assert list(lists.k_cnt) == [1, 2, 3, 4]
    assert list(lists.q_cnt) == [4, 3, 2, 1]
    np.testing.assert_array_equal(lists.k_ids[3][:4], [0, 1, 2, 3])


def test_sparsity_fraction_counts_skipped_blocks():
    text_len = 33
    mask = build_mask("axial_row", text_len, 16)
    frac = sparsity_fraction(text_len + 256, block_q=32, block_k=32, mask=mask)
    dense = sparsity_fraction(text_len + 256, block_q=32, block_k=32)
    assert frac < dense <= 0.6  # causal alone ~ half the blocks


@pytest.mark.parametrize("n", [96, 130])
def test_forward_matches_dense_causal(n):
    q, k, v = _qkv(n)
    ref = attend(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("attn_type", ["axial_row", "axial_col", "conv_like",
                                       "sparse"])
def test_forward_matches_dense_masked(attn_type):
    text_len, fmap = 17, 8
    mask = build_mask(attn_type, text_len, fmap, kernel_size=3, block=32,
                      num_random_blocks=1)
    n = text_len + fmap * fmap
    q, k, v = _qkv(n, seed=1)
    ref = attend(q, k, v, causal=True, static_mask=jnp.asarray(mask))
    out = flash_attention(q, k, v, mask=mask, causal=True,
                          block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("attn_type", [None, "axial_row", "conv_like"])
def test_gradients_match_dense(attn_type):
    text_len, fmap = 17, 8
    n = text_len + fmap * fmap
    if attn_type is None:
        mask = None
        jmask = None
    else:
        mask = build_mask(attn_type, text_len, fmap, kernel_size=3)
        jmask = jnp.asarray(mask)
    q, k, v = _qkv(n, seed=2)

    def loss_ref(q, k, v):
        o = attend(q, k, v, causal=True, static_mask=jmask)
        return jnp.sum(jnp.sin(o))

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, mask=mask, causal=True,
                            block_q=32, block_k=32)
        return jnp.sum(jnp.sin(o))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=3e-5, atol=3e-5)


def test_bfloat16_forward_close():
    n = 64
    q, k, v = _qkv(n, seed=3, dtype=jnp.bfloat16)
    ref = attend(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref, dtype=np.float32),
                               rtol=2e-2, atol=2e-2)


def test_jit_and_vmap_compatible():
    n = 64
    q, k, v = _qkv(n, seed=4)

    @jax.jit
    def f(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32, block_k=32)

    out = f(q, k, v)
    ref = attend(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_use_pallas_auto_policy():
    """use_pallas='auto': flash from seq 2048 up on the TPU, below that the
    fused kernel where it fits and dense where it does not, dense off the
    TPU; 'off' and False are dense everywhere; nothing else is taken."""
    from dalle_tpu.ops.attention import FLASH_MIN_SEQ, attention_tier
    assert FLASH_MIN_SEQ == 2048
    assert attention_tier("auto", 4352, 8, 64, backend="tpu") == "flash"
    assert attention_tier("auto", 2048, 8, 64, backend="tpu") == "flash"
    assert attention_tier("auto", 2047, 8, 64, backend="tpu") == "dense"
    assert attention_tier("auto", 512, 8, 64, backend="tpu") == "fused"
    # shapes whose fused backward busts scoped VMEM stay dense
    assert attention_tier("auto", 512, 14, 128, backend="tpu") == "dense"
    assert attention_tier("auto", 4352, 8, 64, backend="cpu") == "dense"
    assert attention_tier(False, 99999, 8, 64) == "dense"
    assert attention_tier("off", 99999, 8, 64, backend="tpu") == "dense"
    with pytest.raises(ValueError, match="auto.*off"):
        attention_tier("sometimes", 128, 8, 64)


def test_transformer_use_pallas_matches_dense(monkeypatch):
    """The flash tier flips the full-sequence path onto the flash kernel;
    the result must match the dense masked path. (The chooser answers dense
    off the TPU and below 2048, so the test substitutes it.)"""
    from dalle_tpu.config import TransformerConfig
    from dalle_tpu.models.transformer import Transformer

    kw = dict(dim=32, depth=2, heads=2, dim_head=16, seq_len=80,
              image_fmap_size=8, attn_types=("full", "axial_row"),
              rotary_emb=False)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 81, 32))
    m_dense = Transformer(TransformerConfig(**kw, use_pallas="off"))
    params = m_dense.init(jax.random.PRNGKey(1), x)
    y_dense = m_dense.apply(params, x)
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **k2: "flash")
    m_flash = Transformer(TransformerConfig(**kw))
    y_flash = m_flash.apply(params, x)
    np.testing.assert_allclose(np.asarray(y_flash), np.asarray(y_dense),
                               rtol=2e-4, atol=2e-4)


def test_fully_masked_row_inside_visible_block():
    """A row whose every key is masked, inside a block other rows keep visible:
    forward must output 0 for that row (dense path convention: uniform attention
    over -inf rows differs, so compare via gradients being finite and other rows
    matching dense)."""
    n = 64
    mask = np.tril(np.ones((n, n), dtype=bool))
    mask[10, :] = False   # row 10 sees nothing
    q, k, v = _qkv(n, seed=7)
    out = flash_attention(q, k, v, mask=mask, causal=True,
                          block_q=32, block_k=32)
    # empty row → zero output, and it must not pollute its block's neighbors
    np.testing.assert_allclose(np.asarray(out[:, :, 10]), 0.0, atol=1e-6)
    ref = attend(q, k, v, causal=True, static_mask=jnp.asarray(mask))
    keep = [i for i in range(n) if i != 10]
    np.testing.assert_allclose(np.asarray(out[:, :, keep]),
                               np.asarray(ref[:, :, keep]),
                               rtol=2e-5, atol=2e-5)

    def loss(q, k, v):
        o = flash_attention(q, k, v, mask=mask, causal=True,
                            block_q=32, block_k=32)
        return jnp.sum(jnp.sin(o))

    grads = jax.grad(loss, (0, 1, 2))(q, k, v)
    for g in grads:
        assert bool(jnp.all(jnp.isfinite(g)))
    # gradients for surviving rows must match the dense path

    def loss_ref(q, k, v):
        o = attend(q, k, v, causal=True, static_mask=jnp.asarray(mask))
        keep_o = jnp.concatenate([o[:, :, :10], o[:, :, 11:]], axis=2)
        return jnp.sum(jnp.sin(keep_o))

    def loss_keep(q, k, v):
        o = flash_attention(q, k, v, mask=mask, causal=True,
                            block_q=32, block_k=32)
        keep_o = jnp.concatenate([o[:, :, :10], o[:, :, 11:]], axis=2)
        return jnp.sum(jnp.sin(keep_o))

    g_ref = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_keep, (0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        # dense grad for the empty q row is garbage-driven; exclude it
        am, bm = np.array(a), np.array(b)
        am[:, :, 10] = 0; bm[:, :, 10] = 0
        np.testing.assert_allclose(bm, am, rtol=3e-5, atol=3e-5)


@pytest.mark.parametrize("spec,builder", [
    (("axial", 10, 4, 0), lambda: axial_mask(10, 4, axis=0)),
    (("axial", 10, 4, 1), lambda: axial_mask(10, 4, axis=1)),
    (("conv", 10, 4, 3, 1), lambda: conv_like_mask(10, 4, kernel_size=3)),
])
def test_structured_mask_spec_matches_table(spec, builder):
    """mask_spec computes element visibility in-kernel from iotas; outputs and
    grads must equal the mask-table path exactly (same block lists, same
    math — just no mask operand)."""
    mask = np.asarray(builder())
    n = mask.shape[0]
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 2, n, 16))
               for i in range(3))

    def loss_table(q, k, v):
        o = flash_attention(q, k, v, mask=mask, causal=True,
                            block_q=16, block_k=16)
        return jnp.sum(jnp.sin(o))

    def loss_spec(q, k, v):
        o = flash_attention(q, k, v, mask=mask, mask_spec=spec, causal=True,
                            block_q=16, block_k=16)
        return jnp.sum(jnp.sin(o))

    lt, gt = jax.value_and_grad(loss_table, (0, 1, 2))(q, k, v)
    ls, gs = jax.value_and_grad(loss_spec, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(ls), float(lt), rtol=1e-6)
    for a, b in zip(gt, gs):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n,B", [
    (26, 8),     # non-lane-aligned pattern block → falls back to the tabled
                 # element-mask path (tiny Mosaic tiles would be a lowering
                 # failure/perf cliff on real TPU); numerics must be identical
    (300, 128),  # lane-aligned: kernel tiles pinned to the pattern's block
                 # grid so the block lists alone encode the sparsity
])
def test_block_aligned_spec_matches_table(n, B):
    """('block', B) spec vs the tabled path for the DeepSpeed-style
    random-block pattern — equal outputs/grads whether the spec engages the
    pinned-tile shortcut (B % 128 == 0) or falls back to the mask table."""
    from dalle_tpu.ops.attn_masks import block_sparse_mask
    mask = np.asarray(block_sparse_mask(n, text_len=10, block=B,
                                        num_random_blocks=1, seed=3))
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (2, 2, n, 16))
               for i in range(3))

    def loss_table(q, k, v):
        o = flash_attention(q, k, v, mask=mask, causal=True,
                            block_q=min(B, 32), block_k=min(B, 32))
        return jnp.sum(jnp.sin(o))

    def loss_spec(q, k, v):
        o = flash_attention(q, k, v, mask=mask, mask_spec=("block", B),
                            causal=True)
        return jnp.sum(jnp.sin(o))

    lt, gt = jax.value_and_grad(loss_table, (0, 1, 2))(q, k, v)
    ls, gs = jax.value_and_grad(loss_spec, (0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(ls), float(lt), rtol=1e-6)
    for a, b in zip(gt, gs):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n", [128, 96, 150],
                         ids=["whole_blocks", "one_short_block", "ragged"])
@pytest.mark.parametrize("dk,dv", [(24, 16), (8, 16)],
                         ids=["keys_wider", "values_wider"])
def test_two_head_widths_match_dense(n, dk, dv):
    """Latent attention's queries and keys are wider than its values (192
    against 128; 24 against 16 here): the one family of kernels takes the
    two widths as they come, forward and the three gradients, against
    ``attend``, at a length that is a block multiple and at two that are
    not, under a scale that is not the default's."""
    ks = jax.random.split(jax.random.PRNGKey(n + dk), 3)
    q, k = (jax.random.normal(key, (B, H, n, dk)) for key in ks[:2])
    v = jax.random.normal(ks[2], (B, H, n, dv))
    scale = 0.7 * dk ** -0.5

    def dense(q, k, v):
        return attend(q, k, v, causal=True, scale=scale)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               block_q=32, block_k=32)
    out = flash(q, k, v)
    assert out.shape == (B, H, n, dv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    w = jax.random.normal(jax.random.PRNGKey(7), out.shape)
    want = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), err_msg=name,
                                   rtol=3e-5, atol=3e-5)
