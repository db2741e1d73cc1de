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
from dalle_tpu.ops.fused_attention import (BlockPlan, block_plan, fused_fits,
                                           fused_qkv_attention,
                                           validity_table)


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


def _table(kind, n):
    """The parametrised cases' masks: (mask, mask_spec, the (n, n) table)."""
    fmap = 4 if n < 128 else 16
    text_len = n - fmap * fmap
    if kind == "causal":
        mask, spec = None, None
    elif kind == "conv":
        mask, spec = None, ("conv", text_len, fmap, 3, 1)
    elif kind == "axial":
        mask, spec = None, ("axial", text_len, fmap, 0)
    else:
        mask, spec = np.tril(np.ones((n, n), bool)), None
        if kind == "explicit":   # a key block below the diagonal left empty
            mask[n // 2:3 * n // 4, n // 4:n // 2] = False
        else:   # short_sighted: the third quarter of the rows sees the first
            # quarter of the keys only (the second is reached from two row
            # blocks that are no neighbours), and no row sees the last
            mask[n // 2:3 * n // 4, n // 4:] = False
            mask[3 * n // 4:, 3 * n // 4:] = False
    return mask, spec, validity_table(n, mask, spec) != 0


@pytest.mark.parametrize("kind", ["causal", "conv", "axial", "explicit",
                                  "short_sighted"])
@pytest.mark.parametrize("n", [48, 512, 513],
                         ids=["one_block", "four_blocks", "ragged_block"])
def test_blocks_match_dense(n, kind):
    """Forward and gradient over the block plan's three shapes (one block
    of the whole square; four row blocks; a ragged fifth) ≡ dense attention
    under the same table."""
    rng = np.random.RandomState(n)
    h, d = 2, 16
    mask, spec, table = _table(kind, n)
    plan = block_plan(table)
    assert len(plan.spans) == {48: 1, 512: 4, 513: 5}[n]
    if n > 48:
        assert plan.computed < plan.of
    qkv = jnp.asarray(rng.standard_normal((1, n, 3 * h * d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((1, n, h * d)), jnp.float32)

    def fused(a):
        return fused_qkv_attention(a, mask, h, None, True, spec)

    out, vjp = jax.vjp(fused, qkv)
    ref, vjp_ref = jax.vjp(lambda a: _dense(a, h, table), qkv)
    # the file's tolerances, absolute: a key counted twice in a ragged
    # block's sums would hide in a relative one
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0, atol=2e-2)
    np.testing.assert_allclose(np.asarray(vjp(do)[0]),
                               np.asarray(vjp_ref(do)[0]),
                               rtol=0, atol=5e-2)


def test_delta_from_saved_output_matches_recomputed():
    """The backward forms delta = sum(o * do) from the forward's saved
    output where the kernel before it ran P V a second time: the gradient
    is the one that six-product backward gave (written out here in
    jax.numpy with its bfloat16 operands)."""
    rng = np.random.RandomState(7)
    n, h, d = 256, 2, 16
    qkv = jnp.asarray(rng.standard_normal((1, n, 3 * h * d)), jnp.float32)
    do = jnp.asarray(rng.standard_normal((1, n, h * d)), jnp.float32)
    gk = jax.vjp(lambda a: fused_qkv_attention(a, None, h, None, True),
                 qkv)[1](do)[0]

    bf = jnp.bfloat16
    valid = np.tril(np.ones((n, n), bool))
    q4, k4, v4 = (t[0].astype(bf) for t in _split(qkv, h))      # (h, n, d)
    do4 = do[0].astype(bf).reshape(n, h, d).transpose(1, 0, 2)
    grads = []
    for q, k, v, do16 in zip(q4, k4, v4, do4):
        qs = (q.astype(jnp.float32) * d ** -0.5).astype(bf)
        s = jnp.where(valid, jnp.dot(qs, k.T,
                                     preferred_element_type=jnp.float32),
                      -1e9)
        p = jax.nn.softmax(s, axis=-1)
        p16 = p.astype(bf)
        dp = jnp.dot(do16, v.T, preferred_element_type=jnp.float32)
        o = jnp.dot(p16, v, preferred_element_type=jnp.float32)   # again
        delta = jnp.sum(o * do16.astype(jnp.float32), -1, keepdims=True)
        ds = (p * (dp - delta)).astype(bf)
        f32 = dict(preferred_element_type=jnp.float32)
        grads.append((jnp.dot(ds, k, **f32) * d ** -0.5,
                      jnp.dot(ds.T, q, **f32) * d ** -0.5,
                      jnp.dot(p16.T, do16, **f32)))
    six = jnp.concatenate([g[part] for part in range(3) for g in grads],
                          axis=-1)[None]
    np.testing.assert_allclose(np.asarray(gk), np.asarray(six),
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


# -- the block plan alone (numpy, no kernel) -----------------------------------

def _short_sighted(n=512):
    """Causal, but the last row block sees nothing past column 128."""
    table = np.tril(np.ones((n, n), bool))
    table[384:, 128:] = False
    return table


@pytest.mark.parametrize("table, extents, computed, of, masked", [
    # the plain causal table: only the blocks on the diagonal are masked
    (np.tril(np.ones((512, 512), bool)), [128, 256, 384, 512], 10, 16,
     [(0, 0), (1, 1), (2, 2), (3, 3)]),
    # one row block: the whole square, today's program
    (np.tril(np.ones((48, 48), bool)), [48], 1, 1, [(0, 0)]),
    (_short_sighted(), [128, 256, 384, 128], 7, 16,
     [(0, 0), (1, 1), (2, 2)]),
    # a ragged last block of one row, which sees every key
    (np.tril(np.ones((513, 513), bool)), [128, 256, 384, 512, 513], 15, 25,
     [(0, 0), (1, 1), (2, 2), (3, 3)]),
    # nothing to skip (the last row of the first block sees the last key)
    (np.ones((256, 256), bool), [256], 1, 1, []),
], ids=["causal_512", "one_block_48", "short_sighted_rows", "ragged_513",
        "nothing_to_skip"])
def test_block_plan(table, extents, computed, of, masked):
    plan = block_plan(table)
    assert isinstance(plan, BlockPlan)
    assert list(plan.extents) == extents
    assert (plan.computed, plan.of) == (computed, of)
    assert sorted(plan.masked) == masked
    n = table.shape[0]
    assert plan.spans[0][0] == 0 and plan.spans[-1][1] == n
    assert all(a[1] == b[0] for a, b in zip(plan.spans, plan.spans[1:]))
    # a row block's extent is whole key blocks, and ``reach`` is its
    # transpose: the runs of row blocks that cover a key block
    widths = [plan.width(j) for j in range(len(plan.spans))]
    assert [plan.spans[w - 1][1] for w in widths] == extents
    assert sum(widths) == computed
    for ci in range(len(plan.spans)):
        covered = [j for first, last in plan.reach(ci)
                   for j in range(first, last + 1)]
        assert covered == [j for j, w in enumerate(widths) if w > ci]


def test_block_plan_keeps_a_row_that_sees_nothing_whole():
    """Such a row's softmax is taken over masked scores alone; it spans what
    is computed, so the plan computes what the whole square did."""
    table = np.tril(np.ones((512, 512), bool))
    table[130] = False
    assert list(block_plan(table).extents) == [128, 512, 384, 512]


def test_stack_layers_counts_the_score_blocks(monkeypatch):
    """``init/build_step``'s ``layers`` says how far the plan engages for the
    fused tier (DALL·E-small: 10 of 16 blocks, five products), from the
    plan the kernel is built from; a dense or a flash stack has no entry."""
    from dalle_tpu.config import DalleConfig
    from dalle_tpu.models.transformer import stack_layers

    small = DalleConfig(num_text_tokens=10000, text_seq_len=256, dim=512,
                        depth=12, heads=8, dim_head=64, image_size=128,
                        image_vocab_size=8192, image_fmap_size=16)
    assert "fused" not in stack_layers(small.transformer())   # dense: no TPU
    for tier in ("flash", "dense"):
        monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                            lambda *a, tier=tier, **kw: tier)
        assert "fused" not in stack_layers(small.transformer())
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **kw: "fused")
    assert stack_layers(small.transformer())["fused"] == {
        "score_blocks": [10, 16], "products_bwd": 5}
    tiny = DalleConfig(num_text_tokens=64, text_seq_len=8, dim=32, depth=2,
                       heads=2, dim_head=16, image_size=16,
                       image_vocab_size=32, image_fmap_size=4)
    assert stack_layers(tiny.transformer())["fused"]["score_blocks"] == [1, 1]
    mixed = small.replace(attn_types=("full", "axial_row"))
    computed, of = stack_layers(mixed.transformer())["fused"]["score_blocks"]
    assert (computed, of) == (20, 32)   # the two tables, ten blocks each
