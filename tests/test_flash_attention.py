"""Flash/block-sparse Pallas kernels vs the dense reference `attend`.

Runs in interpret mode on CPU (conftest forces JAX_PLATFORMS=cpu)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.attention import attend
from dalle_tpu.ops.attn_masks import (axial_mask, build_mask,
                                      conv_like_mask)
from dalle_tpu.ops.flash_attention import (build_block_lists,
                                           flash_attention,
                                           flash_block_counts,
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


# -- the operands' own type, and the count of blocks no mask cuts (PR 35) ----

@pytest.mark.parametrize("n,dk,dv", [
    (256, 128, 128),     # the grouped-query layer's head (Solar)
    (256, 192, 128),     # latent attention's two widths (Ling)
    (300, 128, 128),     # a ragged tail: the last block holds padded keys
    (300, 192, 128)], ids=["128_128", "192_128", "ragged_128", "ragged_192"])
def test_bfloat16_inputs_match_dense_forward_and_gradients(n, dk, dv):
    """bfloat16 q, k, v through bfloat16 products with float32 sums, as
    ``attend`` multiplies them: forward and the three gradients against
    ``attend`` on the same bfloat16 inputs, at the cells' two shapes of head
    (a full block, a cut one and, ragged, a padded tail)."""
    ks = jax.random.split(jax.random.PRNGKey(n + dk), 4)
    q, k = (jax.random.normal(key, (1, 2, n, dk), jnp.bfloat16)
            for key in ks[:2])
    v, w = (jax.random.normal(key, (1, 2, n, dv), jnp.bfloat16)
            for key in ks[2:])
    scale = dk ** -0.5

    def run(core):
        def loss(q, k, v):
            o = core(q, k, v)
            return jnp.sum(o.astype(jnp.float32) * w.astype(jnp.float32)), o
        return jax.grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)

    want, o_want = run(lambda q, k, v: attend(q, k, v, causal=True,
                                               scale=scale))
    got, o_got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=True, scale=scale, block_q=128, block_k=128))
    assert o_got.dtype == jnp.bfloat16 and o_got.shape == (1, 2, n, dv)
    for name, a, b in zip(("o", "dq", "dk", "dv"), (o_got, *got),
                          (o_want, *want)):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape, name
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        np.testing.assert_allclose(a, b, err_msg=name, rtol=2e-2, atol=2e-2)
        # and as a whole: two roundings to bfloat16 apart, no more
        assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b), name


def test_not_causal_with_a_padded_tail_matches_dense():
    """Without the causal mask the padded tail's column of blocks is the
    only one a mask cuts."""
    q, k, v = _qkv(150, seed=11)
    assert flash_block_counts(150, 32, 32, causal=False) == {
        "visited": 25, "full": 20, "total": 25}
    out = flash_attention(q, k, v, causal=False, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(attend(q, k, v, causal=False)),
                               rtol=2e-5, atol=2e-5)


def _dots(jaxpr, inside=None, found=None):
    """Every dot_general under ``jaxpr``, by the Pallas kernel it is in."""
    found = {} if found is None else found
    for eqn in jaxpr.eqns:
        at = inside
        if eqn.primitive.name == "pallas_call":
            at = eqn.params["name"]
        if eqn.primitive.name == "dot_general" and at is not None:
            found.setdefault(at, []).append(
                (*(x.aval.dtype for x in eqn.invars), eqn.outvars[0].aval.dtype))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _dots(sub, at, found)
    return found


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
def test_every_product_has_the_inputs_type_and_a_float32_sum(dtype):
    """Traced with bfloat16 inputs no product inside the three kernels has a
    float32 operand, and every one sums in float32; the type is read from
    the input (float32 inputs: float32 operands), there is no flag."""
    q, k, v = _qkv(64, dtype=dtype)
    traced = jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(flash_attention(
            *a, causal=True, block_q=32, block_k=32).astype(jnp.float32)),
        argnums=(0, 1, 2)))(q, k, v)
    dots = _dots(traced.jaxpr)
    assert set(dots) == {"flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"}
    # one loop of 2, 3 and 4 products; the forward's first scores ahead of
    # its loop and its last block's second product after it
    assert {name: len(d) for name, d in dots.items()} == {
        "flash_attn_fwd": 4, "flash_attn_dq": 3, "flash_attn_dkv": 4}
    for name, kernel in dots.items():
        for lhs, rhs, out in kernel:
            assert (lhs, rhs, out) == (dtype, dtype, jnp.float32), name


def test_block_counts_of_the_cells():
    """Causal 4352 positions at blocks of 256 (what ``_auto_block`` takes
    there): 17 x 17 blocks, 153 visited, all but the diagonal's 17 full."""
    want = {"visited": 153, "full": 136, "total": 289}
    assert flash_block_counts(4352, 256, 256) == want
    assert flash_block_counts(4352) == want               # the auto sizes
    # the count is of the lists the kernels are handed
    lists = build_block_lists(4352, 256, 256)
    assert list(lists.k_cnt) == list(range(1, 18))
    assert list(lists.q_cnt) == list(range(17, 0, -1))
    np.testing.assert_array_equal(lists.k_ids[5][:6], [0, 1, 2, 3, 4, 5])
    np.testing.assert_array_equal(lists.q_ids[5][:12], range(5, 17))
    assert sparsity_fraction(4352, 256, 256) == 153 / 289


def test_a_block_a_mask_reaches_is_never_full():
    """Full means every entry visible: the padded tail's key blocks, a ragged
    static mask's blocks and a block that holds one fully masked row are
    visited and not full."""
    # a padded tail: 300 positions at 128 are 3 x 3 blocks, the last key
    # block holds 84 padded keys, so every block of its column is cut
    assert flash_block_counts(300, 128, 128, causal=False) == {
        "visited": 9, "full": 6, "total": 9}
    assert flash_block_counts(384, 128, 128, causal=False) == {
        "visited": 9, "full": 9, "total": 9}
    tail = build_block_lists(384, 128, 128, causal=False, n_valid=300)
    assert list(tail.k_cnt) == [3, 3, 3] and list(tail.q_cnt) == [3, 3, 3]
    np.testing.assert_array_equal(tail.k_ids, [[0, 1, 2]] * 3)
    # a key block of padding alone is not listed at all
    wide = build_block_lists(512, 256, 128, causal=False, n_valid=300)
    assert list(wide.k_cnt) == [3, 3] and list(wide.q_cnt) == [2, 2, 2, 0]
    # under the causal mask that column is the diagonal's block alone
    assert flash_block_counts(300, 128, 128) == {
        "visited": 6, "full": 3, "total": 9}
    # a ragged static mask, against the count taken entry by entry
    text_len, fmap = 17, 8
    n = text_len + fmap * fmap
    for attn_type in ("axial_row", "conv_like", "sparse"):
        mask = build_mask(attn_type, text_len, fmap, kernel_size=3, block=32,
                          num_random_blocks=1)
        vis = np.zeros((96, 96), bool)
        vis[:n, :n] = mask[:n, :n] & np.tril(np.ones((n, n), bool))
        tiles = vis.reshape(3, 32, 3, 32)
        counts = flash_block_counts(n, 32, 32, mask=mask)
        assert counts == {"visited": int(tiles.any(axis=(1, 3)).sum()),
                          "full": int(tiles.all(axis=(1, 3)).sum()),
                          "total": 9}, attn_type
        assert counts["full"] < counts["visited"]
    # test_fully_masked_row_inside_visible_block's mask: row 10 sees nothing
    mask = np.tril(np.ones((64, 64), dtype=bool))
    mask[10, :] = False
    assert flash_block_counts(64, 32, 32, mask=mask) == {
        "visited": 3, "full": 1, "total": 4}  # (1, 0) full; row 10 cuts (0, 0)


@pytest.mark.parametrize("attn_type,spec", [
    ("axial_row", ("axial", 17, 8, 0)), ("axial_col", ("axial", 17, 8, 1)),
    ("conv_like", ("conv", 17, 8, 3, 1))])
def test_structured_spec_without_its_table_is_exact(attn_type, spec):
    """``mask_spec`` alone: the lists are plain causal ones and the element
    test runs in every visited block, so the answer is the tabled mask's
    (forward and gradients); the host-side count calls no block full, since
    no table says which blocks the test leaves whole."""
    text_len, fmap = 17, 8
    n = text_len + fmap * fmap
    mask = build_mask(attn_type, text_len, fmap, kernel_size=3)[:n, :n]
    q, k, v = _qkv(n, seed=5)

    def loss(core):
        return lambda q, k, v: jnp.sum(core(q, k, v) ** 2)

    def flash(q, k, v):
        return flash_attention(q, k, v, causal=True, mask_spec=spec,
                               block_q=32, block_k=32)

    def dense(q, k, v):
        return attend(q, k, v, causal=True, static_mask=jnp.asarray(mask))

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=2e-5, atol=2e-5)
    for a, b in zip(jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v),
                    jax.grad(loss(dense), argnums=(0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)
    assert flash_block_counts(n, 32, 32, mask_spec=spec) == {
        "visited": 6, "full": 0, "total": 9}
    assert flash_block_counts(n, 32, 32, mask=mask, mask_spec=spec)["full"] \
        < flash_block_counts(n, 32, 32)["full"] == 3


@pytest.mark.parametrize("how", ["table", "table_and_spec"])
def test_a_query_block_with_no_listed_key_block(how):
    """A static mask that hides every key from a whole block of queries: that
    row of the lists is empty (count 0), the forward's one step outside its
    loop walks block 0 with nothing visible, the rows come out 0 and the
    other rows and their gradients are dense attention's."""
    text_len, fmap = 32, 8
    n = text_len + fmap * fmap                                    # 96
    mask = np.asarray(axial_mask(text_len, fmap, axis=0))[:n, :n].copy()
    mask[32:64, :] = False
    kw = dict(mask=mask, causal=True, block_q=32, block_k=32)
    if how == "table_and_spec":
        kw["mask_spec"] = ("axial", text_len, fmap, 0)
        # the element test alone would let these rows see the text: what is
        # listed decides, as for every block the lists skip
    assert list(build_block_lists(96, 32, 32, mask).k_cnt) == [1, 0, 2]
    q, k, v = _qkv(n, seed=9)
    out = flash_attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(out[:, :, 32:64]), 0.0, atol=1e-6)
    keep = np.r_[0:32, 64:96]

    def loss(core):
        return lambda q, k, v: jnp.sum(jnp.sin(core(q, k, v)[:, :, keep]))

    def dense(q, k, v):
        return attend(q, k, v, causal=True, static_mask=jnp.asarray(mask))

    np.testing.assert_allclose(np.asarray(out[:, :, keep]),
                               np.asarray(dense(q, k, v)[:, :, keep]),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(lambda *a: flash_attention(*a, **kw)),
                   (0, 1, 2))(q, k, v)
    for a, b in zip(got, jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)
