"""The main path's Pallas kernels compile for the chip — asked of the TPU
compiler itself, against a *described* v5e (no chip attached, nothing runs).

Interpret mode (every other kernel test here) cannot see what Mosaic refuses:
a slice off the tiling, more scoped VMEM than a kernel may use. These cases
lower each kernel with ``interpret=False`` at the widths chip_smoke.py runs
and compile it for ``TPU v5 lite``; the compiled text must hold the Mosaic
custom call. A compile that passes is not a chip run — results and times
come from chip_smoke.py.

The topology is described inside a module-scoped fixture (never at import:
only one process may load the TPU library, and every xdist worker imports
every test file), compiles happen in the test's own process, and the
persistent compilation cache is off around them (an entry compiled for a
described device cannot be read back without one).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from dalle_tpu.ops.attention import KVCache
from dalle_tpu.ops.decode_attention import (decode_attend_kernel,
                                            decode_attend_window_kernel,
                                            decode_attend_window_paged)
from dalle_tpu.ops.flash_attention import flash_attention
from dalle_tpu.ops.fused_attention import fused_qkv_attention
from dalle_tpu.ops.grouped_matmul import grouped_matmul
from dalle_tpu.ops.paged_kv import PagedKVCache

# DALL·E-1.4B decode shapes as the serve engine builds them: 8 slots,
# 14 heads x 128, cache max_seq == total_seq_len == 512
B, H, D, S = 8, 14, 128, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot ask"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Sharding on the described chip; around the module's compiles the
    persistent cache is off and matmul precision is the chip default (the
    harness's float32 setting is a CPU-numerics choice)."""
    from jax.experimental.compilation_cache import compilation_cache
    cache_was = jax.config.jax_enable_compilation_cache
    precision_was = jax.config.jax_default_matmul_precision
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.config.update("jax_default_matmul_precision", None)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_default_matmul_precision", precision_was)
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def _mosaic_text(fn, *shapes) -> str:
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic custom call in the program"
    return text


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cache(sharding, dtype):
    scale = (_sds(sharding, (B, 2 * H, S), jnp.float32)
             if dtype == jnp.int8 else None)
    return KVCache(_sds(sharding, (B, S, 2 * H * D), dtype), scale, heads=H)


def test_fused_fwd_bwd_small(one_chip):
    # DALL·E-small train step's attention: b8 of the 64, n=513, 8 x 64
    def loss(qkv):
        return jnp.sum(fused_qkv_attention(qkv, heads=8, interpret=False)
                       .astype(jnp.float32))

    _mosaic_text(jax.grad(loss), _sds(one_chip, (8, 513, 3 * 512),
                                      jnp.bfloat16))


def test_fused_fwd_medium(one_chip):
    # DALL·E-medium width, 16 x 64 (its backward alone compiles ~26 s)
    _mosaic_text(lambda qkv: fused_qkv_attention(qkv, heads=16,
                                                 interpret=False),
                 _sds(one_chip, (4, 513, 3 * 1024), jnp.bfloat16))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_decode_kernel_1p4b(one_chip, dtype):
    _mosaic_text(
        lambda q, cache, n: decode_attend_kernel(q, cache, n,
                                                 interpret=False),
        _sds(one_chip, (B, H, 1, D), jnp.bfloat16), _cache(one_chip, dtype),
        _sds(one_chip, (), jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
@pytest.mark.parametrize("w", [1, 8])
def test_decode_window_kernel_1p4b(one_chip, dtype, w):
    _mosaic_text(
        lambda q, cache, starts: decode_attend_window_kernel(
            q, cache, starts, interpret=False),
        _sds(one_chip, (B, H, w, D), jnp.bfloat16), _cache(one_chip, dtype),
        _sds(one_chip, (B,), jnp.int32))


def test_decode_window_paged_1p4b(one_chip):
    # the paged engine's decode attend: page-table gather + the same kernel
    bt = 64
    blocks = B * S // bt
    cache = PagedKVCache(
        _sds(one_chip, (blocks, bt, 2 * H * D), jnp.int8),
        _sds(one_chip, (blocks, bt, 2 * H), jnp.float32),
        _sds(one_chip, (B, S // bt), jnp.int32),
        heads=H, block_tokens=bt, max_seq=S)
    _mosaic_text(
        lambda q, cache, starts: decode_attend_window_paged(
            q, cache, starts, interpret=False),
        _sds(one_chip, (B, H, 1, D), jnp.bfloat16), cache,
        _sds(one_chip, (B,), jnp.int32))


def _flash_loss(mask, mask_spec=None):
    def loss(q, k, v):
        o = flash_attention(q, k, v, causal=True, mask=mask,
                            mask_spec=mask_spec, interpret=False)
        return jnp.sum(o.astype(jnp.float32))
    return jax.grad(loss, argnums=(0, 1, 2))


def test_flash_fwd_bwd_long(one_chip):
    # above the "auto" crossover (seq >= 2048): fmap-48 image grid
    qkv = [_sds(one_chip, (1, 8, 2304, 64), jnp.bfloat16)] * 3
    _mosaic_text(_flash_loss(None), *qkv)


def test_flash_fwd_bwd_solar2_softmax_layer(one_chip):
    """train_solar2_ep32_fit's softmax layer as ``GatedGQAttention`` calls
    the flash tier: batch 2, the 8 key/value heads repeated to the 64 query
    heads of 128, 4352 positions (17 blocks of 256). The three kernels by the
    names the benchmark's rooflines read."""
    qkv = [_sds(one_chip, (2, 64, 4352, 128), jnp.bfloat16)] * 3
    text = _mosaic_text(_flash_loss(None), *qkv)
    for kernel in ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"):
        assert kernel in text, kernel


def test_flash_fwd_bwd_ling3_latent_layer(one_chip):
    """train_ling3_ep32_fit's latent layers as ``MLAttention`` calls the
    flash tier: batch 2, 32 heads, queries and keys of 192 (128 + the rotary
    64), values of 128, 4352 positions and the multi-token-prediction
    block's 4351 (padded to 17 blocks of 256 alike). 192 is no multiple of
    the 128 lanes: Mosaic takes it as the array's whole last dimension; the
    ``dkv`` kernel asks for the raised scoped-VMEM ceiling."""
    for n in (4352, 4351):
        q = _sds(one_chip, (2, 32, n, 192), jnp.bfloat16)
        v = _sds(one_chip, (2, 32, n, 128), jnp.bfloat16)
        text = _mosaic_text(_flash_loss(None), q, q, v)
        for kernel in ("flash_attn_fwd", "flash_attn_dq", "flash_attn_dkv"):
            assert kernel in text, kernel


@pytest.mark.parametrize("masked,dtype", [
    (None, jnp.bfloat16), ("table", jnp.bfloat16), ("spec", jnp.bfloat16),
    (None, jnp.float32)],
    ids=["mask_free", "axial_masked", "axial_spec", "mask_free_float32"])
def test_flash_fwd_bwd_512(one_chip, masked, dtype):
    """The full-causal mask-free variant, a block-sparse one with its mask as
    a table (the forward and dkv kernels read its transpose) and as an
    in-kernel element test, and float32 inputs (the operands' type is the
    inputs': the same bodies with float32 products)."""
    from dalle_tpu.ops.attn_masks import axial_mask
    n, fmap = 256 + 16 * 16, 16
    mask = (np.asarray(axial_mask(256, fmap, axis=0))[:n, :n]
            if masked else None)
    spec = ("axial", 256, fmap, 0) if masked == "spec" else None
    qkv = [_sds(one_chip, (2, 2, n, 64), dtype)] * 3
    _mosaic_text(_flash_loss(mask, spec), *qkv)


@pytest.mark.parametrize("k,n", [(5120, 1536), (1536, 5120)],
                         ids=["gate_up_5120x1536", "down_1536x5120"])
def test_grouped_product_fwd_bwd_dsv2_share(one_chip, k, n):
    """The routed experts' three kernels (moe_gmm_fwd, moe_gmm_dlhs,
    moe_gmm_drhs) at train_dsv2_share16_fit's shapes: the 15,360-row buffer
    of batch 8 x 1280 tokens (models/latent_moe.row_buffer_size), 10 held
    experts."""
    from dalle_tpu.models.latent_moe import row_buffer_size
    rows = row_buffer_size(8 * 1280, 6, 10, 160)

    def loss(lhs, rhs, sizes):
        out = grouped_matmul(lhs, rhs, sizes, use_kernel=True,
                             interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    text = _mosaic_text(jax.grad(loss, argnums=(0, 1)),
                        _sds(one_chip, (rows, k), jnp.bfloat16),
                        _sds(one_chip, (10, k, n), jnp.bfloat16),
                        _sds(one_chip, (10,), jnp.int32))
    for kernel in ("moe_gmm_fwd", "moe_gmm_dlhs", "moe_gmm_drhs"):
        # jax.grad of a sum needs no forward output: XLA may drop that call
        assert kernel in text or kernel == "moe_gmm_fwd", kernel


@pytest.mark.parametrize("rows,ids", [(8192, (8, 1024)), (4608, (8, 257))],
                         ids=["image_emb", "text_emb"])
def test_table_product_backward_dsv2_share(one_chip, rows, ids):
    """train_dsv2_share16_fit's two token tables (5120 wide, bfloat16): the
    backward is the product and no scatter, and the compiler folds the
    one-hot into the product's operand, so the program has no temporary
    (an ids x rows one-hot in memory would be 134 MB for the image table)."""
    from dalle_tpu.ops.table_lookup import grad_path, take_rows
    assert grad_path(rows, 5120, jnp.bfloat16) == "product"

    def d_table(table, i, g):
        return jax.vjp(lambda t: take_rows(t, i), table)[1](g)[0]

    compiled = jax.jit(d_table).lower(
        _sds(one_chip, (rows, 5120), jnp.bfloat16),
        _sds(one_chip, ids, jnp.int32),
        _sds(one_chip, ids + (5120,), jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert " scatter(" not in text and "convolution(" in text
    assert compiled.memory_analysis().temp_size_in_bytes < 2 ** 20
