"""Pallas decode-attention kernel ≡ the dense cached_attend path (interpret
mode on CPU; the Mosaic build is compiled for a described v5e in
tests/test_chip_compile.py and run on the chip by chip_smoke.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.ops.attention import KVCache, cached_attend
from dalle_tpu.ops.decode_attention import (decode_attend_kernel,
                                            decode_kernel_supported)


def _cache(rng, b, h, S, d, dtype):
    c = KVCache.init(b, h, S, d, dtype)
    k = jnp.asarray(rng.standard_normal((b, h, S, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, S, d)), jnp.float32)
    return c.append(k, v, 0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_kernel_matches_dense(dtype):
    rng = np.random.RandomState(0)
    b, h, S, d = 2, 4, 256, 64
    cache = _cache(rng, b, h, S, d, dtype)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    length = jnp.int32(135)
    dense = cached_attend(q, cache, length, use_kernel=False)
    kern = decode_attend_kernel(q, cache, length, interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)


def test_kernel_matches_dense_with_mask_row():
    rng = np.random.RandomState(1)
    b, h, S, d = 2, 2, 128, 64
    cache = _cache(rng, b, h, S, d, jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    mask = jnp.asarray(rng.rand(S, S) > 0.4)
    length, qpos = jnp.int32(90), jnp.int32(89)
    dense = cached_attend(q, cache, length, static_mask=mask, qpos=qpos,
                          use_kernel=False)
    row = jax.lax.dynamic_index_in_dim(mask, qpos, 0, keepdims=False)
    kern = decode_attend_kernel(q, cache, length, mask_row=row,
                                interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)


def test_cached_attend_kernel_flag_roundtrip():
    """use_kernel=True routes through the kernel (interpret on CPU) and
    agrees with the dense default."""
    rng = np.random.RandomState(2)
    cache = _cache(rng, 1, 2, 128, 64, jnp.int8)
    q = jnp.asarray(rng.standard_normal((1, 2, 1, 64)), jnp.float32)
    dense = cached_attend(q, cache, jnp.int32(70))
    kern = cached_attend(q, cache, jnp.int32(70), use_kernel=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)


def test_cache_roundtrip_layout():
    """Sequence-major storage presents the conventional (b,h,S,d) view and
    append/read_kv round-trips exactly (f32) / within quant noise (int8)."""
    rng = np.random.RandomState(3)
    b, h, S, d = 2, 3, 16, 8
    k = rng.standard_normal((b, h, S, d)).astype(np.float32)
    v = rng.standard_normal((b, h, S, d)).astype(np.float32)
    c = KVCache.init(b, h, S, d).append(jnp.asarray(k), jnp.asarray(v), 0)
    ck, cv = c.read_kv()
    np.testing.assert_array_equal(np.asarray(ck), k)
    np.testing.assert_array_equal(np.asarray(cv), v)
    c8 = KVCache.init(b, h, S, d, jnp.int8).append(
        jnp.asarray(k), jnp.asarray(v), 0)
    ck8, _ = c8.read_kv(dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(ck8), k, atol=0.02)


def test_supported_gate():
    q = jnp.zeros((1, 2, 1, 64))
    ok = KVCache.init(1, 2, 256, 64)
    assert decode_kernel_supported(q, ok, stable=False)
    assert not decode_kernel_supported(q, KVCache.init(1, 2, 200, 64),
                                       stable=False)   # S not lane-tiled
    assert not decode_kernel_supported(q, ok, stable=True)
    assert not decode_kernel_supported(jnp.zeros((1, 2, 2, 64)), ok,
                                       stable=False)   # multi-token q
    # h*d not lane-tiled
    assert not decode_kernel_supported(jnp.zeros((1, 2, 1, 16)),
                                       KVCache.init(1, 2, 256, 16),
                                       stable=False)
    # merged K+V block beyond the per-program VMEM budget: 14 x 128 heads at
    # S=2560 is 17.9MB and stays on the dense path
    assert not decode_kernel_supported(
        jnp.zeros((2, 14, 1, 128), jnp.bfloat16),
        KVCache.init(2, 14, 2560, 128, jnp.bfloat16), stable=False)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_window_kernel_matches_dense_ragged(dtype):
    """Windowed per-row-length kernel ≡ dense cached_attend_window across
    ragged starts — including a row at 0 (fresh refill prefill) and a row
    whose window overshoots the final cache slot (boundary clamp: positions
    beyond start are masked, never gathered)."""
    from dalle_tpu.ops.decode_attention import decode_attend_window_kernel
    from dalle_tpu.ops.attention import cached_attend_window
    rng = np.random.RandomState(0)
    b, h, S, d, w = 4, 4, 256, 64, 5
    cache = _cache(rng, b, h, S, d, dtype)
    q = jnp.asarray(rng.standard_normal((b, h, w, d)), jnp.float32)
    starts = jnp.asarray([0, 100, 197, S - 2], jnp.int32)
    dense = cached_attend_window(q, cache, starts, use_kernel=False)
    kern = decode_attend_window_kernel(q, cache, starts, interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)


def test_window_kernel_w1_matches_single_token():
    """w=1 degenerates to the single-token decode shape: both kernels and
    the dense path agree (starts = length-1 ↔ cached_attend's length)."""
    from dalle_tpu.ops.decode_attention import decode_attend_window_kernel
    rng = np.random.RandomState(1)
    b, h, S, d = 2, 2, 128, 64
    cache = _cache(rng, b, h, S, d, jnp.float32)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    length = jnp.int32(90)
    dense = cached_attend(q, cache, length, use_kernel=False)
    kern = decode_attend_window_kernel(
        q, cache, jnp.full((b,), length - 1, jnp.int32), interpret=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)


def test_cached_attend_window_kernel_flag_roundtrip():
    """use_kernel=True routes cached_attend_window through the windowed
    kernel (interpret on CPU) and agrees with the dense default; the
    auto-gate (use_kernel=None) stays dense off-TPU."""
    from dalle_tpu.ops.attention import cached_attend_window
    rng = np.random.RandomState(2)
    b, h, S, d, w = 2, 2, 128, 64, 3
    cache = _cache(rng, b, h, S, d, jnp.int8)
    q = jnp.asarray(rng.standard_normal((b, h, w, d)), jnp.float32)
    starts = jnp.asarray([5, 77], jnp.int32)
    dense = cached_attend_window(q, cache, starts)          # auto → dense
    kern = cached_attend_window(q, cache, starts, use_kernel=True)
    np.testing.assert_allclose(np.asarray(kern), np.asarray(dense),
                               rtol=2e-2, atol=2e-2)


def test_window_supported_gate():
    """Runtime-shape gate: lane-tiled shapes pass; untiled S / huge windows
    / stable softmax / VMEM-busting caches fall back to dense (a shape the
    gate rejects must never reach a failing Mosaic compile)."""
    from dalle_tpu.ops.decode_attention import decode_window_kernel_supported
    ok = KVCache.init(2, 2, 256, 64)
    q = jnp.zeros((2, 2, 5, 64))
    assert decode_window_kernel_supported(q, ok, stable=False)
    assert not decode_window_kernel_supported(q, ok, stable=True)
    assert not decode_window_kernel_supported(
        q, KVCache.init(2, 2, 200, 64), stable=False)   # S not lane-tiled
    assert not decode_window_kernel_supported(
        jnp.zeros((2, 2, 5, 16)), KVCache.init(2, 2, 256, 16),
        stable=False)                                   # h*d not lane-tiled
    assert not decode_window_kernel_supported(
        jnp.zeros((2, 2, 100, 64)), ok, stable=False)   # window too wide
    # merged K+V block beyond the per-program VMEM budget
    big = KVCache.init(2, 14, 2560, 128, jnp.bfloat16)
    assert not decode_window_kernel_supported(
        jnp.zeros((2, 14, 5, 128), jnp.bfloat16), big, stable=False)
