"""Solar-Open2's two attention layers (``model_type: solar_open2``) as block
parts of the ``Transformer`` (config.BlockConfig ``attention_layers``): three
linear-attention layers (``kda``) to one softmax layer (``gqa_gated``).
No biases but the decay's, no positional term anywhere.

``KimiDeltaAttention`` (Kimi Linear, arXiv:2510.26692), per head of width
``d``, ``x`` the layer's normed input:

    q, k, v = SiLU(conv(W x))            causal depthwise, one filter a channel
    q <- q / |q| * d^-1/2,  k <- k / |k|
    g = -exp(A_h) softplus(W_f_up W_f_down x + b)      per key channel, float32
        (or, bounded: lower_bound * sigmoid(exp(A_h) (W_f x + b)))
    beta = beta_max sigmoid(W_beta x)                  per head, beta_max 2
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    y = W_o [RMSNorm_d(S_t^T q_t) * sigmoid(W_g_up W_g_down x)]

The recurrence is ``ops.kda.kda_chunked``. ``GatedGQAttention``: ``heads``
query heads over ``kv_heads`` key and value heads (query head ``i`` reads
head ``i // (heads / kv_heads)``), causal softmax of ``q k^T / sqrt(d)``,
``y = W_o [attn * sigmoid(W_gate x)]``, on the tier ``attention_tier``
chose for the stack (the key and value heads are repeated ahead of the
tier's call; the sum over a group in the backward pass is autodiff's).

Training forward only: a recurrent state and a convolution tail have no
cache layout yet (``Transformer``'s cached paths refuse these kinds by
name).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attend
from ..ops.kda import kda_chunked
from .latent_moe import _dense, _seen, _to_length


def causal_conv(x, w):
    """Depthwise causal convolution over time: ``y_t = sum_i w[i] x_{t-K+1+i}``
    with zeros before the start; ``x`` (b, n, c), ``w`` (K, c). Summed in
    float32 (each tap cast after its slice: a cast of the padded whole
    would be a float32 copy of it)."""
    taps, n = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    y = sum(xp[:, i:i + n].astype(jnp.float32) * w[i].astype(jnp.float32)
            for i in range(taps))
    return y.astype(x.dtype)


def _decay_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step drawn log-uniformly from (1e-3, 1e-1), as the
    public implementation's ``dt_bias``."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _mixed(mdl, x):
    """The recurrence's inputs from the layer's normed input ``x`` (b, n,
    dim): q, k (not yet normalised), v and the decay's pre-activation f, each
    (b, n, h, d), and beta (b, n, h). Module first, so that ``nn.remat`` can
    lift it."""
    b, n, _ = x.shape
    shape = (b, n, mdl.heads, mdl.dim_head)
    with jax.named_scope("attn/kda_proj"):
        q, k, v = mdl.w_q(x), mdl.w_k(x), mdl.w_v(x)
    with jax.named_scope("attn/kda_conv"):
        q, k, v = (jax.nn.silu(causal_conv(t, w)).reshape(shape)
                   for t, w in ((q, mdl.conv_q), (k, mdl.conv_k),
                                (v, mdl.conv_v)))
    with jax.named_scope("attn/kda_gates"):
        f = mdl.f_up(mdl.f_down(x) if mdl.gate_rank else x).reshape(shape)
        beta = mdl.beta_max * jax.nn.sigmoid(
            mdl.w_beta(x).astype(jnp.float32))
    return q, k, v, f, beta


class KimiDeltaAttention(nn.Module):
    """Returns (output, {"kda_logdecay_min": ...}): the most negative
    cumulative log-decay over a chunk, which says how far the chunked form
    is from float32's range. ``gate_rank`` 0 (Ling's ``no_kda_lora``) makes
    ``W_f`` and ``W_g`` whole projections, leaves ``f_up`` and ``g_up``
    alone; ``lower_bound`` < 0 is the bounded decay (``ops.kda._log_decay``)
    and ``beta_max`` beta's range."""
    dim: int
    heads: int
    dim_head: int
    conv_size: int = 4
    gate_rank: int = 128
    eps: float = 1e-5
    lower_bound: float = 0.0
    beta_max: float = 2.0

    def setup(self):
        inner = self.heads * self.dim_head
        bound = self.conv_size ** -0.5

        def conv_filter(name):
            # uniform in +-K^-1/2, a depthwise Conv1d's default
            return self.param(
                name, lambda key, shape: jax.random.uniform(
                    key, shape, jnp.float32, -bound, bound),
                (self.conv_size, inner))
        self.w_q, self.w_k, self.w_v = (_dense(inner, n) for n in "qkv")
        self.conv_q, self.conv_k, self.conv_v = (
            conv_filter(f"conv_{n}") for n in "qkv")
        if self.gate_rank:
            self.f_down = _dense(self.gate_rank, "f_down")
            self.g_down = _dense(self.gate_rank, "g_down")
        self.f_up = _dense(inner, "f_up")
        self.a_log = self.param(
            "a_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0)),
            (self.heads,))
        self.decay_bias = self.param("decay_bias", _decay_bias_init, (inner,))
        self.w_beta = _dense(self.heads, "beta")
        self.g_up = _dense(inner, "g_up")
        self.o_norm_scale = self.param("o_norm", nn.initializers.ones,
                                       (self.dim_head,))
        self.o = _dense(self.dim, "o")

    def __call__(self, x, *, key_mask=None, rotary=None, np_mask=None,
                 mask_spec=None, deterministic: bool = True):
        if key_mask is not None:
            raise ValueError(
                "kda: a key mask has no meaning for a recurrent state (a "
                "masked position would still decay it); pad ids are tokens")
        if np_mask is not None:
            raise ValueError("kda is causal by construction, no static mask")
        n = x.shape[1]
        x = _seen(self, x)       # init needs shapes, not 68 chunks run eagerly
        b, seen, _ = x.shape
        h, d = self.heads, self.dim_head
        # rematerialised on its own: what the projections, convolutions and
        # gates hold for their backward pass is not alive beside the
        # recurrence's
        mix = (_mixed if self.is_initializing()
               else nn.remat(_mixed, prevent_cse=False))
        q, k, v, f, beta = mix(self, x)
        # the heads' own arithmetic (q and k over their norms, the decay
        # from f, the head norm of o) runs inside the core's loops
        o, logdecay_min = kda_chunked(
            q, k, v, f, beta, a_log=self.a_log,
            bias=self.decay_bias.reshape(h, d),
            norm_scale=self.o_norm_scale, eps=self.eps,
            lower_bound=self.lower_bound)
        with jax.named_scope("attn/out"):
            gate = jax.nn.sigmoid(
                self.g_up(self.g_down(x) if self.gate_rank else x))
            y = self.o(o.reshape(b, seen, h * d) * gate)
        return _to_length(y, n), {"kda_logdecay_min": logdecay_min}


class GatedGQAttention(nn.Module):
    dim: int
    heads: int
    kv_heads: int
    dim_head: int
    # what ops.attention.attention_tier chose for the stack: "flash" is the
    # flash kernels, anything else dense (the fused kernel takes one merged
    # qkv of equal head counts)
    tier: str = "dense"
    softmax_f32: bool = True

    def setup(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads do not divide over "
                             f"{self.kv_heads} key/value heads")
        self.w_q = _dense(self.heads * self.dim_head, "q")
        self.w_k = _dense(self.kv_heads * self.dim_head, "k")
        self.w_v = _dense(self.kv_heads * self.dim_head, "v")
        self.w_gate = _dense(self.heads * self.dim_head, "gate")
        self.o = _dense(self.dim, "o")

    def __call__(self, x, *, key_mask=None, rotary=None, np_mask=None,
                 mask_spec=None, deterministic: bool = True):
        if np_mask is not None:
            raise ValueError("gqa_gated runs full causal attention, no "
                             "static mask")
        n = x.shape[1]
        x = _seen(self, x)       # init needs shapes, not 64 heads of scores
        b, seen, _ = x.shape
        h, kv, d = self.heads, self.kv_heads, self.dim_head
        with jax.named_scope("attn/gqa_qkv"):
            q = self.w_q(x).reshape(b, seen, h, d).transpose(0, 2, 1, 3)
            k, v = (jnp.repeat(
                w(x).reshape(b, seen, kv, d).transpose(0, 2, 1, 3), h // kv,
                axis=1) for w in (self.w_k, self.w_v))
        with jax.named_scope("attn_core"):
            if (self.tier == "flash" and key_mask is None
                    and not self.is_initializing()):
                from ..ops.flash_attention import flash_attention
                out = flash_attention(q, k, v, causal=True)
            else:
                out = attend(q, k, v, causal=True, key_mask=key_mask,
                             softmax_f32=self.softmax_f32)
        with jax.named_scope("attn/gate"):
            out = (out.transpose(0, 2, 1, 3).reshape(b, seen, h * d)
                   * jax.nn.sigmoid(self.w_gate(x)))
        with jax.named_scope("attn/out"):
            return _to_length(self.o(out), n)
