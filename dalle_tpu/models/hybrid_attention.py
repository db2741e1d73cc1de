"""The linear-attention layers and the softmax layers without positions
that hybrid stacks put beside them, as block parts of the ``Transformer``
(config.BlockConfig ``attention_layers``): Kimi delta attention (``kda``;
Solar-Open2's and Ling-3.0's linear layers), Gated DeltaNet (``gdn``;
Olmo-Hybrid's), and grouped-query softmax attention with a sigmoid output
gate (``gqa_gated``; Solar-Open2's) or without one, its queries and keys
normed over the whole projection (``gqa``; Olmo-Hybrid's, OLMo 2's
QK-norm). No biases but the decay's, no positional term anywhere.

``KimiDeltaAttention`` (Kimi Linear, arXiv:2510.26692), per head of width
``d``, ``x`` the layer's normed input:

    q, k, v = SiLU(conv(W x))            causal depthwise, one filter a channel
    q <- q / |q| * d^-1/2,  k <- k / |k|
    g = -exp(A_h) softplus(W_f_up W_f_down x + b)      per key channel, float32
        (or, bounded: lower_bound * sigmoid(exp(A_h) (W_f x + b)))
    beta = beta_max sigmoid(W_beta x)                  per head, beta_max 2
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    y = W_o [RMSNorm_d(S_t^T q_t) * sigmoid(W_g_up W_g_down x)]

``GatedDeltaNet`` (arXiv:2412.06464, in the public flash-linear-attention
layer's form), per head of key width ``d_k`` and value width ``d_v``:

    q, k, v = SiLU(conv(W x))            causal depthwise, one filter a channel
    q <- q / |q| * d_k^-1/2,  k <- k / |k|
    g = -exp(A_h) softplus(W_a x + dt_bias_h)          one a head, float32
    beta = beta_max sigmoid(W_b x)                     per head, beta_max 2
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    y = W_o [RMSNorm_{d_v}(S_t^T q_t) * SiLU(W_g x)]

With ``beta_max`` 2 (``linear_allow_neg_eigval``) the eigenvalue ``1 -
beta`` of the transition lies in (-1, 1). Both recurrences are
``ops.kda.kda_chunked``, chosen by the decay's shape.

``GatedGQAttention``: ``heads`` query heads over ``kv_heads`` key and value
heads (query head ``i`` reads head ``i // (heads / kv_heads)``), causal
softmax of ``q k^T / sqrt(d)``, ``y = W_o [attn * sigmoid(W_gate x)]``, on
the tier ``attention_tier`` chose for the stack (the key and value heads are
repeated ahead of the tier's call; the sum over a group in the backward pass
is autodiff's). ``gate`` False drops the gate (``y = W_o attn``) and
``qk_norm`` puts a learned RMSNorm over the whole query and key projections
ahead of the split into heads.

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
from .latent_moe import RMSNorm, _dense, _seen, _to_length


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
    """The recurrence's inputs from the layer's input ``x`` (b, n, dim): q,
    k (not yet normalised) and v, each (b, n, h, width), the decay's
    pre-activation f ((b, n, h, d_k) for ``kda``, (b, n, h) for ``gdn``)
    and beta (b, n, h), under the kind's own scopes. Module first, so that
    ``nn.remat`` can lift it."""
    b, n, _ = x.shape
    with jax.named_scope(f"attn/{mdl.KIND}_proj"):
        q, k, v = mdl.w_q(x), mdl.w_k(x), mdl.w_v(x)
    with jax.named_scope(f"attn/{mdl.KIND}_conv"):
        q, k, v = (jax.nn.silu(causal_conv(t, w)).reshape(b, n, mdl.heads, -1)
                   for t, w in ((q, mdl.conv_q), (k, mdl.conv_k),
                                (v, mdl.conv_v)))
    with jax.named_scope(f"attn/{mdl.KIND}_gates"):
        f = mdl.decay_input(x)
        beta = mdl.beta_max * jax.nn.sigmoid(
            mdl.w_beta(x).astype(jnp.float32))
    return q, k, v, f, beta


class _DeltaRule(nn.Module):
    """What the two delta-rule layers share: the q, k and v projections and
    their filters, ``A_log``, the read-out norm and the output projection,
    the inputs' own remat, ``kda_chunked`` and the output. A layer names its
    ``KIND`` (the scopes' prefix) and gives its decay's input and bias, its
    output gate and its counters."""
    lower_bound = 0.0

    @nn.nowrap
    def _projections(self, key_width: int, value_width: int):
        h, bound = self.heads, self.conv_size ** -0.5

        def conv_filter(name, width):
            # uniform in +-K^-1/2, a depthwise Conv1d's default
            return self.param(
                name, lambda key, shape: jax.random.uniform(
                    key, shape, jnp.float32, -bound, bound),
                (self.conv_size, width))
        self.w_q, self.w_k = (_dense(h * key_width, n) for n in "qk")
        self.w_v = _dense(h * value_width, "v")
        self.conv_q, self.conv_k, self.conv_v = (
            conv_filter(f"conv_{n}", h * w)
            for n, w in zip("qkv", (key_width, key_width, value_width)))

    @nn.nowrap
    def _a_log(self, low: float):
        return self.param(
            "a_log", lambda key, shape: jnp.log(
                jax.random.uniform(key, shape, jnp.float32, low, 16.0)),
            (self.heads,))

    @nn.nowrap
    def _read_out(self, value_width: int):
        self.o_norm_scale = self.param("o_norm", nn.initializers.ones,
                                       (value_width,))
        self.o = _dense(self.dim, "o")

    def __call__(self, x, *, key_mask=None, rotary=None, np_mask=None,
                 mask_spec=None, deterministic: bool = True):
        if key_mask is not None or np_mask is not None:
            raise ValueError(
                f"{self.KIND}: a key mask or a static mask has no meaning for "
                f"a recurrent state (a masked position would still decay "
                f"it); it is causal by construction and pad ids are tokens")
        n = x.shape[1]
        x = _seen(self, x)       # init needs shapes, not 68 chunks run eagerly
        b, seen, _ = x.shape
        # rematerialised on its own: what the projections, convolutions and
        # gates hold for their backward pass is not alive beside the
        # recurrence's
        mix = (_mixed if self.is_initializing()
               else nn.remat(_mixed, prevent_cse=False))
        q, k, v, f, beta = mix(self, x)
        # the heads' own arithmetic (q and k over their norms, the decay
        # from f, the head norm of o) runs inside the core's loops
        o, logdecay_min = kda_chunked(
            q, k, v, f, beta, a_log=self.a_log, bias=self.core_bias(),
            norm_scale=self.o_norm_scale, eps=self.eps,
            lower_bound=self.lower_bound)
        with jax.named_scope("attn/out"):
            gate = self.output_gate(x)
            y = self.o(o.reshape(b, seen, -1) * gate)
        return _to_length(y, n), self.counters(logdecay_min, beta)


class KimiDeltaAttention(_DeltaRule):
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
    KIND = "kda"

    def setup(self):
        inner = self.heads * self.dim_head
        self._projections(self.dim_head, self.dim_head)
        if self.gate_rank:
            self.f_down = _dense(self.gate_rank, "f_down")
            self.g_down = _dense(self.gate_rank, "g_down")
        self.f_up = _dense(inner, "f_up")
        self.a_log = self._a_log(1.0)
        self.decay_bias = self.param("decay_bias", _decay_bias_init, (inner,))
        self.w_beta = _dense(self.heads, "beta")
        self.g_up = _dense(inner, "g_up")
        self._read_out(self.dim_head)

    @nn.nowrap
    def decay_input(self, x):
        b, n, _ = x.shape
        return self.f_up(self.f_down(x) if self.gate_rank else x).reshape(
            b, n, self.heads, -1)

    @nn.nowrap
    def core_bias(self):
        return self.decay_bias.reshape(self.heads, self.dim_head)

    @nn.nowrap
    def output_gate(self, x):
        return jax.nn.sigmoid(self.g_up(self.g_down(x) if self.gate_rank
                                        else x))

    @nn.nowrap
    def counters(self, logdecay_min, beta):
        return {"kda_logdecay_min": logdecay_min}


class GatedDeltaNet(_DeltaRule):
    """Returns (output, {"gdn_logdecay_min": ..., "gdn_beta_over_one_pct":
    ...}): the most negative cumulative log-decay over a chunk, and the
    share of (position, head) pairs whose beta passes 1, where the
    transition's eigenvalue ``1 - beta`` is negative."""
    dim: int
    heads: int
    key_dim: int
    value_dim: int
    conv_size: int = 4
    eps: float = 1e-6
    beta_max: float = 2.0
    KIND = "gdn"

    def setup(self):
        self._projections(self.key_dim, self.value_dim)
        self.w_a = _dense(self.heads, "a")
        # A = U(0, 16) and dt_bias as the public layer initialises them
        self.a_log = self._a_log(0.0)
        self.dt_bias = self.param("dt_bias", _decay_bias_init, (self.heads,))
        self.w_beta = _dense(self.heads, "b")
        self.w_g = _dense(self.heads * self.value_dim, "g")
        self._read_out(self.value_dim)

    @nn.nowrap
    def decay_input(self, x):
        return self.w_a(x)

    @nn.nowrap
    def core_bias(self):
        return self.dt_bias

    @nn.nowrap
    def output_gate(self, x):
        return jax.nn.silu(self.w_g(x))

    @nn.nowrap
    def counters(self, logdecay_min, beta):
        return {"gdn_logdecay_min": logdecay_min,
                "gdn_beta_over_one_pct": 100.0 * jnp.mean(
                    (beta > 1.0).astype(jnp.float32))}


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
    gate: bool = True
    qk_norm: bool = False
    eps: float = 1e-6

    def setup(self):
        if self.heads % self.kv_heads:
            raise ValueError(f"{self.heads} query heads do not divide over "
                             f"{self.kv_heads} key/value heads")
        self.w_q = _dense(self.heads * self.dim_head, "q")
        self.w_k = _dense(self.kv_heads * self.dim_head, "k")
        self.w_v = _dense(self.kv_heads * self.dim_head, "v")
        if self.qk_norm:
            self.q_norm = RMSNorm(self.eps, name="q_norm")
            self.k_norm = RMSNorm(self.eps, name="k_norm")
        if self.gate:
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
        same = lambda t: t
        q_norm, k_norm = ((self.q_norm, self.k_norm) if self.qk_norm
                          else (same, same))
        with jax.named_scope("attn/gqa_qkv"):
            q = q_norm(self.w_q(x)).reshape(b, seen, h, d).transpose(0, 2, 1, 3)
            k, v = (jnp.repeat(
                norm(w(x)).reshape(b, seen, kv, d).transpose(0, 2, 1, 3),
                h // kv, axis=1)
                for w, norm in ((self.w_k, k_norm), (self.w_v, same)))
        with jax.named_scope("attn_core"):
            if (self.tier == "flash" and key_mask is None
                    and not self.is_initializing()):
                from ..ops.flash_attention import flash_attention
                out = flash_attention(q, k, v, causal=True)
            else:
                out = attend(q, k, v, causal=True, key_mask=key_mask,
                             softmax_f32=self.softmax_f32)
        if self.gate:
            with jax.named_scope("attn/gate"):
                out = (out.transpose(0, 2, 1, 3).reshape(b, seen, h * d)
                       * jax.nn.sigmoid(self.w_gate(x)))
        else:
            out = out.transpose(0, 2, 1, 3).reshape(b, seen, h * d)
        with jax.named_scope("attn/out"):
            return _to_length(self.o(out), n)
