"""DeepSeek-V2's layer kinds (arXiv:2405.04434) as block parts of the
``Transformer`` (config.BlockConfig): multi-head latent attention, SwiGLU,
routed + shared experts, RMSNorm. No biases anywhere.

**A chip's share of a layer.** ``MLAttention`` is told how many heads it
holds of the model's (``heads_held`` of ``heads_total``) and computes those
heads and their rows of the output projection: a partial sum of the layer's
attention. ``MoEFeedForward`` is told which routed experts it holds
(``experts_held`` from ``first_expert``) of ``n_routed_experts``: it routes
every token over all experts, computes the choices that fall on held experts
and adds the shared experts; what absent experts would add is left out and
the partial result goes on. Where a deployment would exchange rows and sum
partial results across chips (two all-reduces a layer), one chip has nothing
to exchange with and nothing stands in for it. With every head and expert
held these are the uncut layers.

``MLAttention`` and the router also take Ling-3.0's forms (``model_type:
bailing_hybrid``): queries without a latent, a learned RMSNorm on every
head's query and key, a head-wise sigmoid gate on the output, the flash tier
for its two head widths; ``noaux_tc`` routing (DeepSeek-V3,
arXiv:2412.19437): a per-expert bias that selects and never weighs, groups
scored by their two best.

Training forward only: latent keys and values have no cache layout yet
(``Transformer``'s cached paths refuse these kinds by name).
"""

from __future__ import annotations

import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops.attention import attend
from ..ops.grouped_matmul import (combine_rows, gather_rows,
                                  grouped_matmul)
from ..ops.kda import CHUNK
from ..ops.rotary import apply_rotary

# init runs un-jitted and its values are thrown away: the attention cores
# see this many leading positions there (every parameter's shape is the same)
INIT_POSITIONS = CHUNK


def _seen(mdl, x):
    """``x`` (b, n, dim) as a layer's core sees it: whole, or its leading
    ``INIT_POSITIONS`` while the module initialises."""
    return x[:, :INIT_POSITIONS] if mdl.is_initializing() else x


def _to_length(y, n: int):
    """Zeros behind what ``_seen`` kept, back to ``n`` positions."""
    return jnp.pad(y, ((0, 0), (0, n - y.shape[1]), (0, 0)))


class RMSNorm(nn.Module):
    """x * rsqrt(mean(x^2) + eps) * scale, statistics in float32."""
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        # norm statistics are float32 whatever the compute type, as
        # nn.LayerNorm's are
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True)
                                + self.eps)
        return (y * scale).astype(x.dtype)


def _dense(features: int, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, name=name)


class SwiGLUFeedForward(nn.Module):
    """w_down(silu(w_gate x) * w_up x)."""
    dim: int
    inner: int

    def setup(self):
        self.w_gate = _dense(self.inner, "w_gate")
        self.w_up = _dense(self.inner, "w_up")
        self.w_down = _dense(self.dim, "w_down")

    def __call__(self, x, deterministic: bool = True):
        return self.w_down(jax.nn.silu(self.w_gate(x)) * self.w_up(x))


class MLAttention(nn.Module):
    """Multi-head latent attention over the held heads. Queries come through
    a ``q_lora_rank`` latent (0: one direct projection, ``q``); keys and
    values through a ``kv_lora_rank`` latent that is normed and expanded per
    head, plus one rotary key part (``qk_rope_head_dim``) computed once and
    shared by all heads. A head's query and key are ``qk_nope_head_dim +
    qk_rope_head_dim`` wide, its value ``v_head_dim``: ``attend`` and the
    flash kernels take the two widths as they come. ``qk_norm``: a learned
    RMSNorm of a head's whole width on every query and key, ahead of the
    rotation (``q_head_norm``, ``k_head_norm``). ``gate`` ``head_wise``:
    each head's output times a sigmoid of its own projection of the layer's
    input, one number a head. ``tier`` is what
    ``ops.attention.attention_tier`` chose for the stack's softmax layers:
    ``flash`` is the flash kernels, anything else dense (the fused kernel
    takes one merged qkv of one head width)."""
    dim: int
    heads_held: int
    heads_total: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    softmax_scale: float
    eps: float = 1e-6
    softmax_f32: bool = True
    qk_norm: bool = False
    gate: str = "none"             # none | head_wise
    tier: str = "dense"

    def setup(self):
        h = self.heads_held
        if not 0 < h <= self.heads_total:
            raise ValueError(f"heads_held {h} of {self.heads_total} heads")
        qk = self.qk_nope_head_dim + self.qk_rope_head_dim
        if self.q_lora_rank:
            self.q_a = _dense(self.q_lora_rank, "q_a")
            self.q_norm = RMSNorm(self.eps, name="q_norm")
            self.q_b = _dense(h * qk, "q_b")
        else:
            self.q = _dense(h * qk, "q")
        self.kv_a = _dense(self.kv_lora_rank + self.qk_rope_head_dim, "kv_a")
        self.kv_norm = RMSNorm(self.eps, name="kv_norm")
        self.kv_b = _dense(h * (self.qk_nope_head_dim + self.v_head_dim),
                           "kv_b")
        if self.qk_norm:
            self.q_head_norm = RMSNorm(self.eps, name="q_head_norm")
            self.k_head_norm = RMSNorm(self.eps, name="k_head_norm")
        if self.gate == "head_wise":
            self.w_gate = _dense(h, "gate")
        # the held heads' rows of the whole projection
        self.o = _dense(self.dim, "o")

    def _rotated(self, rot, t):
        """``t`` (b, h, n, qk) with its last ``qk_rope_head_dim`` turned by
        the angles ``rot`` (n, qk_rope_head_dim)."""
        dn = self.qk_nope_head_dim
        return jnp.concatenate(
            [t[..., :dn], apply_rotary(rot[None, None], t[..., dn:])],
            axis=-1)

    def __call__(self, x, *, key_mask=None, rotary=None, np_mask=None,
                 mask_spec=None, deterministic: bool = True):
        if np_mask is not None:
            raise ValueError("mla runs full causal attention, no static mask")
        length = x.shape[1]
        x = _seen(self, x)       # init needs shapes, not every head's scores
        b, n, _ = x.shape
        h, dn, dv = self.heads_held, self.qk_nope_head_dim, self.v_head_dim
        rot = rotary[:n]
        with jax.named_scope("attn/mla_q"):
            q = (self.q_b(self.q_norm(self.q_a(x))) if self.q_lora_rank
                 else self.q(x))
            q = q.reshape(b, n, h, -1).transpose(0, 2, 1, 3)
            if not self.qk_norm:
                q = self._rotated(rot, q)
        with jax.named_scope("attn/mla_kv"):
            kv = self.kv_a(x)
            # (b, n, dr): turned here, or with the whole key behind its norm
            k_rope = (kv[..., self.kv_lora_rank:] if self.qk_norm
                      else apply_rotary(rot[None],
                                        kv[..., self.kv_lora_rank:]))
            kv = self.kv_b(self.kv_norm(kv[..., :self.kv_lora_rank]))
            kv = kv.reshape(b, n, h, dn + dv).transpose(0, 2, 1, 3)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_rope[:, None], (b, h) + k_rope.shape[1:])],
                axis=-1)
            v = kv[..., dn:]
        if self.qk_norm:
            with jax.named_scope("attn/mla_norm"):
                q = self._rotated(rot, self.q_head_norm(q))
                k = self._rotated(rot, self.k_head_norm(k))
        with jax.named_scope("attn_core"):
            if (self.tier == "flash" and key_mask is None
                    and not self.is_initializing()):
                from ..ops.flash_attention import flash_attention
                out = flash_attention(q, k, v, causal=True,
                                      scale=self.softmax_scale)
            else:
                out = attend(q, k, v, causal=True, key_mask=key_mask,
                             softmax_f32=self.softmax_f32,
                             scale=self.softmax_scale)
        if self.gate == "head_wise":
            with jax.named_scope("attn/gate"):
                gate = jax.nn.sigmoid(self.w_gate(x))           # (b, n, h)
                out = out * gate.transpose(0, 2, 1)[..., None]
        with jax.named_scope("attn/out"):
            out = self.o(out.transpose(0, 2, 1, 3).reshape(b, n, h * dv))
            return _to_length(out, length) if n < length else out


def group_limited_top_k(scores, n_group: int, topk_group: int, top_k: int,
                        *, group_score: str = "best", bias=None):
    """Group-limited routing: the experts are ``n_group`` groups in index
    order; only the ``topk_group`` best groups stay eligible, and the
    ``top_k`` best of their experts are taken. Returns (weights, indices),
    the weights being the scores themselves (nothing is renormalised).

    ``group_score`` is a group's score: ``best``, its best expert's
    (DeepSeek-V2's ``group_limited_greedy``), or ``best2``, the sum of its
    two best (DeepSeek-V3's ``noaux_tc``). A ``bias`` (one number an expert)
    is added to the scores that select, groups and experts, and never to
    the weights, which stay the chosen experts' own scores."""
    t, e = scores.shape
    select = scores if bias is None else scores + bias
    grouped = select.reshape(t, n_group, e // n_group)
    if group_score == "best":
        best = grouped.max(-1)
    elif group_score == "best2":
        best = jax.lax.top_k(grouped, 2)[0].sum(-1)
    else:
        raise ValueError(f"group_score {group_score!r}: best | best2")
    _, groups = jax.lax.top_k(best, topk_group)
    allowed = jnp.any(jax.nn.one_hot(groups, n_group, dtype=bool), axis=-2)
    allowed = jnp.repeat(allowed, e // n_group, axis=-1)
    if bias is None:
        return jax.lax.top_k(jnp.where(allowed, scores, 0.0), top_k)
    # a biased score may be negative: outside the kept groups nothing is
    _, idx = jax.lax.top_k(jnp.where(allowed, select, -jnp.inf), top_k)
    return jnp.take_along_axis(scores, idx, axis=-1), idx


# rows of the sorted-by-expert buffer over what uniform routing sends to the
# held experts. The worst case (every choice of every token held here) is 16 x
# uniform for a 1/16 share and does not fit a chip; seeded routers send
# 0.9-1.1 x. A step that needs more drops rows, counts them
# (``moe_rows_dropped``) and DalleTrainer stops on the count.
ROW_BUFFER = 4


def row_buffer_size(tokens: int, top_k: int, experts_held: int,
                    n_routed_experts: int) -> int:
    """Rows of the sorted-by-expert buffer: ``ROW_BUFFER`` times what uniform
    routing sends to the held experts, at most the worst case (every choice
    of every token held), a whole number of the kernel's row tiles."""
    worst = tokens * min(top_k, experts_held)
    rows = min(worst, math.ceil(
        ROW_BUFFER * tokens * top_k * experts_held / n_routed_experts))
    tile = 256 if rows >= 256 else 8
    return -(-rows // tile) * tile


class MoEFeedForward(nn.Module):
    """Routed experts (the held ones) + shared experts. Returns (output,
    counters): ``moe_rows_held`` (token-expert pairs computed here),
    ``moe_load_max_over_mean`` (largest held group over the mean held group)
    and ``moe_rows_dropped`` (pairs routed to a held expert that the row
    buffer had no room for: 0 unless the held experts draw more than
    ``ROW_BUFFER`` times their uniform share).

    The held experts' weights are three stacked leaves, ``e_gate`` and
    ``e_up`` (held, dim, inner) and ``e_down`` (held, inner, dim). Rows
    routed to absent experts never enter the grouped product."""
    dim: int
    inner: int
    experts_held: int
    n_routed_experts: int
    n_group: int
    topk_group: int
    top_k: int
    routed_scale: float
    n_shared: int
    first_expert: int = 0
    scoring: str = "softmax"       # softmax | sigmoid
    norm_topk: bool = False        # a token's weights renormalised to sum 1
    # group_limited_greedy | noaux_tc (a bias that selects, groups by their
    # two best: ``group_limited_top_k``)
    topk_method: str = "group_limited_greedy"

    def setup(self):
        e, d, f = self.experts_held, self.dim, self.inner
        if not (0 < e and self.first_expert + e <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.first_expert}..{self.first_expert + e - 1} "
                f"held of {self.n_routed_experts} routed experts")
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=1, out_axis=2, batch_axis=0)
        self.router = self.param("router", nn.initializers.lecun_normal(),
                                 (d, self.n_routed_experts))
        # no gradient reaches the bias (``route``); how a training run moves
        # it towards balance is that run's business, not the layer's
        self.router_bias = (
            self.param("router_bias", nn.initializers.zeros,
                       (self.n_routed_experts,))
            if self.topk_method == "noaux_tc" else None)
        self.e_gate = self.param("e_gate", init, (e, d, f))
        self.e_up = self.param("e_up", init, (e, d, f))
        self.e_down = self.param("e_down", init, (e, f, d))
        self.shared = (SwiGLUFeedForward(d, f * self.n_shared, name="shared")
                       if self.n_shared else None)

    def route(self, rows):
        """(weights (t, top_k) float32, expert indices (t, top_k)): the
        router's product and scores (a softmax over the experts, or a
        sigmoid each) run in float32, as the source's gate does, whatever
        the compute type. With ``norm_topk`` a token's weights are divided
        by their sum over its ``top_k`` choices, held here or not. Under
        ``noaux_tc`` the bias helps choose and the weights are the unbiased
        scores of the chosen."""
        logits = jnp.einsum(
            "td,de->te", rows.astype(jnp.float32),
            self.router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        bias = (None if self.router_bias is None else jax.lax.stop_gradient(
            self.router_bias.astype(jnp.float32)))
        weights, idx = group_limited_top_k(
            scores, self.n_group, self.topk_group, self.top_k,
            group_score="best" if bias is None else "best2", bias=bias)
        if self.norm_topk:
            weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
        return weights * self.routed_scale, idx

    def __call__(self, x, deterministic: bool = True):
        b, n, d = x.shape
        e, k = self.experts_held, self.top_k
        rows = x.reshape(b * n, d)
        with jax.named_scope("moe/router"):
            weights, idx = self.route(rows)
        with jax.named_scope("moe/dispatch"):
            # sort the (token, choice) pairs by held expert; pairs that went
            # to absent experts sort behind them and are cut off
            local = idx - self.first_expert
            key = jnp.where((local >= 0) & (local < e), local, e).reshape(-1)
            size = row_buffer_size(b * n, k, e, self.n_routed_experts)
            pairs = b * n * k
            order = jnp.argsort(key, stable=True)[:size]
            if size > pairs:      # a tile's rounding past the pairs there are
                order = jnp.pad(order, (0, size - pairs))
            routed = jnp.sum(key[:, None] == jnp.arange(e)[None, :], axis=0,
                             dtype=jnp.int32)
            ends = jnp.minimum(jnp.cumsum(routed), size)
            group_sizes = jnp.diff(ends, prepend=0)
            kept = ends[-1]
            token = order // k
            row_weight = weights.reshape(-1)[order]
            x_rows = gather_rows(rows, token, kept)
        with jax.named_scope("moe/experts"):
            # (init runs un-jitted: the kernels would run eagerly there)
            gmm = functools.partial(
                grouped_matmul, group_sizes=group_sizes,
                use_kernel=False if self.is_initializing() else None)
            gate, up = gmm(x_rows, self.e_gate), gmm(x_rows, self.e_up)
            out_rows = gmm(jax.nn.silu(gate) * up, self.e_down)
        with jax.named_scope("moe/combine"):
            out = combine_rows(out_rows, row_weight, token, kept,
                               b * n).astype(x.dtype)
        if self.shared is not None:
            with jax.named_scope("moe/shared"):
                out = out + self.shared(rows)
        held = group_sizes.astype(weights.dtype)
        counters = {
            "moe_rows_held": kept.astype(weights.dtype),
            "moe_load_max_over_mean": jnp.max(held) / jnp.maximum(
                jnp.mean(held), 1e-9),
            "moe_rows_dropped": (jnp.sum(routed) - kept).astype(weights.dtype)}
        return out.reshape(b, n, d), counters
