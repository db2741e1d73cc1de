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
from ..ops.rotary import apply_rotary


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
    a ``q_lora_rank`` latent; keys and values through a ``kv_lora_rank``
    latent that is normed and expanded per head, plus one rotary key part
    (``qk_rope_head_dim``) computed once and shared by all heads. A head's
    query and key are ``qk_nope_head_dim + qk_rope_head_dim`` wide, its value
    ``v_head_dim``: ``attend`` takes the two widths as they come. Always the
    dense tier: the fused and flash kernels assume one head width, so
    ``Transformer.setup`` builds this layer without asking for a tier."""
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

    def setup(self):
        h = self.heads_held
        if not 0 < h <= self.heads_total:
            raise ValueError(f"heads_held {h} of {self.heads_total} heads")
        self.q_a = _dense(self.q_lora_rank, "q_a")
        self.q_norm = RMSNorm(self.eps, name="q_norm")
        self.q_b = _dense(h * (self.qk_nope_head_dim + self.qk_rope_head_dim),
                          "q_b")
        self.kv_a = _dense(self.kv_lora_rank + self.qk_rope_head_dim, "kv_a")
        self.kv_norm = RMSNorm(self.eps, name="kv_norm")
        self.kv_b = _dense(h * (self.qk_nope_head_dim + self.v_head_dim),
                           "kv_b")
        # the held heads' rows of the whole projection
        self.o = _dense(self.dim, "o")

    def __call__(self, x, *, key_mask=None, rotary=None, np_mask=None,
                 mask_spec=None, deterministic: bool = True):
        if np_mask is not None:
            raise ValueError("mla runs full causal attention, no static mask")
        b, n, _ = x.shape
        h, dn, dv = self.heads_held, self.qk_nope_head_dim, self.v_head_dim
        rot = rotary[:n]
        with jax.named_scope("attn/mla_q"):
            q = self.q_b(self.q_norm(self.q_a(x)))
            q = q.reshape(b, n, h, -1).transpose(0, 2, 1, 3)
            q = jnp.concatenate(
                [q[..., :dn],
                 apply_rotary(rot[None, None], q[..., dn:])],
                axis=-1)
        with jax.named_scope("attn/mla_kv"):
            kv = self.kv_a(x)
            k_rope = apply_rotary(rot[None],
                                  kv[..., self.kv_lora_rank:])  # (b, n, dr)
            kv = self.kv_b(self.kv_norm(kv[..., :self.kv_lora_rank]))
            kv = kv.reshape(b, n, h, dn + dv).transpose(0, 2, 1, 3)
            k = jnp.concatenate(
                [kv[..., :dn],
                 jnp.broadcast_to(k_rope[:, None], (b, h) + k_rope.shape[1:])],
                axis=-1)
            v = kv[..., dn:]
        with jax.named_scope("attn_core"):
            out = attend(q, k, v, causal=True, key_mask=key_mask,
                         softmax_f32=self.softmax_f32,
                         scale=self.softmax_scale)
        with jax.named_scope("attn/out"):
            out = out.transpose(0, 2, 1, 3).reshape(b, n, h * dv)
            return self.o(out)


def group_limited_top_k(scores, n_group: int, topk_group: int, top_k: int):
    """DeepSeek-V2's ``group_limited_greedy``: the experts are ``n_group``
    groups in index order; a group's score is its best expert's; only the
    ``topk_group`` best groups stay eligible, and the ``top_k`` best of
    their experts are taken. Returns (weights, indices), the weights being
    the scores themselves (nothing is renormalised)."""
    t, e = scores.shape
    best = scores.reshape(t, n_group, e // n_group).max(-1)
    _, groups = jax.lax.top_k(best, topk_group)
    allowed = jnp.any(jax.nn.one_hot(groups, n_group, dtype=bool), axis=-2)
    allowed = jnp.repeat(allowed, e // n_group, axis=-1)
    return jax.lax.top_k(jnp.where(allowed, scores, 0.0), top_k)


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
        by their sum over its ``top_k`` choices, held here or not."""
        logits = jnp.einsum(
            "td,de->te", rows.astype(jnp.float32),
            self.router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.sigmoid(logits) if self.scoring == "sigmoid"
                  else jax.nn.softmax(logits, axis=-1))
        weights, idx = group_limited_top_k(scores, self.n_group,
                                           self.topk_group, self.top_k)
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
