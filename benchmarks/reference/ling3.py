"""Plain reference for one chip's share of a Ling-3.0-flash stack trained as
the DALL-E transformer: forward, both losses, gradients, clipping and the
optimizer in straightforward jax.numpy and float32.

The layers are Ling-3.0-flash's as its ``config.json`` gives them
(``model_type: bailing_hybrid``), ``y`` a layer's normed input:

**Kimi-delta layers** (Kimi Linear, arXiv:2510.26692, in Ling's form: whole
gate projections, a decay bounded from below, beta in (0, 1)), one position
at a time exactly as the layer is defined (``reference/solar_open2.py``'s
``delta_rule``: per head ``S`` of (d_k, d_v), ``S_0 = 0``):

    q, k, v = SiLU(conv4(W y));  q <- q / |q| * d^-1/2,  k <- k / |k|
    g    = lower_bound * sigmoid(exp(A_h) (W_f y + b))      in (-5, 0)
    beta = sigmoid(W_beta y)
    S_t  = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    out  = W_o [RMSNorm_d(S_t^T q_t) * sigmoid(W_g y)]

**Latent-attention layers** with the whole score matrix a head:

    q = W_q y -> (h, 192);  c, k_r = split(W_kva y, [512, 64])
    [k_n, v] = W_kvb RMSNorm(c) -> (h, 128 + 128)
    q <- RMSNorm_192(q),  k <- RMSNorm_192([k_n ; k_r]),  then the last 64
    of each turned by plain rotary positions 0..n-1 (theta 6e6)
    o_h = softmax(q_h k_h^T / sqrt(192), causal) v_h
    out = W_o [o_h * sigmoid(W_gate y)_h]

**Feed-forward**: SwiGLU in the leading dense layers; elsewhere routed +
shared experts behind DeepSeek-V3's ``noaux_tc`` router (arXiv:2412.19437):
sigmoid scores ``s``; ``s' = s + bias`` selects (a group's score the sum of
its two largest ``s'``, the ``topk_group`` best groups kept, the
``num_experts_per_tok`` largest ``s'`` within them) and ``s`` weighs
(``s[idx] / sum(s[idx]) * routed_scaling_factor``). The expert layer's plain
form (every held expert applied to every row, times the row's weight for it)
is ``reference/deepseek_v2.py``'s.

**The multi-token-prediction block** (DeepSeek-V3 section 2.2), ``h_i`` the
last layer's output before the final norm, ``e_i`` the embedded input:

    h'_i = W_eh [RMSNorm(h_i) ; RMSNorm(e_{i+1})],  i = 0 .. n-2
    z    = one more latent-attention + routed layer over h'
    logits'_i = head(RMSNorm_mtp(z_i)), which predicts label_{i+1}
    loss = L_main + mtp_loss_weight * L_mtp

``L_mtp`` is DALL-E's text 1 : image 7 weighted cross-entropy over the n - 1
positions, each against the vocabulary of the position it predicts.

On the chip everything is computed in blocks so that float32 at 4352
positions fits (a layer's attention a batch row at a time, a linear layer a
quarter of its heads at a time, the scores one head at a time, the experts
a quarter of a row at a time); that changes no number. It imports nothing of
the program. ``precision`` is ``reference/dalle.py``'s: ``f32`` (the
reference proper), ``bf16`` or ``fp8`` (the control). The router, the decay
gates, their cumulative effect and the state stay float32 in every
precision, as the configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dalle import (LOSS_IMG_WEIGHT, _quantize,
                                        chunk_logits, clip_by_global_norm,
                                        product, seed_key, token_ids)
from benchmarks.reference.deepseek_v2 import (FLAT_OPTIMIZERS, by_batch_row,
                                              layer_params, rms_norm, rotate,
                                              swiglu)
from benchmarks.reference.solar_open2 import (causal_conv, delta_rule,
                                              l2_normalise)

_BLOCK_INTS = (
    "first_dense_layers", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "linear_num_heads", "linear_head_dim",
    "short_conv_kernel_size", "intermediate_size", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "n_group",
    "topk_group")
_BLOCK_FLOATS = ("kda_lower_bound", "kda_beta_max", "rope_theta",
                 "routed_scaling_factor", "rms_norm_eps")
# what this reference is written for: any other value is another model
_BLOCK_FORMS = {"scoring_func": "sigmoid", "norm_topk_prob": True,
                "topk_method": "noaux_tc", "positions": "seq_yarn",
                "q_lora_rank": 0, "qk_norm": True,
                "attention_gate": "head_wise", "linear_gate_rank": 0}
ROW_PARTS = 4       # parts of a batch row the experts see at a time
HEAD_PARTS = 4      # parts of a linear-attention layer's heads at a time
BIAS_SCALE = 0.01   # the router bias is seeded N(0, 0.01^2), not zero


class Shapes(NamedTuple):
    """The sizes of one configuration file, as the reference needs them."""
    num_text_tokens: int
    text_seq_len: int
    dim: int
    depth: int
    heads: int
    experts_held: int
    image_vocab_size: int
    image_fmap_size: int
    mtp_depth: int
    mtp_loss_weight: float
    attention_layers: Tuple[str, ...]
    first_dense_layers: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    linear_num_heads: int
    linear_head_dim: int
    short_conv_kernel_size: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    kda_lower_bound: float
    kda_beta_max: float
    rope_theta: float
    routed_scaling_factor: float
    rms_norm_eps: float
    first_expert: int = 0

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """``model``: the program's keyword arguments, the block's own sizes
        under ``block`` by the source's names. 0 experts held means all."""
        block = model["block"]
        for key, value in _BLOCK_FORMS.items():
            if block.get(key) != value:
                raise ValueError(f"this reference is Ling-3.0's forms: "
                                 f"block.{key} must be {value!r}, got "
                                 f"{block.get(key)!r}")
        if float(block.get("yarn_factor", 1.0)) != 1.0:
            raise ValueError("this reference turns by plain rotary positions")
        if block["kda_lower_bound"] >= 0:
            raise ValueError("this reference's decay is the bounded form")
        top = {k: int(model[k]) for k in (
            "num_text_tokens", "text_seq_len", "dim", "depth", "heads",
            "image_vocab_size", "image_fmap_size", "mtp_depth")}
        top["experts_held"] = int(model.get("experts_held")
                                  or block["n_routed_experts"])
        return cls(**top, mtp_loss_weight=float(model["mtp_loss_weight"]),
                   attention_layers=tuple(block["attention_layers"]),
                   **{k: int(block[k]) for k in _BLOCK_INTS},
                   **{k: float(block[k]) for k in _BLOCK_FLOATS})

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size ** 2

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def text_vocab(self) -> int:
        return self.num_text_tokens + self.text_seq_len

    @property
    def total_vocab(self) -> int:
        return self.text_vocab + self.image_vocab_size

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def kind(self, layer: int) -> str:
        """Layer ``depth`` is the multi-token-prediction block's."""
        if layer >= self.depth:
            return "mla"
        return self.attention_layers[layer % len(self.attention_layers)]

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_dense_layers


# --------------------------------------------------------------------------
# weights from the seed: a flat {leaf name: array}, "l_q.3" is layer 3's;
# the multi-token-prediction block's layer is number ``depth``
# --------------------------------------------------------------------------

def layer_leaf_specs(s: Shapes, kind: str, moe: bool) -> dict:
    """name -> (shape, how, value) of one layer's leaves, as
    ``reference/solar_open2.py``'s: ``normal`` N(0, value^2), ``const``,
    ``uniform`` U(-value, value), ``a_log`` log U(1, 16), ``decay_bias``
    softplus^-1 of a step drawn log-uniformly from (1e-3, 1e-1)."""
    d = s.dim
    if kind == "mla":
        h, qk, r = s.heads, s.qk_head_dim, s.kv_lora_rank
        specs = {"m_q": ((d, h * qk), "normal", d ** -0.5),
                 "m_kv_a": ((d, r + s.qk_rope_head_dim), "normal", d ** -0.5),
                 "m_kv_norm_g": ((r,), "const", 1.0),
                 "m_kv_b": ((r, h * (s.qk_nope_head_dim + s.v_head_dim)),
                            "normal", r ** -0.5),
                 "m_q_norm_g": ((qk,), "const", 1.0),
                 "m_k_norm_g": ((qk,), "const", 1.0),
                 "m_gate": ((d, h), "normal", d ** -0.5),
                 "m_o": ((h * s.v_head_dim, d), "normal",
                         (h * s.v_head_dim) ** -0.5)}
    elif kind == "kda":
        h, dh = s.linear_num_heads, s.linear_head_dim
        inner, taps = h * dh, s.short_conv_kernel_size
        specs = {"l_q": ((d, inner), "normal", d ** -0.5),
                 "l_k": ((d, inner), "normal", d ** -0.5),
                 "l_v": ((d, inner), "normal", d ** -0.5),
                 "conv_q": ((taps, inner), "uniform", taps ** -0.5),
                 "conv_k": ((taps, inner), "uniform", taps ** -0.5),
                 "conv_v": ((taps, inner), "uniform", taps ** -0.5),
                 "w_f": ((d, inner), "normal", d ** -0.5),
                 "a_log": ((h,), "a_log", None),
                 "decay_bias": ((inner,), "decay_bias", None),
                 "w_beta": ((d, h), "normal", d ** -0.5),
                 "w_g": ((d, inner), "normal", d ** -0.5),
                 "o_norm_g": ((dh,), "const", 1.0),
                 "l_o": ((inner, d), "normal", inner ** -0.5)}
    else:
        raise ValueError(f"no layer kind {kind!r} in this reference")
    specs.update({"attn_norm_g": ((d,), "const", 1.0),
                  "ff_norm_g": ((d,), "const", 1.0)})
    if not moe:
        f = s.intermediate_size
        specs.update({"w_gate": ((d, f), "normal", d ** -0.5),
                      "w_up": ((d, f), "normal", d ** -0.5),
                      "w_down": ((f, d), "normal", f ** -0.5)})
        return specs
    f, e = s.moe_intermediate_size, s.experts_held
    specs.update({"router": ((d, s.n_routed_experts), "normal", d ** -0.5),
                  "router_bias": ((s.n_routed_experts,), "normal",
                                  BIAS_SCALE),
                  "e_gate": ((e, d, f), "normal", d ** -0.5),
                  "e_up": ((e, d, f), "normal", d ** -0.5),
                  "e_down": ((e, f, d), "normal", f ** -0.5)})
    if s.n_shared_experts:
        fs = f * s.n_shared_experts
        specs.update({"s_gate": ((d, fs), "normal", d ** -0.5),
                      "s_up": ((d, fs), "normal", d ** -0.5),
                      "s_down": ((fs, d), "normal", fs ** -0.5)})
    return specs


def top_leaf_specs(s: Shapes) -> dict:
    d = s.dim
    specs = {"text_emb": ((s.text_vocab, d), "normal", d ** -0.5),
             "image_emb": ((s.image_vocab_size, d), "normal", d ** -0.5),
             "final_norm_g": ((d,), "const", 1.0),
             "w_logits": ((d, s.total_vocab), "normal", d ** -0.5),
             "b_logits": ((s.total_vocab,), "const", 0.0)}
    if s.mtp_depth:
        specs.update({"mtp_norm_h_g": ((d,), "const", 1.0),
                      "mtp_norm_e_g": ((d,), "const", 1.0),
                      "mtp_merge": ((2 * d, d), "normal", (2 * d) ** -0.5),
                      "mtp_final_norm_g": ((d,), "const", 1.0)})
    return specs


def leaf_specs(s: Shapes) -> dict:
    """Every leaf by its full name."""
    out = dict(top_leaf_specs(s))
    for l in range(s.depth + s.mtp_depth):
        moe = s.is_moe(l) or l >= s.depth
        for name, spec in layer_leaf_specs(s, s.kind(l), moe).items():
            out[f"{name}.{l}"] = spec
    return out


_NAMES = sorted({
    "text_emb", "image_emb", "final_norm_g", "w_logits", "b_logits",
    "mtp_norm_h_g", "mtp_norm_e_g", "mtp_merge", "mtp_final_norm_g",
    "attn_norm_g", "ff_norm_g", "w_gate", "w_up", "w_down", "router",
    "router_bias", "e_gate", "e_up", "e_down", "s_gate", "s_up", "s_down",
    "m_q", "m_kv_a", "m_kv_norm_g", "m_kv_b", "m_q_norm_g", "m_k_norm_g",
    "m_gate", "m_o", "l_q", "l_k", "l_v", "conv_q", "conv_k", "conv_v",
    "w_f", "a_log", "decay_bias", "w_beta", "w_g", "o_norm_g", "l_o"})
LEAF_IDS = {name: i for i, name in enumerate(_NAMES)}


def init_leaf(key, full_name: str, spec):
    shape, how, value = spec
    if how == "const":
        return jnp.full(shape, value, jnp.float32)
    name, _, layer = full_name.partition(".")
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]),
                           int(layer or 0))
    if how == "normal":
        return jax.random.normal(k, shape, jnp.float32) * value
    if how == "uniform":
        return jax.random.uniform(k, shape, jnp.float32, -value, value)
    if how == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if how == "decay_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(how)


def init_params(s: Shapes, key) -> dict:
    return {name: init_leaf(key, name, spec)
            for name, spec in leaf_specs(s).items()}


# --------------------------------------------------------------------------
# the layers
# --------------------------------------------------------------------------

def rotary_table(s: Shapes, n: int):
    """(cos, sin), each (n, qk_rope_head_dim), of plain rotary positions
    0..n-1: a frequency serves one pair of adjacent features
    (``rope_interleave`` is that column order)."""
    dim = s.qk_rope_head_dim
    inv = 1.0 / s.rope_theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    angles = np.repeat(np.outer(np.arange(n, dtype=np.float64), inv), 2, -1)
    return (jnp.asarray(np.cos(angles), jnp.float32),
            jnp.asarray(np.sin(angles), jnp.float32))


def kda_heads(s: Shapes, y, lp: dict, precision: str):
    """Kimi delta attention's heads ``lp`` holds, of ``y`` (b, n, dim): their
    rows of the output projection's sum. Nothing couples two heads before
    that projection."""
    b, n, _ = y.shape
    d = s.linear_head_dim
    h = lp["a_log"].shape[0]

    def mixed(w, conv):
        t = _quantize(product("bnd,de->bne", y, lp[w], precision), precision)
        t = _quantize(causal_conv(t, lp[conv]), precision)
        return _quantize(jax.nn.silu(t), precision).reshape(b, n, h, d)

    q, k, v = mixed("l_q", "conv_q"), mixed("l_k", "conv_k"), mixed("l_v",
                                                                    "conv_v")
    q = _quantize(l2_normalise(q) * d ** -0.5, precision)
    k = _quantize(l2_normalise(k), precision)
    f = _quantize(product("bnd,de->bne", y, lp["w_f"], precision), precision)
    g = s.kda_lower_bound * jax.nn.sigmoid(
        jnp.exp(lp["a_log"])[:, None]
        * (f + lp["decay_bias"]).reshape(b, n, h, d))
    beta = s.kda_beta_max * jax.nn.sigmoid(_quantize(
        product("bnd,dh->bnh", y, lp["w_beta"], precision), precision))
    o = _quantize(delta_rule(q, k, v, g, beta, precision), precision)
    o = _quantize(rms_norm(o, lp["o_norm_g"], s.rms_norm_eps), precision)
    gate = jax.nn.sigmoid(_quantize(
        product("bnd,de->bne", y, lp["w_g"], precision), precision))
    o = _quantize(o.reshape(b, n, h * d) * gate, precision)
    return product("bne,ed->bnd", o, lp["l_o"], precision)


# the leaves of a linear-attention layer that hold one column (or row, or
# element) a head's channel, by the axis the heads lie along
_BY_HEAD = {"l_q": 1, "l_k": 1, "l_v": 1, "conv_q": 1, "conv_k": 1,
            "conv_v": 1, "w_f": 1, "w_g": 1, "decay_bias": 0, "a_log": 0,
            "w_beta": 1, "l_o": 0}


def kda(s: Shapes, table, y, lp: dict, precision: str):
    """Kimi delta attention of ``y`` (b, n, dim), the layer's normed input:
    ``HEAD_PARTS`` parts of the heads at a time, their outputs summed.
    ``table`` is not used: a linear layer takes no positions."""
    parts = math.gcd(s.linear_num_heads, HEAD_PARTS)

    def cut(name, x):
        if name not in _BY_HEAD:
            return jnp.broadcast_to(x, (parts,) + x.shape)
        axis = _BY_HEAD[name]
        x = x.reshape(x.shape[:axis] + (parts, -1) + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)
    of_heads = {k: cut(k, v) for k, v in lp.items()
                if k in _BY_HEAD or k == "o_norm_g"}
    return jnp.sum(by_batch_row(
        lambda part: kda_heads(s, y, part, precision), of_heads), 0)


def mla(s: Shapes, table, y, lp: dict, precision: str):
    """Latent attention of ``y`` (b, n, dim): queries without a latent, a
    learned norm on every head's query and key ahead of the rotation, the
    whole (n, n) score matrix a head, a sigmoid gate a head on the output."""
    b, n, _ = y.shape
    h, dn, dr, dv = (s.heads, s.qk_nope_head_dim, s.qk_rope_head_dim,
                     s.v_head_dim)
    q = _quantize(product("bnd,de->bne", y, lp["m_q"], precision),
                  precision).reshape(b, n, h, dn + dr).transpose(0, 2, 1, 3)
    kv = _quantize(product("bnd,dr->bnr", y, lp["m_kv_a"], precision),
                   precision)
    c_kv = _quantize(rms_norm(kv[..., :s.kv_lora_rank], lp["m_kv_norm_g"],
                              s.rms_norm_eps), precision)
    k_rope = kv[..., s.kv_lora_rank:]                            # (b, n, dr)
    kv_up = _quantize(product("bnr,re->bne", c_kv, lp["m_kv_b"], precision),
                      precision).reshape(b, n, h, dn + dv).transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv_up[..., :dn], jnp.broadcast_to(k_rope[:, None], (b, h, n, dr))],
        -1)
    v = kv_up[..., dn:]

    def normed_and_turned(t, g):
        t = _quantize(rms_norm(t, g, s.rms_norm_eps), precision)
        return _quantize(jnp.concatenate(
            [t[..., :dn], rotate((table[0][:n], table[1][:n]), t[..., dn:])],
            -1), precision)
    q = normed_and_turned(q, lp["m_q_norm_g"])
    k = normed_and_turned(k, lp["m_k_norm_g"])
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def head(x):
        q_h, k_h, v_h = x                                   # (n, 192 | 128)
        dots = product("id,jd->ij", q_h * (dn + dr) ** -0.5, k_h, precision)
        attn = jax.nn.softmax(jnp.where(causal, dots, -jnp.inf), -1)
        return product("ij,jd->id", attn, v_h, precision)
    out = by_batch_row(lambda row: by_batch_row(head, row), (q, k, v))
    gate = jax.nn.sigmoid(_quantize(
        product("bnd,dh->bnh", y, lp["m_gate"], precision), precision))
    out = _quantize(out, precision) * gate.transpose(0, 2, 1)[..., None]
    out = _quantize(out, precision).transpose(0, 2, 1, 3).reshape(b, n, h * dv)
    return product("bne,ed->bnd", out, lp["m_o"], precision)


def route(s: Shapes, y, router, bias, precision: str):
    """``noaux_tc`` for rows ``y`` (n, dim): (indices, weights). The biased
    scores choose, groups by the sum of their two largest, then experts
    within the kept groups; the unbiased scores of the chosen, over their
    sum, times the scaling factor, weigh. Float32."""
    scores = jax.nn.sigmoid(product("nd,de->ne", y, router, precision))
    n, e = scores.shape
    choose = scores + bias
    pair = jax.lax.top_k(choose.reshape(n, s.n_group, e // s.n_group), 2)[0]
    _, groups = jax.lax.top_k(jnp.sum(pair, -1), s.topk_group)
    allowed = jnp.any(jax.nn.one_hot(groups, s.n_group, dtype=bool), -2)
    allowed = jnp.repeat(allowed, e // s.n_group, -1)
    _, idx = jax.lax.top_k(jnp.where(allowed, choose, -jnp.inf),
                           s.num_experts_per_tok)
    weights = jnp.take_along_axis(scores, idx, -1)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    return idx, weights * s.routed_scaling_factor


def moe(s: Shapes, y, lp: dict, precision: str):
    """``reference/deepseek_v2.py``'s ``moe`` with this router. Returns
    (output, the (n, experts_held) weights)."""
    idx, weights = route(s, y, lp["router"], lp["router_bias"], precision)
    per_expert = jnp.sum(jax.nn.one_hot(idx, s.n_routed_experts)
                         * weights[..., None], -2)
    held = per_expert[:, s.first_expert:s.first_expert + s.experts_held]
    gate = _quantize(product("nd,edf->nef", y, lp["e_gate"], precision),
                     precision)
    up = _quantize(product("nd,edf->nef", y, lp["e_up"], precision), precision)
    each = _quantize(product("nef,efd->ned", jax.nn.silu(gate) * up,
                             lp["e_down"], precision), precision)
    out = jnp.sum(each * held[..., None], 1)
    if s.n_shared_experts:
        out = out + _quantize(swiglu(y, lp["s_gate"], lp["s_up"],
                                     lp["s_down"], precision), precision)
    return out, held


def block(s: Shapes, table, x, lp: dict, layer: int, precision: str):
    """One layer: x + attention(norm(x)), then x + ff(norm(x)). Returns (x,
    the routing weights of the held experts or None)."""
    attention = kda if s.kind(layer) == "kda" else mla
    b, n, d = x.shape
    y = _quantize(rms_norm(x, lp["attn_norm_g"], s.rms_norm_eps), precision)
    # a batch row at a time; the experts a quarter of a row at a time
    out = by_batch_row(
        lambda r: attention(s, table, r[None], lp, precision)[0], y)
    x = _quantize(x + _quantize(out, precision), precision)
    y = _quantize(rms_norm(x, lp["ff_norm_g"], s.rms_norm_eps), precision)
    if "router" in lp:
        parts = math.gcd(n, ROW_PARTS)
        out, held = by_batch_row(lambda r: moe(s, r, lp, precision),
                                 y.reshape(b * parts, n // parts, d))
        out, held = out.reshape(b, n, d), held.reshape(b, n, -1)
    else:
        out, held = by_batch_row(
            lambda r: swiglu(r, lp["w_gate"], lp["w_up"], lp["w_down"],
                             precision), y), None
    return _quantize(x + _quantize(out, precision), precision), held


def hidden_states(s: Shapes, params, text, image_ids, precision: str):
    """(the last layer's output before the final norm, the embedded input,
    labels, per routed layer the (b, n, held) routing weights)."""
    text_in, labels = token_ids(s, text, image_ids)
    x = jnp.concatenate([jnp.take(params["text_emb"], text_in, 0),
                         jnp.take(params["image_emb"], image_ids, 0)], 1)
    x = embedded = _quantize(x[:, :s.seq_len], precision)
    table = rotary_table(s, s.seq_len)
    routed = []
    for l in range(s.depth):
        x, held = jax.checkpoint(
            lambda x, lp, l=l: block(s, table, x, lp, l, precision))(
                x, layer_params(params, l))
        if held is not None:
            routed.append(held)
    return x, embedded, labels, routed


def weighted_ce(s: Shapes, params, x, labels, shift: int, precision: str,
                chunk: int):
    """DALL-E's loss of normed hidden states ``x`` (b, n, dim), position
    ``i`` predicting ``labels[i + shift]`` under the logits mask of position
    ``i + shift``: (mean CE over the text labels + 7 x mean CE over the image
    labels) / 8, over the n - shift positions that have a label."""
    n = s.seq_len
    chunk = math.gcd(chunk, n)
    ahead = jnp.pad(labels[:, shift:], ((0, 0), (0, shift)))
    xs = x.reshape(x.shape[0], n // chunk, chunk, -1).transpose(1, 0, 2, 3)
    ls = ahead.reshape(ahead.shape[0], n // chunk, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def ce(x_c, l_c, start):
        logits = chunk_logits(s, params, x_c, start + shift, precision)
        logz = jax.nn.logsumexp(logits, -1)
        return logz - jnp.take_along_axis(logits, l_c[..., None], -1)[..., 0]

    ces = jax.lax.map(lambda a: ce(*a), (xs, ls, jnp.arange(0, n, chunk)))
    ces = ces.transpose(1, 0, 2).reshape(labels.shape)
    text = s.text_seq_len - shift
    text_ce = jnp.mean(ces[:, :text])
    image_ce = jnp.mean(ces[:, text:n - shift])
    return (text_ce + LOSS_IMG_WEIGHT * image_ce) / (LOSS_IMG_WEIGHT + 1.0)


def mtp_hidden(s: Shapes, params, h, embedded, precision: str):
    """The multi-token-prediction block's normed output at positions
    0 .. n-2 and its routed layer's weights. Both carry one more row, of
    zeros in front of the block (causal attention and row-wise experts let
    it change nothing before it) and of no account behind it: the blocks of
    positions then divide n as everywhere else."""
    merged = jnp.concatenate(
        [_quantize(rms_norm(h[:, :-1], params["mtp_norm_h_g"],
                            s.rms_norm_eps), precision),
         _quantize(rms_norm(embedded[:, 1:], params["mtp_norm_e_g"],
                            s.rms_norm_eps), precision)], -1)
    x = _quantize(product("bne,ed->bnd", merged, params["mtp_merge"],
                          precision), precision)
    x = jnp.pad(x, ((0, 0), (0, 1), (0, 0)))
    table = rotary_table(s, s.seq_len)
    z, held = jax.checkpoint(
        lambda x, lp: block(s, table, x, lp, s.depth, precision))(
            x, layer_params(params, s.depth))
    return (rms_norm(z, params["mtp_final_norm_g"], s.rms_norm_eps),
            held.at[:, -1].set(0.0))


def loss_fn(s: Shapes, params, text, image_ids, precision: str = "f32",
            chunk: int = 128):
    """loss = L_main + mtp_loss_weight * L_mtp. Returns (loss, (routing
    weights per routed layer, the block's last, and L_mtp))."""
    h, embedded, labels, routed = hidden_states(s, params, text, image_ids,
                                                precision)
    loss = weighted_ce(
        s, params, rms_norm(h, params["final_norm_g"], s.rms_norm_eps),
        labels, 0, precision, chunk)
    loss_mtp = jnp.zeros((), jnp.float32)
    if s.mtp_depth:
        z, held = mtp_hidden(s, params, h, embedded, precision)
        routed = routed + [held]
        loss_mtp = weighted_ce(s, params, z, labels, 1, precision, chunk)
        loss = loss + s.mtp_loss_weight * loss_mtp
    return loss, (routed, loss_mtp)


# --------------------------------------------------------------------------
# the first steps of a training run (reference/deepseek_v2.py's, this loss)
# --------------------------------------------------------------------------

def make_step(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str = "f32"):
    """step(params, opt_state, text, image_ids) -> (params, opt_state, out).
    ``out``: the loss, the multi-token-prediction loss, the gradient's norm
    before clipping, every leaf's gradient norm as the optimizer gets it,
    and per routed layer the count of rows routed to each held expert."""
    _, update = FLAT_OPTIMIZERS[optimizer]

    def step(params, opt_state, text, image_ids):
        (loss, (routed, loss_mtp)), grads = jax.value_and_grad(
            lambda p: loss_fn(s, p, text, image_ids, precision),
            has_aux=True)(params)
        grads, norm = clip_by_global_norm(grads, clip)
        leaf_norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
        params, opt_state = update(grads, opt_state, params, lr=lr)
        rows = [jnp.sum(w > 0, (0, 1)) for w in routed]
        return params, opt_state, {"loss": loss, "loss_mtp": loss_mtp,
                                   "grad_norm": norm,
                                   "leaf_grad_norms": leaf_norms,
                                   "rows_per_expert": rows}

    return step


@functools.lru_cache(maxsize=None)
def _programs(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str):
    def change(params, key):
        start = init_params(s, key)
        return {k: jnp.sqrt(jnp.sum(jnp.square(params[k] - start[k])))
                for k in params}

    return (jax.jit(lambda key: init_params(s, key)),
            jax.jit(make_step(s, optimizer, lr, clip, precision),
                    donate_argnums=(0, 1)),
            jax.jit(change))


def first_steps(s: Shapes, recipe: dict, seed: int, batches, *,
                precision: str = "f32", rows=None) -> dict:
    """``reference/deepseek_v2.py``'s ``first_steps`` for this stack, with
    every step's ``loss_mtp`` besides."""
    key = seed_key(seed)
    init, step, change = _programs(
        s, recipe["optimizer"], float(recipe.get("learning_rate", 3e-4)),
        float(recipe.get("grad_clip_norm", 0.0)), precision)
    params = init(key)
    opt_state = FLAT_OPTIMIZERS[recipe["optimizer"]][0](params)
    losses, mtp, norms, first, rows_per_expert = [], [], [], None, None
    for text, ids in batches:
        if rows is not None:
            text, ids = text[rows], ids[rows]
        params, opt_state, out = step(params, opt_state, jnp.asarray(text),
                                      jnp.asarray(ids))
        out = jax.device_get(out)
        losses.append(float(out["loss"]))
        mtp.append(float(out["loss_mtp"]))
        norms.append(float(out["grad_norm"]))
        if first is None:
            first = {k: float(v) for k, v in out["leaf_grad_norms"].items()}
            rows_per_expert = [[int(c) for c in layer]
                               for layer in out["rows_per_expert"]]
    del opt_state
    delta = {k: float(v)
             for k, v in jax.device_get(change(params, key)).items()}
    del params
    return {"loss": losses, "loss_mtp": mtp, "grad_norm": norms,
            "leaf_grad_norms": first, "leaf_change_norms": delta,
            "rows_per_expert": rows_per_expert}
