"""Plain reference for one chip's share of a Solar-Open2 stack trained as the
DALL-E transformer: forward, loss, gradients, clipping and the optimizer in
straightforward jax.numpy and float32.

The layers are Solar-Open2-250B's as its ``config.json`` gives them
(``model_type: solar_open2``): three Kimi-delta linear-attention layers
(Kimi Linear, arXiv:2510.26692) to one gated grouped-query softmax layer
with no positional term, every layer followed by 320 routed (8 a token, by
sigmoid scores renormalised over the 8) + 1 shared SwiGLU experts, RMSNorm,
no biases but the decay's. The embeddings of the two vocabularies, the
logits mask, the weighted cross-entropy and the optimizer are DALL-E's and
come from ``reference/dalle.py``; the expert layer's plain form (every held
expert applied to every row, times the row's weight for it) is
``reference/deepseek_v2.py``'s with this router.

**Linear attention in its recurrent form**, one position at a time, exactly
as the layer is defined (per head, ``S`` of (d_k, d_v), ``S_0 = 0``):

    S_t = (I - b_t k_t k_t^T) Diag(exp g_t) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

so that the comparison tests the program's chunked algebra and not a copy
of it. Softmax attention is plain masked scores. On the chip both are
computed in blocks so that float32 at 4352 positions fits: a layer's
attention a batch row at a time (a linear layer's half of the heads at a
time), the recurrence as a scan over blocks of
positions whose inner scan is rematerialised (68 saved states, not 4352), the
scores one query head at a time, the experts a quarter of a row at a time;
that changes no number. It imports nothing of the program.

**The share.** A layer is told which routed experts it holds
(``experts_held`` of ``n_routed_experts`` from ``first_expert``): the router
scores all experts, picks ``num_experts_per_tok`` and renormalises over
them, held or not; the layer adds what its held experts contribute and the
shared expert; what absent experts would add is left out.

``precision`` is ``reference/dalle.py``'s: ``f32`` (the reference proper),
``bf16`` or ``fp8`` (the control). The router, the decay gates, their
cumulative effect and the state stay float32 in every precision, as the
configuration states.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.dalle import (LOSS_IMG_WEIGHT, _quantize,
                                        chunk_logits, clip_by_global_norm,
                                        product, seed_key, token_ids)
from benchmarks.reference.deepseek_v2 import (FLAT_OPTIMIZERS, by_batch_row,
                                              layer_params, rms_norm, swiglu)

_BLOCK_INTS = (
    "num_key_value_heads", "linear_num_heads", "linear_head_dim",
    "short_conv_kernel_size", "linear_gate_rank", "moe_intermediate_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok")
L2_EPS = 1e-6
SCAN_BLOCK = 64     # positions of the recurrence rematerialised together
ROW_PARTS = 4       # parts of a batch row the experts see at a time
HEAD_PARTS = 4      # parts of a linear-attention layer's heads at a time


class Shapes(NamedTuple):
    """The sizes of one configuration file, as the reference needs them."""
    num_text_tokens: int
    text_seq_len: int
    dim: int
    depth: int
    heads: int
    dim_head: int
    experts_held: int
    image_vocab_size: int
    image_fmap_size: int
    attention_layers: Tuple[str, ...]
    num_key_value_heads: int
    linear_num_heads: int
    linear_head_dim: int
    short_conv_kernel_size: int
    linear_gate_rank: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    routed_scaling_factor: float
    rms_norm_eps: float
    first_expert: int = 0

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """``model``: the program's keyword arguments, the block's own sizes
        under ``block`` by the source's names. 0 experts held means all."""
        block = model["block"]
        if (block.get("scoring_func") != "sigmoid"
                or not block.get("norm_topk_prob")
                or block.get("n_group", 1) != 1
                or block.get("positions") != "none"):
            raise ValueError("this reference routes by sigmoid scores "
                             "renormalised over the chosen, with no groups, "
                             "and adds no positions")
        top = {k: int(model[k]) for k in (
            "num_text_tokens", "text_seq_len", "dim", "depth", "heads",
            "dim_head", "image_vocab_size", "image_fmap_size")}
        top["experts_held"] = int(model.get("experts_held")
                                  or block["n_routed_experts"])
        return cls(**top, attention_layers=tuple(block["attention_layers"]),
                   **{k: int(block[k]) for k in _BLOCK_INTS},
                   routed_scaling_factor=float(block["routed_scaling_factor"]),
                   rms_norm_eps=float(block["rms_norm_eps"]))

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

    def kind(self, layer: int) -> str:
        return self.attention_layers[layer % len(self.attention_layers)]


# --------------------------------------------------------------------------
# weights from the seed: a flat {leaf name: array}, "l_q.3" is layer 3's
# --------------------------------------------------------------------------

def layer_leaf_specs(s: Shapes, kind: str) -> dict:
    """name -> (shape, how, value) of one layer's leaves. ``normal``: N(0,
    value^2), value the fan-in ^ -0.5; ``const``: ``value``; ``uniform``:
    U(-value, value); ``a_log``: log U(1, 16); ``decay_bias``: softplus^-1 of
    a step drawn log-uniformly from (1e-3, 1e-1) (the last three as the
    public implementation of Kimi delta attention initialises them)."""
    d = s.dim
    if kind == "gqa_gated":
        inner, kv = s.heads * s.dim_head, s.num_key_value_heads * s.dim_head
        specs = {"g_q": ((d, inner), "normal", d ** -0.5),
                 "g_k": ((d, kv), "normal", d ** -0.5),
                 "g_v": ((d, kv), "normal", d ** -0.5),
                 "g_gate": ((d, inner), "normal", d ** -0.5),
                 "g_o": ((inner, d), "normal", inner ** -0.5)}
    elif kind == "kda":
        h, dh = s.linear_num_heads, s.linear_head_dim
        inner, r, taps = h * dh, s.linear_gate_rank, s.short_conv_kernel_size
        specs = {"l_q": ((d, inner), "normal", d ** -0.5),
                 "l_k": ((d, inner), "normal", d ** -0.5),
                 "l_v": ((d, inner), "normal", d ** -0.5),
                 "conv_q": ((taps, inner), "uniform", taps ** -0.5),
                 "conv_k": ((taps, inner), "uniform", taps ** -0.5),
                 "conv_v": ((taps, inner), "uniform", taps ** -0.5),
                 "f_down": ((d, r), "normal", d ** -0.5),
                 "f_up": ((r, inner), "normal", r ** -0.5),
                 "a_log": ((h,), "a_log", None),
                 "decay_bias": ((inner,), "decay_bias", None),
                 "w_beta": ((d, h), "normal", d ** -0.5),
                 "g_down": ((d, r), "normal", d ** -0.5),
                 "g_up": ((r, inner), "normal", r ** -0.5),
                 "o_norm_g": ((dh,), "const", 1.0),
                 "l_o": ((inner, d), "normal", inner ** -0.5)}
    else:
        raise ValueError(f"no layer kind {kind!r} in this reference")
    f, e = s.moe_intermediate_size, s.experts_held
    specs.update({"attn_norm_g": ((d,), "const", 1.0),
                  "ff_norm_g": ((d,), "const", 1.0),
                  "router": ((d, s.n_routed_experts), "normal", d ** -0.5),
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
    return {"text_emb": ((s.text_vocab, d), "normal", d ** -0.5),
            "image_emb": ((s.image_vocab_size, d), "normal", d ** -0.5),
            "final_norm_g": ((d,), "const", 1.0),
            "w_logits": ((d, s.total_vocab), "normal", d ** -0.5),
            "b_logits": ((s.total_vocab,), "const", 0.0)}


def leaf_specs(s: Shapes) -> dict:
    """Every leaf by its full name."""
    out = dict(top_leaf_specs(s))
    for l in range(s.depth):
        for name, spec in layer_leaf_specs(s, s.kind(l)).items():
            out[f"{name}.{l}"] = spec
    return out


_NAMES = sorted({
    "text_emb", "image_emb", "final_norm_g", "w_logits", "b_logits",
    "attn_norm_g", "ff_norm_g", "router", "e_gate", "e_up", "e_down",
    "s_gate", "s_up", "s_down", "g_q", "g_k", "g_v", "g_gate", "g_o", "l_q",
    "l_k", "l_v", "conv_q", "conv_k", "conv_v", "f_down", "f_up", "a_log",
    "decay_bias", "w_beta", "g_down", "g_up", "o_norm_g", "l_o"})
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

def causal_conv(x, w):
    """y_t = sum_i w[i] x_{t - K + 1 + i}, zeros before the start; ``x``
    (b, n, c), ``w`` (K, c)."""
    taps, n = w.shape[0], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, i:i + n] * w[i] for i in range(taps))


def l2_normalise(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                             + L2_EPS)


def delta_rule(q, k, v, g, beta, precision: str):
    """The gated delta rule one position at a time. ``q``, ``k``, ``g``:
    (b, n, h, d_k); ``v``: (b, n, h, d_v); ``beta``: (b, n, h). Returns
    (b, n, h, d_v). The state is float32; a product takes its operands in
    ``precision``."""
    b, n, h, dk = q.shape

    def position(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - product("bhd,bhdv->bhv", k_t, state,
                                            precision))
        state = state + product("bhd,bhv->bhdv", k_t, u, precision)
        return state, product("bhd,bhdv->bhv", q_t, state, precision)

    @jax.checkpoint
    def block(state, xs):
        return jax.lax.scan(position, state, xs)

    size = math.gcd(SCAN_BLOCK, n)
    xs = tuple(jnp.moveaxis(t, 1, 0).reshape((n // size, size) + t.shape[:1]
                                             + t.shape[2:])
               for t in (q, k, v, g, beta))
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dk, v.shape[-1]),
                                         jnp.float32), xs)
    return jnp.moveaxis(o.reshape((n,) + o.shape[2:]), 0, 1)


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
    f = _quantize(product("bnd,dr->bnr", y, lp["f_down"], precision),
                  precision)
    f = _quantize(product("bnr,re->bne", f, lp["f_up"], precision), precision)
    g = (-jnp.exp(lp["a_log"])[:, None]
         * jax.nn.softplus(f + lp["decay_bias"]).reshape(b, n, h, d))
    beta = 2.0 * jax.nn.sigmoid(_quantize(
        product("bnd,dh->bnh", y, lp["w_beta"], precision), precision))
    o = _quantize(delta_rule(q, k, v, g, beta, precision), precision)
    o = _quantize(rms_norm(o, lp["o_norm_g"], s.rms_norm_eps), precision)
    gate = _quantize(product("bnd,dr->bnr", y, lp["g_down"], precision),
                     precision)
    gate = jax.nn.sigmoid(_quantize(
        product("bnr,re->bne", gate, lp["g_up"], precision), precision))
    o = _quantize(o.reshape(b, n, h * d) * gate, precision)
    return product("bne,ed->bnd", o, lp["l_o"], precision)


# the leaves of a linear-attention layer that hold one column (or row, or
# element) a head's channel, by the axis the heads lie along
_BY_HEAD = {"l_q": 1, "l_k": 1, "l_v": 1, "conv_q": 1, "conv_k": 1,
            "conv_v": 1, "f_up": 1, "g_up": 1, "decay_bias": 0, "a_log": 0,
            "w_beta": 1, "l_o": 0}


def kda(s: Shapes, y, lp: dict, precision: str):
    """Kimi delta attention of ``y`` (b, n, dim), the layer's normed input:
    ``HEAD_PARTS`` parts of the heads at a time, their outputs summed (a
    whole layer's float32 tensors of 4352 x 8192 would not fit)."""
    h = s.linear_num_heads
    parts = math.gcd(h, HEAD_PARTS)

    def cut(name, x):
        if name not in _BY_HEAD:
            return jnp.broadcast_to(x, (parts,) + x.shape)
        axis = _BY_HEAD[name]
        x = x.reshape(x.shape[:axis] + (parts, -1) + x.shape[axis + 1:])
        return jnp.moveaxis(x, axis, 0)
    of_heads = {k: cut(k, v) for k, v in lp.items()
                if k in _BY_HEAD or k in ("f_down", "g_down", "o_norm_g")}
    return jnp.sum(by_batch_row(
        lambda part: kda_heads(s, y, part, precision), of_heads), 0)


def gqa_gated(s: Shapes, y, lp: dict, precision: str):
    """Causal softmax attention of ``heads`` query heads over
    ``num_key_value_heads`` key and value heads, no positions, the output
    gated element by element by a sigmoid of its own projection of ``y``."""
    b, n, _ = y.shape
    h, kv, d = s.heads, s.num_key_value_heads, s.dim_head
    per = h // kv

    def heads(w, count):
        t = _quantize(product("bnd,de->bne", y, lp[w], precision), precision)
        return t.reshape(b, n, count, d)

    # (b, kv, per, ...): a batch row, a group's keys and values and one
    # query head at a time
    q = heads("g_q", h).reshape(b, n, kv, per, d).transpose(0, 2, 3, 1, 4)
    k = heads("g_k", kv).transpose(0, 2, 1, 3)
    v = heads("g_v", kv).transpose(0, 2, 1, 3)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def group(x):
        q_g, k_g, v_g = x                       # (per, n, d), (n, d), (n, d)

        def head(q_h):
            dots = product("id,jd->ij", q_h * d ** -0.5, k_g, precision)
            attn = jax.nn.softmax(jnp.where(causal, dots, -jnp.inf), -1)
            return product("ij,jd->id", attn, v_g, precision)
        return by_batch_row(head, q_g)

    out = by_batch_row(lambda row: by_batch_row(group, row), (q, k, v))
    out = _quantize(out, precision).transpose(0, 3, 1, 2, 4).reshape(
        b, n, h * d)
    gate = jax.nn.sigmoid(_quantize(
        product("bnd,de->bne", y, lp["g_gate"], precision), precision))
    return product("bne,ed->bnd", _quantize(out * gate, precision),
                   lp["g_o"], precision)


def route(s: Shapes, y, router, precision: str):
    """The ``num_experts_per_tok`` largest of all routed experts' sigmoid
    scores for rows ``y`` (n, dim): (indices, weights), a row's weights its
    scores over their sum, times the scaling factor. Float32."""
    scores = jax.nn.sigmoid(product("nd,de->ne", y, router, precision))
    weights, idx = jax.lax.top_k(scores, s.num_experts_per_tok)
    weights = weights / jnp.sum(weights, -1, keepdims=True)
    return idx, weights * s.routed_scaling_factor


def moe(s: Shapes, y, lp: dict, precision: str):
    """``reference/deepseek_v2.py``'s ``moe`` with this router. Returns
    (output, the (n, experts_held) weights)."""
    idx, weights = route(s, y, lp["router"], precision)
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


def block(s: Shapes, x, lp: dict, layer: int, precision: str):
    """One layer: x + attention(norm(x)), then x + experts(norm(x)).
    Returns (x, the routing weights of the held experts)."""
    attention = kda if s.kind(layer) == "kda" else gqa_gated
    b, n, d = x.shape
    y = _quantize(rms_norm(x, lp["attn_norm_g"], s.rms_norm_eps), precision)
    # a batch row at a time; the experts a quarter of a row at a time (at
    # 4352 positions one row's (n, held, dim) products are 0.7 GB each)
    out = by_batch_row(lambda r: attention(s, r[None], lp, precision)[0], y)
    x = _quantize(x + _quantize(out, precision), precision)
    y = _quantize(rms_norm(x, lp["ff_norm_g"], s.rms_norm_eps), precision)
    parts = math.gcd(n, ROW_PARTS)
    out, held = by_batch_row(lambda r: moe(s, r, lp, precision),
                             y.reshape(b * parts, n // parts, d))
    return (_quantize(x + _quantize(out.reshape(b, n, d), precision),
                      precision), held.reshape(b, n, -1))


def hidden_states(s: Shapes, params, text, image_ids, precision: str):
    """(final hidden states, labels, per layer the (b, n, held) routing
    weights). No positional term enters anywhere."""
    text_in, labels = token_ids(s, text, image_ids)
    x = jnp.concatenate([jnp.take(params["text_emb"], text_in, 0),
                         jnp.take(params["image_emb"], image_ids, 0)], 1)
    x = _quantize(x[:, :s.seq_len], precision)
    routed = []
    for l in range(s.depth):
        x, held = jax.checkpoint(
            lambda x, lp, l=l: block(s, x, lp, l, precision))(
                x, layer_params(params, l))
        routed.append(held)
    return (rms_norm(x, params["final_norm_g"], s.rms_norm_eps), labels,
            routed)


def loss_fn(s: Shapes, params, text, image_ids, precision: str = "f32",
            chunk: int = 128):
    """(mean text CE + 7 * mean image CE) / 8, DALL-E's. Returns (loss,
    routing weights per layer)."""
    x, labels, routed = hidden_states(s, params, text, image_ids, precision)
    n = s.seq_len
    chunk = math.gcd(chunk, n)
    xs = x.reshape(x.shape[0], n // chunk, chunk, -1).transpose(1, 0, 2, 3)
    ls = labels.reshape(labels.shape[0], n // chunk, chunk).transpose(1, 0, 2)

    @jax.checkpoint
    def ce(x_c, l_c, start):
        logits = chunk_logits(s, params, x_c, start, precision)
        logz = jax.nn.logsumexp(logits, -1)
        return logz - jnp.take_along_axis(logits, l_c[..., None], -1)[..., 0]

    ces = jax.lax.map(lambda a: ce(*a), (xs, ls, jnp.arange(0, n, chunk)))
    ces = ces.transpose(1, 0, 2).reshape(labels.shape)
    text_ce = jnp.mean(ces[:, :s.text_seq_len])
    image_ce = jnp.mean(ces[:, s.text_seq_len:])
    loss = (text_ce + LOSS_IMG_WEIGHT * image_ce) / (LOSS_IMG_WEIGHT + 1.0)
    return loss, routed


# --------------------------------------------------------------------------
# the first steps of a training run (reference/deepseek_v2.py's, this loss)
# --------------------------------------------------------------------------

def make_step(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str = "f32"):
    """step(params, opt_state, text, image_ids) -> (params, opt_state, out).
    ``out``: the loss, the gradient's norm before clipping, every leaf's
    gradient norm as the optimizer gets it, and per layer the count of rows
    routed to each held expert."""
    _, update = FLAT_OPTIMIZERS[optimizer]

    def step(params, opt_state, text, image_ids):
        (loss, routed), grads = jax.value_and_grad(
            lambda p: loss_fn(s, p, text, image_ids, precision),
            has_aux=True)(params)
        grads, norm = clip_by_global_norm(grads, clip)
        leaf_norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
        params, opt_state = update(grads, opt_state, params, lr=lr)
        rows = [jnp.sum(w > 0, (0, 1)) for w in routed]
        return params, opt_state, {"loss": loss, "grad_norm": norm,
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
    """``reference/dalle.py``'s ``first_steps`` for this stack, with the
    first step's ``rows_per_expert`` (a list over layers) besides."""
    key = seed_key(seed)
    init, step, change = _programs(
        s, recipe["optimizer"], float(recipe.get("learning_rate", 3e-4)),
        float(recipe.get("grad_clip_norm", 0.0)), precision)
    params = init(key)
    opt_state = FLAT_OPTIMIZERS[recipe["optimizer"]][0](params)
    losses, norms, first, rows_per_expert = [], [], None, None
    for text, ids in batches:
        if rows is not None:
            text, ids = text[rows], ids[rows]
        params, opt_state, out = step(params, opt_state, jnp.asarray(text),
                                      jnp.asarray(ids))
        out = jax.device_get(out)
        losses.append(float(out["loss"]))
        norms.append(float(out["grad_norm"]))
        if first is None:
            first = {k: float(v) for k, v in out["leaf_grad_norms"].items()}
            rows_per_expert = [[int(c) for c in layer]
                               for layer in out["rows_per_expert"]]
    del opt_state
    delta = {k: float(v)
             for k, v in jax.device_get(change(params, key)).items()}
    del params
    return {"loss": losses, "grad_norm": norms, "leaf_grad_norms": first,
            "leaf_change_norms": delta, "rows_per_expert": rows_per_expert}
