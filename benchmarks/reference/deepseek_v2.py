"""Plain reference for one chip's share of a DeepSeek-V2 stack trained as the
DALL-E transformer: forward, loss, gradients, clipping and the optimizer in
straightforward jax.numpy and float32.

The block is DeepSeek-V2's as its ``config.json`` gives it (arXiv:2405.04434;
``modeling_deepseek.py``: ``DeepseekV2Attention``, ``DeepseekV2MoE``,
``MoEGate`` with ``group_limited_greedy``, ``DeepseekV2YarnRotaryEmbedding``):
multi-head latent attention with a rotary part shared by all heads' keys, a
SwiGLU MLP in the leading dense layers, then routed experts plus shared
experts, RMSNorm, no biases. The embeddings of the two vocabularies, the
logits mask, the weighted cross-entropy and the optimizers are DALL-E's and
come from ``reference/dalle.py``.

**The share.** A layer is told which heads and which routed experts it holds
(``heads_held`` of ``heads``; ``experts_held`` of ``n_routed_experts``
starting at ``first_expert``). The router scores all experts and picks
``num_experts_per_tok`` of them; the layer adds what the experts it holds
contribute, and the shared experts; what absent experts would add is left
out. Attention computes the held heads and their rows of the output
projection: a partial sum. With every head and every expert held this is the
uncut layer.

No sort, no kernel, no capacity: every held expert is applied to every row
and the result is multiplied by the routing weight, which is 0 where the row
was not routed there. On the chip the rows are walked a batch row at a time
under ``jax.checkpoint`` so that float32 at 10,240 tokens fits; that changes
no number. It imports nothing of the program.

``precision`` is ``reference/dalle.py``'s: ``f32`` (the reference proper),
``bf16`` or ``fp8`` (the control). The router's product and softmax take
their operands in the compute type and run in float32, as the source's gate
does (it casts both to float32).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.dalle import (LOSS_IMG_WEIGHT, _adafactor_leaf,
                                        _adafactor_moments, _quantize,
                                        chunk_logits, clip_by_global_norm,
                                        product, seed_key, token_ids)

_BLOCK_KEYS = (
    "first_dense_layers", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim", "intermediate_size",
    "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
    "num_experts_per_tok", "n_group", "topk_group")
_BLOCK_FLOATS = (
    "routed_scaling_factor", "rope_theta", "yarn_factor",
    "yarn_original_max_position", "yarn_beta_fast", "yarn_beta_slow",
    "yarn_mscale", "yarn_mscale_all_dim", "rms_norm_eps")


class Shapes(NamedTuple):
    """The sizes of one configuration file, as the reference needs them."""
    num_text_tokens: int
    text_seq_len: int
    dim: int
    depth: int
    heads: int
    heads_held: int
    experts_held: int
    image_vocab_size: int
    image_fmap_size: int
    first_dense_layers: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int
    n_shared_experts: int
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    rope_theta: float
    yarn_factor: float
    yarn_original_max_position: float
    yarn_beta_fast: float
    yarn_beta_slow: float
    yarn_mscale: float
    yarn_mscale_all_dim: float
    rms_norm_eps: float
    first_expert: int = 0

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """``model``: the program's keyword arguments, the block's own sizes
        under ``block`` by the source's names. 0 heads or experts held means
        all of them."""
        block = model["block"]
        top = {k: int(model[k]) for k in (
            "num_text_tokens", "text_seq_len", "dim", "depth", "heads",
            "image_vocab_size", "image_fmap_size")}
        top["heads_held"] = int(model.get("heads_held") or model["heads"])
        top["experts_held"] = int(model.get("experts_held")
                                  or block["n_routed_experts"])
        return cls(**top, **{k: int(block[k]) for k in _BLOCK_KEYS},
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

    def is_moe(self, layer: int) -> bool:
        return layer >= self.first_dense_layers


# --------------------------------------------------------------------------
# weights from the seed: a flat {leaf name: array}, "q_a.3" is layer 3's
# --------------------------------------------------------------------------

def layer_leaf_specs(s: Shapes, moe: bool) -> dict:
    """name -> (shape, kind, value) of one layer's leaves. ``normal`` leaves
    are N(0, value**2) with value = fan-in ** -0.5; ``const`` leaves hold
    ``value``."""
    d, h = s.dim, s.heads_held
    specs = {
        "attn_norm_g": ((d,), "const", 1.0),
        "q_a": ((d, s.q_lora_rank), "normal", d ** -0.5),
        "q_norm_g": ((s.q_lora_rank,), "const", 1.0),
        "q_b": ((s.q_lora_rank, h * s.qk_head_dim), "normal",
                s.q_lora_rank ** -0.5),
        "kv_a": ((d, s.kv_lora_rank + s.qk_rope_head_dim), "normal",
                 d ** -0.5),
        "kv_norm_g": ((s.kv_lora_rank,), "const", 1.0),
        "kv_b": ((s.kv_lora_rank, h * (s.qk_nope_head_dim + s.v_head_dim)),
                 "normal", s.kv_lora_rank ** -0.5),
        # fan-in of the whole projection, of which these are the held rows
        "o": ((h * s.v_head_dim, d), "normal",
              (s.heads * s.v_head_dim) ** -0.5),
        "ff_norm_g": ((d,), "const", 1.0),
    }
    if not moe:
        f = s.intermediate_size
        specs.update({"w_gate": ((d, f), "normal", d ** -0.5),
                      "w_up": ((d, f), "normal", d ** -0.5),
                      "w_down": ((f, d), "normal", f ** -0.5)})
        return specs
    f, e = s.moe_intermediate_size, s.experts_held
    specs.update({"router": ((d, s.n_routed_experts), "normal", d ** -0.5),
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
        for name, spec in layer_leaf_specs(s, s.is_moe(l)).items():
            out[f"{name}.{l}"] = spec
    return out


_NAMES = sorted({"text_emb", "image_emb", "final_norm_g", "w_logits",
                 "b_logits", "attn_norm_g", "q_a", "q_norm_g", "q_b", "kv_a",
                 "kv_norm_g", "kv_b", "o", "ff_norm_g", "w_gate", "w_up",
                 "w_down", "router", "e_gate", "e_up", "e_down", "s_gate",
                 "s_up", "s_down"})
LEAF_IDS = {name: i for i, name in enumerate(_NAMES)}


def init_leaf(key, full_name: str, spec):
    shape, kind, value = spec
    if kind == "const":
        return jnp.full(shape, value, jnp.float32)
    name, _, layer = full_name.partition(".")
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]),
                           int(layer or 0))
    return jax.random.normal(k, shape, jnp.float32) * value


def init_params(s: Shapes, key) -> dict:
    return {name: init_leaf(key, name, spec)
            for name, spec in leaf_specs(s).items()}


def leaf_names(s: Shapes) -> list:
    return sorted(leaf_specs(s))


def layer_params(params: dict, layer: int) -> dict:
    tail = f".{layer}"
    return {k[:-len(tail)]: v for k, v in params.items() if k.endswith(tail)}


# --------------------------------------------------------------------------
# positions: rotary over 0..n-1 with YaRN's blend of frequencies
# --------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(s: Shapes) -> np.ndarray:
    """Per frequency: the extrapolated 1 / theta^(2i/dim) where the original
    context holds many rotations of it, that over ``factor`` where it holds
    few, a linear blend between (``_yarn_find_correction_range`` and
    ``_yarn_linear_ramp_mask`` of the source)."""
    dim, base = s.qk_rope_head_dim, s.rope_theta
    inv = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if s.yarn_factor <= 1:
        return inv

    def correction_dim(rotations):
        return (dim * math.log(s.yarn_original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(s.yarn_beta_fast)), 0)
    high = min(math.ceil(correction_dim(s.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inv / s.yarn_factor * (1.0 - keep) + inv * keep


def rotary_table(s: Shapes, n: int):
    """(cos, sin), each (n, qk_rope_head_dim): a frequency serves one pair of
    adjacent features (the checkpoint's interleaved layout; the source
    permutes to halves and rotates those, which is the same rotation)."""
    angles = np.repeat(np.outer(np.arange(n, dtype=np.float64),
                                yarn_inv_freq(s)), 2, -1)
    scale = (yarn_mscale(s.yarn_factor, s.yarn_mscale)
             / yarn_mscale(s.yarn_factor, s.yarn_mscale_all_dim))
    return (jnp.asarray(np.cos(angles) * scale, jnp.float32),
            jnp.asarray(np.sin(angles) * scale, jnp.float32))


def rotate(table, t):
    """Turn adjacent feature pairs of ``t`` (..., n, rope dim)."""
    cos, sin = table
    pairs = t.reshape(*t.shape[:-1], t.shape[-1] // 2, 2)
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(t.shape)
    return t * cos + turned * sin


def softmax_scale(s: Shapes) -> float:
    m = yarn_mscale(s.yarn_factor, s.yarn_mscale_all_dim)
    return s.qk_head_dim ** -0.5 * m * m


# --------------------------------------------------------------------------
# the layer
# --------------------------------------------------------------------------

def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * g


def mla(s: Shapes, table, y, lp: dict, precision: str):
    """Latent attention over the held heads of ``y`` (b, n, dim): the partial
    sum of the output projection over those heads."""
    b, n, _ = y.shape
    h, dn, dr, dv = (s.heads_held, s.qk_nope_head_dim, s.qk_rope_head_dim,
                     s.v_head_dim)
    q_lat = _quantize(product("bnd,dr->bnr", y, lp["q_a"], precision),
                      precision)
    q_lat = _quantize(rms_norm(q_lat, lp["q_norm_g"], s.rms_norm_eps),
                      precision)
    q = _quantize(product("bnr,re->bne", q_lat, lp["q_b"], precision),
                  precision).reshape(b, n, h, dn + dr).transpose(0, 2, 1, 3)
    kv = _quantize(product("bnd,dr->bnr", y, lp["kv_a"], precision), precision)
    c_kv = _quantize(rms_norm(kv[..., :s.kv_lora_rank], lp["kv_norm_g"],
                              s.rms_norm_eps), precision)
    k_rope = rotate(table, kv[..., s.kv_lora_rank:])            # (b, n, dr)
    kv_up = _quantize(product("bnr,re->bne", c_kv, lp["kv_b"], precision),
                      precision).reshape(b, n, h, dn + dv).transpose(0, 2, 1, 3)
    k_nope, v = kv_up[..., :dn], kv_up[..., dn:]
    q = jnp.concatenate([q[..., :dn], rotate(table, q[..., dn:])], -1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, None], (b, h, n, dr))], -1)
    q, k = _quantize(q, precision), _quantize(k, precision)
    dots = product("bhid,bhjd->bhij", q * softmax_scale(s), k, precision)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    attn = jax.nn.softmax(jnp.where(causal, dots, -jnp.inf), -1)
    out = product("bhij,bhjd->bhid", attn, v, precision)
    out = _quantize(out, precision).transpose(0, 2, 1, 3).reshape(b, n, h * dv)
    return product("bne,ed->bnd", out, lp["o"], precision)


def swiglu(x, w_gate, w_up, w_down, precision: str):
    gate = _quantize(product("nd,df->nf", x, w_gate, precision), precision)
    up = _quantize(product("nd,df->nf", x, w_up, precision), precision)
    return product("nf,fd->nd", jax.nn.silu(gate) * up, w_down, precision)


def route(s: Shapes, y, router, precision: str):
    """Group-limited greedy choice of ``num_experts_per_tok`` of all routed
    experts for rows ``y`` (n, dim): (indices, weights), weights the softmax
    probabilities themselves (not renormalised) times the scaling factor."""
    scores = jax.nn.softmax(product("nd,de->ne", y, router, precision), -1)
    n, e = scores.shape
    best_in_group = scores.reshape(n, s.n_group, e // s.n_group).max(-1)
    _, groups = jax.lax.top_k(best_in_group, s.topk_group)
    allowed = jnp.any(jax.nn.one_hot(groups, s.n_group, dtype=bool), -2)
    allowed = jnp.repeat(allowed, e // s.n_group, -1)
    weights, idx = jax.lax.top_k(jnp.where(allowed, scores, 0.0),
                                 s.num_experts_per_tok)
    return idx, weights * s.routed_scaling_factor


def moe(s: Shapes, y, lp: dict, precision: str):
    """Rows ``y`` (n, dim) through the held experts, every one applied to
    every row and multiplied by the row's routing weight for it (0 where the
    row went elsewhere), plus the shared experts. Returns (output, the
    (n, experts_held) weights)."""
    idx, weights = route(s, y, lp["router"], precision)
    per_expert = jnp.sum(jax.nn.one_hot(idx, s.n_routed_experts)
                         * weights[..., None], -2)
    held = per_expert[:, s.first_expert:s.first_expert + s.experts_held]
    gate = _quantize(product("nd,edf->nef", y, lp["e_gate"], precision),
                     precision)
    up = _quantize(product("nd,edf->nef", y, lp["e_up"], precision), precision)
    # as the program combines: each expert's output in the compute type,
    # times its weight, summed
    each = _quantize(product("nef,efd->ned", jax.nn.silu(gate) * up,
                             lp["e_down"], precision), precision)
    out = jnp.sum(each * held[..., None], 1)
    if s.n_shared_experts:
        out = out + _quantize(swiglu(y, lp["s_gate"], lp["s_up"],
                                     lp["s_down"], precision), precision)
    return out, held


def by_batch_row(fn, x):
    """``fn`` of one batch row at a time, each under ``jax.checkpoint``: what
    a whole batch's hidden layer would hold is never alive at once."""
    return jax.lax.map(jax.checkpoint(fn), x)


def block(s: Shapes, table, x, lp: dict, layer: int, precision: str):
    """One layer: x + MLA(norm(x)), then x + FF(norm(x)). Returns (x, the
    routing weights of the held experts or None)."""
    y = _quantize(rms_norm(x, lp["attn_norm_g"], s.rms_norm_eps), precision)
    x = _quantize(x + _quantize(mla(s, table, y, lp, precision), precision),
                  precision)
    y = _quantize(rms_norm(x, lp["ff_norm_g"], s.rms_norm_eps), precision)
    if s.is_moe(layer):
        out, held = by_batch_row(lambda r: moe(s, r, lp, precision), y)
    else:
        out, held = by_batch_row(
            lambda r: swiglu(r, lp["w_gate"], lp["w_up"], lp["w_down"],
                             precision), y), None
    return _quantize(x + _quantize(out, precision), precision), held


def hidden_states(s: Shapes, params, text, image_ids, precision: str):
    """(final hidden states, labels, per expert layer the (b, n, held)
    routing weights)."""
    text_in, labels = token_ids(s, text, image_ids)
    x = jnp.concatenate([jnp.take(params["text_emb"], text_in, 0),
                         jnp.take(params["image_emb"], image_ids, 0)], 1)
    x = _quantize(x[:, :s.seq_len], precision)
    table = rotary_table(s, x.shape[1])
    routed = []
    for l in range(s.depth):
        x, held = jax.checkpoint(
            lambda x, lp, l=l: block(s, table, x, lp, l, precision))(
                x, layer_params(params, l))
        if held is not None:
            routed.append(held)
    return (rms_norm(x, params["final_norm_g"], s.rms_norm_eps), labels,
            routed)


def loss_fn(s: Shapes, params, text, image_ids, precision: str = "f32",
            chunk: int = 128):
    """(mean text CE + 7 * mean image CE) / 8, DALL-E's. Returns (loss,
    routing weights per expert layer)."""
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
# Adafactor over the flat tree (reference/dalle.py's, leaf by leaf)
# --------------------------------------------------------------------------

def adafactor_init(params: dict) -> dict:
    return {"v": {k: _adafactor_moments(x.shape) for k, x in params.items()},
            "count": jnp.zeros((), jnp.int32)}


def adafactor_update(grads, state, params, *, lr):
    """A stacked expert leaf is one leaf, its two largest axes factored, as
    optax factors it."""
    t = state["count"] + 1
    new_p, new_v = {}, {}
    for k in params:
        new_p[k], new_v[k] = _adafactor_leaf(grads[k], params[k],
                                             state["v"][k], t, lr=lr)
    return new_p, {"v": new_v, "count": t}


FLAT_OPTIMIZERS = {"adafactor": (adafactor_init, adafactor_update)}


# --------------------------------------------------------------------------
# the first steps of a training run
# --------------------------------------------------------------------------

def make_step(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str = "f32"):
    """step(params, opt_state, text, image_ids) -> (params, opt_state, out).
    ``out``: the loss, the gradient's norm before clipping, every leaf's
    gradient norm as the optimizer gets it, and per expert layer the count of
    rows routed to each held expert."""
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
    first step's ``rows_per_expert`` (a list over expert layers) besides."""
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
