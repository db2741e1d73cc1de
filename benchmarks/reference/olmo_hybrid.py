"""Plain reference for an Olmo-Hybrid stack trained as the DALL-E transformer:
forward, loss, gradients, clipping and the optimizer in straightforward
jax.numpy and float32.

The layers are Olmo-Hybrid-7B's as its ``config.json`` gives them
(``model_type: olmo_hybrid``): three Gated DeltaNet linear-attention layers
(arXiv:2412.06464, the public flash-linear-attention layer's form: one decay
a head, keys of ``linear_key_head_dim``, values of ``linear_value_head_dim``,
causal convolutions, beta = 2 sigmoid, a SiLU output gate) to one softmax
layer (``heads`` query and ``num_key_value_heads`` key/value heads, RMSNorm
over the whole query and key projections, no positions, no gate); OLMo 2's
block (arXiv:2501.00656), which norms each layer's output and not its input:

    h   = x + RMSNorm(Mixer(x))
    out = h + RMSNorm(W_down(SiLU(W_gate h) * W_up h))

and a final RMSNorm ahead of the head. The embeddings of the two
vocabularies, the logits mask, the weighted cross-entropy and the optimizer
are DALL-E's and come from ``reference/dalle.py`` and
``reference/deepseek_v2.py``.

**The delta rule in its recurrent form**, one position at a time, exactly as
the layer is defined (per head, ``S`` of (d_k, d_v), ``S_0 = 0``, one decay
``exp(g_t)`` a head):

    S_t = exp(g_t) (I - b_t k_t k_t^T) S_{t-1} + b_t k_t v_t^T
    o_t = S_t^T q_t

(``reference/solar_open2.py``'s ``delta_rule``, its decay one number a head),
so that the comparison tests the program's chunked algebra and not a copy of
it. Softmax attention is plain masked scores. On the chip both are computed
in blocks so that float32 at 4352 positions fits: a layer a batch row at a
time, the recurrence as a scan over blocks of positions whose inner scan is
rematerialised, the scores one query head at a time; that changes no number.
It imports nothing of the program.

``precision`` is ``reference/dalle.py``'s: ``f32`` (the reference proper),
``bf16`` or ``fp8`` (the control). The decay, beta, the state and the
softmax stay float32 in every precision, as the configuration states.

The leaves are flat, ``{leaf name: array}`` (``l_q.3`` is layer 3's), and
``leaf_specs`` gives one layer's spec by the leaf's name, as
``kinds/train.py`` reads a reference.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference.dalle import (LOSS_IMG_WEIGHT, _factored,  # noqa: F401
                                        _quantize, chunk_logits,
                                        clip_by_global_norm, product,
                                        seed_key, token_ids)
from benchmarks.reference.deepseek_v2 import (FLAT_OPTIMIZERS, by_batch_row,
                                              layer_params, rms_norm, swiglu)
from benchmarks.reference.solar_open2 import (causal_conv, delta_rule,
                                              l2_normalise)

_BLOCK_INTS = ("num_key_value_heads", "linear_num_heads",
               "linear_key_head_dim", "linear_value_head_dim",
               "short_conv_kernel_size", "intermediate_size")


class Shapes(NamedTuple):
    """The sizes of one configuration file, as the reference needs them."""
    num_text_tokens: int
    text_seq_len: int
    dim: int
    depth: int
    heads: int
    dim_head: int
    image_vocab_size: int
    image_fmap_size: int
    attention_layers: Tuple[str, ...]
    num_key_value_heads: int
    linear_num_heads: int
    linear_key_head_dim: int
    linear_value_head_dim: int
    short_conv_kernel_size: int
    intermediate_size: int
    beta_max: float
    rms_norm_eps: float

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        """``model``: the program's keyword arguments, the block's own sizes
        under ``block`` by the source's names."""
        block = model["block"]
        if (block.get("positions") != "none"
                or block.get("norm_position") != "post"
                or block.get("feed_forward") != "swiglu"
                or not block.get("qk_norm")):
            raise ValueError("this reference is OLMo 2's post-norm block "
                             "with QK-norm and SwiGLU, and adds no positions")
        top = {k: int(model[k]) for k in (
            "num_text_tokens", "text_seq_len", "dim", "depth", "heads",
            "dim_head", "image_vocab_size", "image_fmap_size")}
        return cls(**top, attention_layers=tuple(block["attention_layers"]),
                   **{k: int(block[k]) for k in _BLOCK_INTS},
                   beta_max=float(block.get("kda_beta_max", 2.0)),
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
# weights from the seed
# --------------------------------------------------------------------------

def layer_leaf_specs(s: Shapes, kind: str) -> dict:
    """name -> (shape, how, value) of one layer's leaves. ``normal``: N(0,
    value^2), value the fan-in ^ -0.5; ``const``: ``value``; ``uniform``:
    U(-value, value); ``a_log``: log U(0, 16); ``dt_bias``: softplus^-1 of a
    step drawn log-uniformly from (1e-3, 1e-1) (the last two as the public
    Gated DeltaNet layer initialises them)."""
    d, f = s.dim, s.intermediate_size
    if kind == "gqa":
        inner, kv = s.heads * s.dim_head, s.num_key_value_heads * s.dim_head
        specs = {"s_q": ((d, inner), "normal", d ** -0.5),
                 "s_k": ((d, kv), "normal", d ** -0.5),
                 "s_v": ((d, kv), "normal", d ** -0.5),
                 "q_norm_g": ((inner,), "const", 1.0),
                 "k_norm_g": ((kv,), "const", 1.0),
                 "s_o": ((inner, d), "normal", inner ** -0.5)}
    elif kind == "gdn":
        h, taps = s.linear_num_heads, s.short_conv_kernel_size
        ki, vi = h * s.linear_key_head_dim, h * s.linear_value_head_dim
        specs = {"l_q": ((d, ki), "normal", d ** -0.5),
                 "l_k": ((d, ki), "normal", d ** -0.5),
                 "l_v": ((d, vi), "normal", d ** -0.5),
                 "conv_q": ((taps, ki), "uniform", taps ** -0.5),
                 "conv_k": ((taps, ki), "uniform", taps ** -0.5),
                 "conv_v": ((taps, vi), "uniform", taps ** -0.5),
                 "w_a": ((d, h), "normal", d ** -0.5),
                 "a_log": ((h,), "a_log", None),
                 "dt_bias": ((h,), "dt_bias", None),
                 "w_b": ((d, h), "normal", d ** -0.5),
                 "w_g": ((d, vi), "normal", d ** -0.5),
                 "o_norm_g": ((s.linear_value_head_dim,), "const", 1.0),
                 "l_o": ((vi, d), "normal", vi ** -0.5)}
    else:
        raise ValueError(f"no layer kind {kind!r} in this reference")
    specs.update({"attn_norm_g": ((d,), "const", 1.0),
                  "ff_norm_g": ((d,), "const", 1.0),
                  "w_gate": ((d, f), "normal", d ** -0.5),
                  "w_up": ((d, f), "normal", d ** -0.5),
                  "w_down": ((f, d), "normal", f ** -0.5)})
    return specs


def top_leaf_specs(s: Shapes) -> dict:
    d = s.dim
    return {"text_emb": ((s.text_vocab, d), "normal", d ** -0.5),
            "image_emb": ((s.image_vocab_size, d), "normal", d ** -0.5),
            "final_norm_g": ((d,), "const", 1.0),
            "w_logits": ((d, s.total_vocab), "normal", d ** -0.5),
            "b_logits": ((s.total_vocab,), "const", 0.0)}


def leaf_specs(s: Shapes) -> dict:
    """Every leaf's spec by its name (a layer's leaf by its name without the
    layer): one layer's shape."""
    out = dict(top_leaf_specs(s))
    for kind in dict.fromkeys(s.attention_layers):
        out.update(layer_leaf_specs(s, kind))
    return out


def leaf_names(s: Shapes) -> list:
    """Every leaf the program holds, by the benchmark's own names."""
    return list(top_leaf_specs(s)) + [
        f"{name}.{l}" for l in range(s.depth)
        for name in layer_leaf_specs(s, s.kind(l))]


_NAMES = sorted({
    "text_emb", "image_emb", "final_norm_g", "w_logits", "b_logits",
    "attn_norm_g", "ff_norm_g", "w_gate", "w_up", "w_down", "s_q", "s_k",
    "s_v", "q_norm_g", "k_norm_g", "s_o", "l_q", "l_k", "l_v", "conv_q",
    "conv_k", "conv_v", "w_a", "a_log", "dt_bias", "w_b", "w_g", "o_norm_g",
    "l_o"})
LEAF_IDS = {name: i for i, name in enumerate(_NAMES)}


def init_leaf(key, name: str, spec, layer):
    """One layer's leaf (or one unstacked leaf, ``layer`` 0). ``layer`` may
    be traced."""
    shape, how, value = spec
    if how == "const":
        return jnp.full(shape, value, jnp.float32)
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    if how == "normal":
        return jax.random.normal(k, shape, jnp.float32) * value
    if how == "uniform":
        return jax.random.uniform(k, shape, jnp.float32, -value, value)
    if how == "a_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 0.0, 16.0))
    if how == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    raise ValueError(how)


def init_params(s: Shapes, key) -> dict:
    specs = leaf_specs(s)
    out = {}
    for full in leaf_names(s):
        name, _, layer = full.partition(".")
        out[full] = init_leaf(key, name, specs[name], int(layer or 0))
    return out


# --------------------------------------------------------------------------
# the layers
# --------------------------------------------------------------------------

def gdn(s: Shapes, y, lp: dict, precision: str):
    """Gated DeltaNet of ``y`` (b, n, dim)."""
    b, n, _ = y.shape
    h, dk, dv = (s.linear_num_heads, s.linear_key_head_dim,
                 s.linear_value_head_dim)

    def mixed(w, conv, width):
        t = _quantize(product("bnd,de->bne", y, lp[w], precision), precision)
        t = _quantize(causal_conv(t, lp[conv]), precision)
        return _quantize(jax.nn.silu(t), precision).reshape(b, n, h, width)

    q, k = mixed("l_q", "conv_q", dk), mixed("l_k", "conv_k", dk)
    v = mixed("l_v", "conv_v", dv)
    q = _quantize(l2_normalise(q) * dk ** -0.5, precision)
    k = _quantize(l2_normalise(k), precision)
    a = _quantize(product("bnd,dh->bnh", y, lp["w_a"], precision), precision)
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(a + lp["dt_bias"])
    beta = s.beta_max * jax.nn.sigmoid(_quantize(
        product("bnd,dh->bnh", y, lp["w_b"], precision), precision))
    # one decay a head: the same number for each of the head's key channels
    o = _quantize(delta_rule(q, k, v, g[..., None], beta, precision),
                  precision)
    o = _quantize(rms_norm(o, lp["o_norm_g"], s.rms_norm_eps), precision)
    gate = jax.nn.silu(_quantize(
        product("bnd,de->bne", y, lp["w_g"], precision), precision))
    o = _quantize(o.reshape(b, n, h * dv) * gate, precision)
    return product("bne,ed->bnd", o, lp["l_o"], precision)


def gqa(s: Shapes, y, lp: dict, precision: str):
    """Causal softmax attention of ``heads`` query heads over
    ``num_key_value_heads`` key and value heads, the query and key
    projections RMS-normed over their whole width, no positions, no gate."""
    b, n, _ = y.shape
    h, kv, d = s.heads, s.num_key_value_heads, s.dim_head
    per = h // kv

    def heads(w, count, norm=None):
        t = _quantize(product("bnd,de->bne", y, lp[w], precision), precision)
        if norm is not None:
            t = _quantize(rms_norm(t, lp[norm], s.rms_norm_eps), precision)
        return t.reshape(b, n, count, d)

    # (b, kv, per, ...): a batch row, a group's keys and values and one
    # query head at a time
    q = heads("s_q", h, "q_norm_g").reshape(b, n, kv, per, d).transpose(
        0, 2, 3, 1, 4)
    k = heads("s_k", kv, "k_norm_g").transpose(0, 2, 1, 3)
    v = heads("s_v", kv).transpose(0, 2, 1, 3)
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
    return product("bne,ed->bnd", out, lp["s_o"], precision)


def block(s: Shapes, x, lp: dict, layer: int, precision: str):
    """One layer, post-norm: x + norm(attention(x)), then h + norm(MLP(h)),
    a batch row at a time."""
    attention = gdn if s.kind(layer) == "gdn" else gqa
    eps = s.rms_norm_eps
    out = by_batch_row(lambda r: attention(s, r[None], lp, precision)[0], x)
    out = _quantize(rms_norm(_quantize(out, precision), lp["attn_norm_g"],
                             eps), precision)
    x = _quantize(x + out, precision)
    out = by_batch_row(lambda r: swiglu(r, lp["w_gate"], lp["w_up"],
                                        lp["w_down"], precision), x)
    out = _quantize(rms_norm(_quantize(out, precision), lp["ff_norm_g"], eps),
                    precision)
    return _quantize(x + out, precision)


def hidden_states(s: Shapes, params, text, image_ids, precision: str):
    """(final hidden states, labels). No positional term enters anywhere."""
    text_in, labels = token_ids(s, text, image_ids)
    x = jnp.concatenate([jnp.take(params["text_emb"], text_in, 0),
                         jnp.take(params["image_emb"], image_ids, 0)], 1)
    x = _quantize(x[:, :s.seq_len], precision)
    for l in range(s.depth):
        x = jax.checkpoint(lambda x, lp, l=l: block(s, x, lp, l, precision))(
            x, layer_params(params, l))
    return rms_norm(x, params["final_norm_g"], s.rms_norm_eps), labels


def loss_fn(s: Shapes, params, text, image_ids, precision: str = "f32",
            chunk: int = 128):
    """(mean text CE + 7 * mean image CE) / 8, DALL-E's."""
    x, labels = hidden_states(s, params, text, image_ids, precision)
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
    return (text_ce + LOSS_IMG_WEIGHT * image_ce) / (LOSS_IMG_WEIGHT + 1.0)


# --------------------------------------------------------------------------
# the first steps of a training run (reference/dalle.py's, this loss)
# --------------------------------------------------------------------------

def make_step(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str = "f32"):
    """step(params, opt_state, text, image_ids) -> (params, opt_state, out).
    ``out``: the loss, the gradient's norm before clipping and every leaf's
    gradient norm as the optimizer gets it."""
    _, update = FLAT_OPTIMIZERS[optimizer]

    def step(params, opt_state, text, image_ids):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(s, p, text, image_ids, precision))(params)
        grads, norm = clip_by_global_norm(grads, clip)
        leaf_norms = {k: jnp.sqrt(jnp.sum(g * g)) for k, g in grads.items()}
        params, opt_state = update(grads, opt_state, params, lr=lr)
        return params, opt_state, {"loss": loss, "grad_norm": norm,
                                   "leaf_grad_norms": leaf_norms}

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
    """``reference/dalle.py``'s ``first_steps`` for this stack."""
    key = seed_key(seed)
    init, step, change = _programs(
        s, recipe["optimizer"], float(recipe.get("learning_rate", 3e-4)),
        float(recipe.get("grad_clip_norm", 0.0)), precision)
    params = init(key)
    opt_state = FLAT_OPTIMIZERS[recipe["optimizer"]][0](params)
    losses, norms, first = [], [], None
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
    del opt_state
    delta = {k: float(v)
             for k, v in jax.device_get(change(params, key)).items()}
    del params
    return {"loss": losses, "grad_norm": norms, "leaf_grad_norms": first,
            "leaf_change_norms": delta}
