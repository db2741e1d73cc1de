"""Plain reference for the DALL-E training step: forward, loss, gradients,
clipping and the optimizer, in straightforward jax.numpy and float32.

It imports nothing of the program and takes nothing the program made: the
weights come from ``init_params`` (from the seed), the rotary table is built
here, the optimizers are written out below. It follows DALLE-pytorch's
``DALLE.forward`` (dalle_pytorch.py:560-653) and ``Transformer``
(transformer.py:204-328) at the settings the configurations state (full
causal attention, rotary embeddings, LayerScale, GEGLU, no token shift, no
sandwich norm, separate input and output embeddings), with two departures,
both the program's own definition of the model: GELU in its tanh form and a
LayerNorm epsilon of 1e-6 (torch: erf form, 1e-5).

Layers are held stacked (a leading axis of ``depth``) and run under
``lax.scan`` with one checkpoint per layer, and the vocabulary head and
cross-entropy run in sequence chunks, so three steps at 1.4B parameters fit
one chip once the program's state is freed.

``precision`` names the compute type: ``f32`` (the reference proper: float32
at ``highest``), ``bf16`` (what the configurations state for compute) or
``fp8`` (e4m3 forward, e5m2 backward, plain casts as the program's bfloat16
is: training's control, one step below bf16). As in the program, the compute
type is that of the matrix products' operands and of the activations
between them (the residual stream, the norms' outputs, queries, keys,
values, attention weights, the feed-forward's hidden layer): each is rounded
to it on the way forward, and what flows back through it is rounded to it on
the way back. Sums inside a product, the norms' and the softmax's statistics,
the logits and the loss stay float32, and so do the weights' masters, the
leaves' gradients and the optimizer.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-6
MASK_VALUE = -1e9
LOSS_IMG_WEIGHT = 7.0


class Shapes(NamedTuple):
    """The sizes of one configuration file, as the reference needs them."""
    num_text_tokens: int
    text_seq_len: int
    dim: int
    depth: int
    heads: int
    dim_head: int
    ff_mult: int
    image_vocab_size: int
    image_fmap_size: int

    @classmethod
    def from_model(cls, model: dict) -> "Shapes":
        return cls(**{k: int(model.get("ff_mult", 4)) if k == "ff_mult"
                      else int(model[k]) for k in cls._fields})

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size ** 2

    @property
    def seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def text_vocab(self) -> int:
        # one pad id of its own for every text position (dalle_pytorch.py:370)
        return self.num_text_tokens + self.text_seq_len

    @property
    def total_vocab(self) -> int:
        return self.text_vocab + self.image_vocab_size


# --------------------------------------------------------------------------
# weights from the seed
# --------------------------------------------------------------------------

def leaf_specs(s: Shapes) -> dict:
    """name -> (shape of one layer's leaf or of the whole leaf, stacked?,
    kind, value). ``normal`` leaves are N(0, value**2); ``const`` leaves hold
    ``value``; ``layerscale`` leaves hold the per-layer LayerScale init."""
    d, inner, ff = s.dim, s.heads * s.dim_head, s.dim * s.ff_mult
    top = {
        "text_emb": ((s.text_vocab, d), "normal", d ** -0.5),
        "image_emb": ((s.image_vocab_size, d), "normal", d ** -0.5),
        "final_norm_g": ((d,), "const", 1.0),
        "final_norm_b": ((d,), "const", 0.0),
        "w_logits": ((d, s.total_vocab), "normal", d ** -0.5),
        "b_logits": ((s.total_vocab,), "const", 0.0),
    }
    layer = {
        "attn_norm_g": ((d,), "const", 1.0),
        "attn_norm_b": ((d,), "const", 0.0),
        "w_qkv": ((d, 3 * inner), "normal", d ** -0.5),
        "w_out": ((inner, d), "normal", inner ** -0.5),
        "b_out": ((d,), "const", 0.0),
        "attn_scale": ((d,), "layerscale", None),
        "ff_norm_g": ((d,), "const", 1.0),
        "ff_norm_b": ((d,), "const", 0.0),
        "w1": ((d, 2 * ff), "normal", d ** -0.5),
        "b1": ((2 * ff,), "const", 0.0),
        "w2": ((ff, d), "normal", ff ** -0.5),
        "b2": ((d,), "const", 0.0),
        "ff_scale": ((d,), "layerscale", None),
    }
    out = {k: (shape, False, kind, v) for k, (shape, kind, v) in top.items()}
    out.update({k: (shape, True, kind, v)
                for k, (shape, kind, v) in layer.items()})
    return out


LEAF_IDS = {name: i for i, name in enumerate(sorted(leaf_specs(
    Shapes(1, 1, 1, 1, 1, 1, 1, 1, 1))))}


def seed_key(seed: int):
    """A key from any whole number up to 2**32 and beyond: the seed's two
    halves are folded in, so no 32-bit conversion ever sees the whole."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFF)
    return jax.random.fold_in(jax.random.fold_in(key, (seed >> 16) & 0xFFFF),
                              (seed >> 32) & 0xFFFF)


def init_leaf(key, name: str, spec, layer):
    """One layer's leaf (or one unstacked leaf). ``layer`` may be traced."""
    shape, _, kind, value = spec
    if kind == "normal":
        k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
        return jax.random.normal(k, shape, jnp.float32) * value
    if kind == "layerscale":
        # transformer.py:74-83: 0.1 up to depth 18, 1e-5 to 24, 1e-6 beyond
        layer = jnp.asarray(layer)
        eps = jnp.where(layer < 18, 0.1, jnp.where(layer < 24, 1e-5, 1e-6))
        return jnp.full(shape, 1.0, jnp.float32) * eps.astype(jnp.float32)
    return jnp.full(shape, value, jnp.float32)


def init_params(s: Shapes, key) -> dict:
    """The whole tree, layers stacked. Trace it inside one ``jax.jit``."""
    params, layers = {}, {}
    for name, spec in leaf_specs(s).items():
        if spec[1]:
            layers[name] = jax.vmap(
                lambda l, name=name, spec=spec: init_leaf(key, name, spec, l)
            )(jnp.arange(s.depth))
        else:
            params[name] = init_leaf(key, name, spec, 0)
    params["layers"] = layers
    return params


def leaf_names(s: Shapes) -> list:
    """Every leaf the program holds, by the benchmark's own names: ``w_qkv.3``
    is layer 3's slice of the stacked ``w_qkv``."""
    names = []
    for name, spec in sorted(leaf_specs(s).items()):
        if spec[1]:
            names += [f"{name}.{l}" for l in range(s.depth)]
        else:
            names.append(name)
    return names


def per_leaf(tree: dict, fn) -> dict:
    """``fn`` of every leaf of a reference tree -> {leaf name: scalar};
    stacked leaves give one number per layer."""
    out = {}
    for name, x in tree.items():
        if name == "layers":
            continue
        out[name] = fn(x)
    for name, x in tree["layers"].items():
        vals = jax.vmap(fn)(x)
        for l in range(x.shape[0]):
            out[f"{name}.{l}"] = vals[l]
    return out


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def rotary_table(s: Shapes) -> np.ndarray:
    """DALLE-pytorch transformer.py:302-328 with rotary-embedding-torch's
    ``lang`` and ``pixel`` frequencies: a third of each head's features turn
    with the text position, two thirds with the image row and column. Text
    sits at -10 on both image axes, image tokens at text position 8192."""
    rot = s.dim_head // 3
    half = rot // 2
    text_len, fmap = s.text_seq_len + 1, s.image_fmap_size

    def table(pos, freqs):
        return np.repeat(np.outer(np.asarray(pos, np.float32), freqs), 2, -1)

    lang = 1.0 / (10000.0 ** (np.arange(0, rot, 2)[:half].astype(np.float32)
                               / rot))
    pixel = np.linspace(1.0, 10.0 / 2, half).astype(np.float32) * math.pi
    band_text = np.concatenate([table(np.arange(text_len), lang),
                                table(np.full(fmap * fmap, 8192.0), lang)])
    axial = table(np.linspace(-1.0, 1.0, fmap), pixel)
    rows = np.repeat(axial[:, None], fmap, 1).reshape(fmap * fmap, -1)
    cols = np.repeat(axial[None, :], fmap, 0).reshape(fmap * fmap, -1)
    off = table(np.full(text_len, -10.0), pixel)
    band_image = np.concatenate([np.concatenate([off, off], -1),
                                 np.concatenate([rows, cols], -1)])
    return np.concatenate([band_text, band_image], -1).astype(np.float32)


def rotate(table, t):
    """Turn adjacent feature pairs of the leading ``table.shape[-1]``
    features of ``t`` (…, n, dim_head) by the table's angles."""
    r = table.shape[-1]
    x, rest = t[..., :r], t[..., r:]
    pairs = x.reshape(*x.shape[:-1], r // 2, 2)
    turned = jnp.stack([-pairs[..., 1], pairs[..., 0]], -1).reshape(x.shape)
    return jnp.concatenate([x * jnp.cos(table) + turned * jnp.sin(table),
                            rest], -1)


def _round(x, precision: str, backward: bool):
    """``x`` rounded to ``precision`` by a plain cast, with no scale for a
    tensor or for the loss, as the program's bfloat16 is. fp8 is the usual
    pair: e4m3 forward and e5m2 for what flows backward.
    ``lax.reduce_precision`` and not a pair of casts: XLA may drop a cast to a
    narrower type and back (its allow-excess-precision default does on the
    TPU), and the control would then read as the reference itself (my chip
    run, PR 24)."""
    if precision == "bf16":
        return jax.lax.reduce_precision(x, 8, 7)
    if precision == "fp8":
        return (jax.lax.reduce_precision(x, 5, 2) if backward
                else jax.lax.reduce_precision(x, 4, 3))
    raise ValueError(f"unknown precision {precision!r}")


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _rounded(x, precision: str):
    return _round(x, precision, backward=False)


_rounded.defvjp(
    lambda x, precision: (_round(x, precision, False), None),
    lambda precision, _, g: (_round(g, precision, True),))


def _quantize(x, precision: str):
    """``x`` as ``precision`` holds it, and what flows back through it
    rounded as that precision holds a gradient: the program computes both
    passes in its compute type, and so does the reference put in its place."""
    if precision == "f32":
        return x
    return _rounded(x, precision)


def product(spec: str, a, b, precision: str):
    """einsum with both operands taken in ``precision``, summed in float32."""
    return jnp.einsum(spec, _quantize(a, precision), _quantize(b, precision),
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, g, b):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def block(s: Shapes, table, x, lp: dict, precision: str):
    """One layer: x + scale * attn(norm(x)), then the same with GEGLU.
    Every activation that the program would hold in its compute type is
    rounded to ``precision`` (a product rounds its own operands)."""
    b, n, _ = x.shape
    h, dh = s.heads, s.dim_head
    y = layer_norm(x, lp["attn_norm_g"], lp["attn_norm_b"])
    qkv = product("bnd,de->bne", y, lp["w_qkv"], precision)
    q, k, v = (t.reshape(b, n, h, dh).transpose(0, 2, 1, 3)
               for t in jnp.split(_quantize(qkv, precision), 3, -1))
    # rotary-embedding-torch as DALLE-pytorch calls it turns v as well
    q, k, v = (rotate(table[:n], t) for t in (q, k, v))
    dots = product("bhid,bhjd->bhij", q * dh ** -0.5, k, precision)
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]
    attn = jax.nn.softmax(jnp.where(causal, dots, -jnp.inf), -1)
    out = product("bhij,bhjd->bhid", attn, v, precision)
    out = out.transpose(0, 2, 1, 3).reshape(b, n, h * dh)
    out = product("bne,ed->bnd", out, lp["w_out"], precision) + lp["b_out"]
    x = _quantize(x + _quantize(out, precision) * lp["attn_scale"], precision)
    y = layer_norm(x, lp["ff_norm_g"], lp["ff_norm_b"])
    hid = product("bnd,df->bnf", y, lp["w1"], precision) + lp["b1"]
    val, gate = jnp.split(_quantize(hid, precision), 2, -1)
    out = product("bnf,fd->bnd", val * gelu_tanh(gate), lp["w2"],
                  precision) + lp["b2"]
    return _quantize(x + _quantize(out, precision) * lp["ff_scale"],
                     precision)


def token_ids(s: Shapes, text, image_ids):
    """(input ids in the joint table, labels): pads become each position's
    own id, <bos> = 0 goes in front, the last image token is input to
    nothing (dalle_pytorch.py:578-613)."""
    pads = jnp.arange(s.text_seq_len) + s.num_text_tokens
    text = jnp.where(text == 0, pads[None], text)
    labels = jnp.concatenate([text, image_ids + s.text_vocab], 1)
    return jnp.pad(text, ((0, 0), (1, 0))), labels


def hidden_states(s: Shapes, params, text, image_ids, precision: str):
    text_in, labels = token_ids(s, text, image_ids)
    x = jnp.concatenate([jnp.take(params["text_emb"], text_in, 0),
                         jnp.take(params["image_emb"], image_ids, 0)], 1)
    x = _quantize(x[:, :s.seq_len], precision)
    table = jnp.asarray(rotary_table(s))

    @jax.checkpoint
    def body(x, lp):
        return block(s, table, x, lp, precision), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return layer_norm(x, params["final_norm_g"], params["final_norm_b"]), labels


def chunk_logits(s: Shapes, params, x_c, start, precision: str):
    """Logits of a chunk of positions starting at ``start``: text positions
    may only say text ids, image positions only image ids
    (dalle_pytorch.py:428-439)."""
    logits = product("bnd,dv->bnv", x_c, params["w_logits"],
                     precision) + params["b_logits"]
    pos = start + jnp.arange(x_c.shape[1])
    is_image_pos = (pos >= s.text_seq_len)[:, None]
    is_image_id = (jnp.arange(s.total_vocab) >= s.text_vocab)[None, :]
    return jnp.where(is_image_pos == is_image_id, logits, MASK_VALUE)


def loss_fn(s: Shapes, params, text, image_ids, precision: str = "f32",
            chunk: int = 128):
    """(mean text CE + 7 * mean image CE) / 8 (dalle_pytorch.py:649-653)."""
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
# optimizers, written out
# --------------------------------------------------------------------------

def _leaves_sq(tree: dict):
    return sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree))


def clip_by_global_norm(grads: dict, max_norm: float):
    norm = jnp.sqrt(_leaves_sq(grads))
    if not max_norm:
        return grads, norm
    factor = jnp.where(norm < max_norm, 1.0, max_norm / norm)
    return jax.tree.map(lambda g: g * factor, grads), norm


def adam_init(params: dict) -> dict:
    zeros = jax.tree.map(jnp.zeros_like, params)
    return {"mu": zeros, "nu": jax.tree.map(jnp.zeros_like, params),
            "count": jnp.zeros((), jnp.int32)}


def adam_update(grads, state, params, *, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Kingma & Ba, with bias correction."""
    t = state["count"] + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state["mu"], grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, state["nu"],
                      grads)
    c1 = 1 - b1 ** t.astype(jnp.float32)
    c2 = 1 - b2 ** t.astype(jnp.float32)
    new = jax.tree.map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps),
        params, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": t}


def _factored(shape, min_dim: int = 128):
    """The two largest axes, if both reach ``min_dim``; else None."""
    if len(shape) < 2:
        return None
    order = np.argsort(shape)
    if shape[order[-2]] < min_dim:
        return None
    return int(order[-2]), int(order[-1])


def _adafactor_leaf(g, p, v, t, *, lr, decay_exp=0.8, eps=1e-30,
                    clip_rms=1.0, min_scale=1e-3):
    """Shazeer & Stern 2018 for one leaf, no momentum, no weight decay:
    factored second moment for matrices, update clipped to unit RMS, step
    scaled by the leaf's own RMS. Returns (new leaf, new moments)."""
    decay = 1.0 - t.astype(jnp.float32) ** -decay_exp
    gsq = g * g + eps
    dims = _factored(g.shape)
    if dims is not None:
        d1, d0 = dims
        row = decay * v["row"] + (1 - decay) * jnp.mean(gsq, d0)
        col = decay * v["col"] + (1 - decay) * jnp.mean(gsq, d1)
        reduced = d1 - 1 if d1 > d0 else d1
        row_f = (row / jnp.mean(row, reduced, keepdims=True)) ** -0.5
        u = g * jnp.expand_dims(row_f, d0) * jnp.expand_dims(col ** -0.5, d1)
        new_v = {"row": row, "col": col}
    else:
        full = decay * v["full"] + (1 - decay) * gsq
        u = g * full ** -0.5
        new_v = {"full": full}
    u = u / jnp.maximum(1.0, jnp.sqrt(jnp.mean(u * u)) / clip_rms)
    scale = jnp.maximum(jnp.sqrt(jnp.mean(p * p)), min_scale)
    return p - lr * scale * u, new_v


def _adafactor_moments(shape):
    dims = _factored(shape)
    if dims is None:
        return {"full": jnp.zeros(shape, jnp.float32)}
    d1, d0 = dims
    return {"row": jnp.zeros(tuple(n for i, n in enumerate(shape) if i != d0),
                             jnp.float32),
            "col": jnp.zeros(tuple(n for i, n in enumerate(shape) if i != d1),
                             jnp.float32)}


def adafactor_init(params: dict) -> dict:
    v = {k: _adafactor_moments(x.shape) for k, x in params.items()
         if k != "layers"}
    v["layers"] = {k: jax.vmap(lambda _, s=x.shape[1:]: _adafactor_moments(s))(
        jnp.arange(x.shape[0])) for k, x in params["layers"].items()}
    return {"v": v, "count": jnp.zeros((), jnp.int32)}


def adafactor_update(grads, state, params, *, lr):
    """Every layer's slice of a stacked leaf is a leaf of its own."""
    t = state["count"] + 1
    new_p, new_v = {}, {}
    for k in params:
        if k == "layers":
            continue
        new_p[k], new_v[k] = _adafactor_leaf(grads[k], params[k],
                                             state["v"][k], t, lr=lr)
    new_p["layers"], new_v["layers"] = {}, {}
    for k in params["layers"]:
        new_p["layers"][k], new_v["layers"][k] = jax.vmap(
            lambda g, p, v: _adafactor_leaf(g, p, v, t, lr=lr))(
            grads["layers"][k], params["layers"][k], state["v"]["layers"][k])
    return new_p, {"v": new_v, "count": t}


OPTIMIZERS = {"adam": (adam_init, adam_update),
              "adafactor": (adafactor_init, adafactor_update)}


# --------------------------------------------------------------------------
# the first steps of a training run
# --------------------------------------------------------------------------

def make_step(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str = "f32"):
    """step(params, opt_state, text, image_ids) -> (params, opt_state, out).
    ``out``: the loss, the gradient's norm before clipping, and every leaf's
    gradient norm as the optimizer gets it (after clipping)."""
    _, update = OPTIMIZERS[optimizer]

    def step(params, opt_state, text, image_ids):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(s, p, text, image_ids, precision))(params)
        grads, norm = clip_by_global_norm(grads, clip)
        leaf_norms = per_leaf(grads, lambda g: jnp.sqrt(jnp.sum(g * g)))
        params, opt_state = update(grads, opt_state, params, lr=lr)
        return params, opt_state, {"loss": loss, "grad_norm": norm,
                                   "leaf_grad_norms": leaf_norms}

    return step


@functools.lru_cache(maxsize=None)
def _programs(s: Shapes, optimizer: str, lr: float, clip: float,
              precision: str):
    """(init, step, change) jitted once for a configuration, a recipe and a
    precision: the seed's key is an argument, so every seed runs them."""
    def change(params, key):
        start = init_params(s, key)
        delta = jax.tree.map(lambda a, b: a - b, params, start)
        return per_leaf(delta, lambda d: jnp.sqrt(jnp.sum(d * d)))

    return (jax.jit(lambda key: init_params(s, key)),
            jax.jit(make_step(s, optimizer, lr, clip, precision),
                    donate_argnums=(0, 1)),
            jax.jit(change))


def first_steps(s: Shapes, recipe: dict, seed: int, batches, *,
                precision: str = "f32", rows=None) -> dict:
    """Run the reference through ``batches`` (a list of (text, image_ids))
    from the seed's weights. Returns per-step ``loss`` and ``grad_norm``,
    the first step's per-leaf gradient norms, and per-leaf norms of the
    parameters' change over all the steps. ``rows`` (a slice) plants the
    fault "half of the batch left out" into the reference."""
    key = seed_key(seed)
    init, step, change = _programs(
        s, recipe["optimizer"], float(recipe.get("learning_rate", 3e-4)),
        float(recipe.get("grad_clip_norm", 0.0)), precision)
    params = init(key)
    opt_state = OPTIMIZERS[recipe["optimizer"]][0](params)
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
