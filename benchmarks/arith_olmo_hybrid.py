"""The arithmetic of an Olmo-Hybrid stack (Gated DeltaNet linear attention
and QK-normed softmax attention in a cyclic pattern, a dense SwiGLU in every
layer, post-norm) as the DALL-E transformer: parameters held, FLOPs a token,
and the gated-delta core's operations and bytes. ``model`` is a
configuration file's ``model`` object; the block's sizes are under ``block``
by the source's names.

``kinds/train.py`` reads ``dalle_param_count``, ``rate`` and ``percentile``
of the module it is given as ``arith``: this one gives it those three."""

from __future__ import annotations

from benchmarks.arith import percentile, rate  # noqa: F401  (kinds/train.py)
from benchmarks.arith_hybrid import delta_rule_flops_per_token


def _sizes(model: dict) -> dict:
    b, d = model["block"], model["dim"]
    pattern = b["attention_layers"]
    h = b["linear_num_heads"]
    dk, dv = b["linear_key_head_dim"], b["linear_value_head_dim"]
    inner = model["heads"] * model["dim_head"]
    kv = (b["num_key_value_heads"] or model["heads"]) * model["dim_head"]
    text_vocab = model["num_text_tokens"] + model["text_seq_len"]
    return {
        "d": d, "h": h, "dk": dk, "dv": dv,
        "kinds": [pattern[i % len(pattern)] for i in range(model["depth"])],
        "n": model["text_seq_len"] + model["image_fmap_size"] ** 2,
        "text_vocab": text_vocab,
        "vocab": text_vocab + model["image_vocab_size"],
        # the matrices a token is multiplied by, per layer kind: q, k, v, o
        # of the softmax layer; q, k, v, the decay's and beta's projections,
        # the output gate and o of the linear layer
        "gqa": d * (inner + 2 * kv) + inner * d,
        "gdn": d * (2 * h * dk + h * dv) + 2 * d * h + 2 * d * h * dv,
        # what a layer holds besides: the q / k norms' scales; three
        # filters, A, dt_bias, the head norm's scale
        "gqa_vectors": inner + kv,
        "gdn_vectors": (b["short_conv_kernel_size"] * h * (2 * dk + dv)
                        + 2 * h + dv),
        "mlp": 3 * d * b["intermediate_size"],
    }


def held_param_count(model: dict) -> int:
    """Every parameter the program holds: the two input tables, per layer
    the attention of its kind, the two norms and the MLP, the final norm and
    the vocabulary head with its bias."""
    s = _sizes(model)
    layers = sum(s[k] + s[k + "_vectors"] + 2 * s["d"] + s["mlp"]
                 for k in s["kinds"])
    return (s["vocab"] * s["d"] + layers + s["d"]
            + s["d"] * s["vocab"] + s["vocab"])


dalle_param_count = held_param_count      # the name kinds/train.py reads


def head_columns_per_token(model: dict) -> float:
    """Columns of the vocabulary head a position's logits are built from,
    averaged over a sequence (``models/dalle.py`` ``loss_segments``: a text
    position against the text vocabulary, an image position against the
    codebook)."""
    s = _sizes(model)
    text, image = model["text_seq_len"], model["image_fmap_size"] ** 2
    return (text * s["text_vocab"]
            + image * model["image_vocab_size"]) / (text + image)


def train_flops_per_token(model: dict) -> float:
    """6 x the parameters a token's products touch (each layer's
    projections, gates and MLP, the head by its segment's own columns),
    plus 3 x the forward products of causal softmax attention over the
    causal half (2 h d n a position a softmax layer) and of the chunked
    delta rule (a linear layer). Recomputed operations do not count."""
    s = _sizes(model)
    products = (sum(s[k] + s["mlp"] for k in s["kinds"])
                + s["d"] * head_columns_per_token(model))
    softmax = 2.0 * model["heads"] * model["dim_head"] * s["n"]
    linear = s["h"] * delta_rule_flops_per_token(s["dk"], s["dv"])
    own = sum(linear if k == "gdn" else softmax for k in s["kinds"])
    return 6.0 * products + 3.0 * own


def linear_layers(model: dict) -> int:
    return sum(k == "gdn" for k in _sizes(model)["kinds"])


def gdn_core_cost(model: dict, batch: int) -> dict:
    """Operations and bytes of one linear layer's core (``ops/kda.py``'s
    ``kda_chunked`` with one decay a head), forward and backward, from
    shapes. Operations: the chunked delta rule's forward products a position
    and head (``delta_rule_flops_per_token``), three times (the backward
    twice the forward). Bytes: forward, the inputs read once (q, k of d_k
    and v of d_v in bfloat16, the decay's pre-activation in bfloat16, beta
    in float32) and the output (d_v, bfloat16) written; backward, the inputs
    and the output's gradient read and the inputs' gradients written. A
    forward that the backward pass recomputes adds time, not work."""
    s = _sizes(model)
    pairs = batch * s["n"] * s["h"]
    inputs = 2 * (2 * s["dk"] + s["dv"] + 1) + 4
    output = 2 * s["dv"]
    return {"flops": 3.0 * pairs * delta_rule_flops_per_token(s["dk"], s["dv"]),
            "bytes": float(pairs * (3 * inputs + 2 * output))}
