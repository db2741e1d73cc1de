"""The arithmetic of one chip's share of a Solar-Open2 stack (Kimi-delta
linear attention and gated grouped-query attention in a cyclic pattern,
routed + shared experts in every layer) as the DALL-E transformer:
parameters held, parameters a token's products touch, FLOPs a token, and the
flash attention kernels' operations and bytes. ``model`` is a configuration
file's ``model`` object; the block's sizes are under ``block`` by the
source's names."""

from __future__ import annotations

from benchmarks import arith

KDA_CHUNK = 64      # the program's chunk (ops/kda.py), a size of the algebra


def _sizes(model: dict) -> dict:
    b, d = model["block"], model["dim"]
    pattern = b["attention_layers"]
    kinds = [pattern[i % len(pattern)] for i in range(model["depth"])]
    inner = model["heads"] * model["dim_head"]
    kv = (b["num_key_value_heads"] or model["heads"]) * model["dim_head"]
    lin = b["linear_num_heads"] * b["linear_head_dim"]
    rank, taps = b["linear_gate_rank"], b["short_conv_kernel_size"]
    text_vocab = model["num_text_tokens"] + model["text_seq_len"]
    return {
        "d": d, "kinds": kinds,
        "n": model["text_seq_len"] + model["image_fmap_size"] ** 2,
        "held": model.get("experts_held") or b["n_routed_experts"],
        "text_vocab": text_vocab,
        "vocab": text_vocab + model["image_vocab_size"],
        # the matrices a token is multiplied by, per layer kind
        "gqa_gated": d * (inner + 2 * kv + inner) + inner * d,
        "kda": (3 * d * lin + 2 * (d * rank + rank * lin)
                + d * b["linear_num_heads"] + lin * d),
        # what a kda layer holds besides: three filters, A, the decay's
        # bias, the head norm's scale
        "kda_vectors": (3 * taps * lin + b["linear_num_heads"] + lin
                        + b["linear_head_dim"]),
        "expert": 3 * d * b["moe_intermediate_size"],
        "shared": 3 * d * b["moe_intermediate_size"] * b["n_shared_experts"],
        "router": d * b["n_routed_experts"],
    }


def held_param_count(model: dict) -> int:
    """Every parameter the program holds for this share: the two input
    tables, per layer the attention of its kind, the two norms, the router,
    the shared expert and the held experts, the final norm and the
    vocabulary head with its bias."""
    s = _sizes(model)
    layers = sum(s[k] + (s["kda_vectors"] if k == "kda" else 0)
                 + 2 * s["d"] + s["router"] + s["shared"]
                 + s["held"] * s["expert"] for k in s["kinds"])
    return (s["vocab"] * s["d"] + layers + s["d"]
            + s["d"] * s["vocab"] + s["vocab"])


def head_columns_per_token(model: dict) -> float:
    """Columns of the vocabulary head a position's logits are built from,
    averaged over a sequence: the loss takes a text position against the
    text vocabulary alone and an image position against the codebook alone
    (``models/dalle.py`` ``loss_segments``)."""
    s = _sizes(model)
    text, image = model["text_seq_len"], model["image_fmap_size"] ** 2
    return (text * s["text_vocab"]
            + image * model["image_vocab_size"]) / (text + image)


def product_params_per_token(model: dict, routed_pairs_per_token: float) -> float:
    """Parameters of the matrices a token is multiplied by on this chip: each
    layer's projections and gates, router and shared expert whole, one routed
    expert for each (token, expert) pair computed here
    (``routed_pairs_per_token``: the step's counted ``moe_rows_held`` over its
    tokens, summed over the layers), and the head by its segment's own
    columns. Tables, filters and norms are not products and do not count."""
    s = _sizes(model)
    return (sum(s[k] + s["router"] + s["shared"] for k in s["kinds"])
            + routed_pairs_per_token * s["expert"]
            + s["d"] * head_columns_per_token(model))


def delta_rule_flops_per_token(d_k: int, d_v: int,
                               chunk: int = KDA_CHUNK) -> float:
    """One head's forward products of the chunked delta rule a position, a
    triangular matrix counted as half: the two decayed Gram matrices
    (2 d_k chunk), the inverse of the unit triangular system (chunk^2 / 3)
    and its two products (chunk (d_k + d_v)), the state's four products
    (6 d_k d_v + chunk d_v)."""
    return (2.0 * d_k * chunk + chunk * chunk / 3.0 + chunk * (d_k + d_v)
            + 6.0 * d_k * d_v + chunk * d_v)


def train_flops_per_token(model: dict, routed_pairs_per_token: float) -> float:
    """6 x the parameters a token's products touch, plus 3 x the forward
    products of causal softmax attention over the causal half (4 h d n / 2 a
    position a softmax layer) and of the chunked delta rule (a linear layer).
    Recomputed operations do not count."""
    s, b = _sizes(model), model["block"]
    softmax = 2.0 * model["heads"] * model["dim_head"] * s["n"]
    linear = b["linear_num_heads"] * delta_rule_flops_per_token(
        b["linear_head_dim"], b["linear_head_dim"])
    own = sum(linear if k == "kda" else softmax for k in s["kinds"])
    return (6.0 * product_params_per_token(model, routed_pairs_per_token)
            + 3.0 * own)


def flash_attention_cost(model: dict, batch: int, *, backward: bool,
                         bytes_per_el: int = 2) -> dict:
    """Operations and bytes of one softmax layer's causal attention, from
    shapes: ``arith.causal_attention_cost``'s operations over all query
    heads; bytes of the queries and the output (and their gradients) over
    the query heads, of keys and values (and theirs) over the key/value
    heads alone, which is all a kernel has to move."""
    b, s = model["block"], _sizes(model)
    h, d = model["heads"], model["dim_head"]
    kv = b["num_key_value_heads"] or h
    cost = arith.causal_attention_cost(batch, h, s["n"], d, backward=backward,
                                       bytes_per_el=bytes_per_el)
    one = batch * s["n"] * d * bytes_per_el
    # forward: q, o of h heads, k, v of kv; backward: q, o, do, dq of h,
    # k, v, dk, dv of kv
    cost["bytes"] = float(one * ((4 * h + 4 * kv) if backward
                                 else (2 * h + 2 * kv)))
    return cost


def softmax_layers(model: dict) -> int:
    return sum(k == "gqa_gated" for k in _sizes(model)["kinds"])
