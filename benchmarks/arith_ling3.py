"""The arithmetic of one chip's share of a Ling-3.0-flash stack (Kimi-delta
linear attention and latent attention in a cyclic pattern, a leading dense
layer, routed + shared experts behind a biased router elsewhere, a
multi-token-prediction block) as the DALL-E transformer: parameters held,
parameters a token's products touch, FLOPs a token, and the flash attention
kernels' operations and bytes at latent attention's two head widths.
``model`` is a configuration file's ``model`` object; the block's sizes are
under ``block`` by the source's names."""

from __future__ import annotations

from benchmarks.arith_hybrid import delta_rule_flops_per_token


def _sizes(model: dict) -> dict:
    b, d = model["block"], model["dim"]
    pattern = b["attention_layers"]
    kinds = [pattern[i % len(pattern)] for i in range(model["depth"])]
    h = model["heads"]
    qk, dv = b["qk_nope_head_dim"] + b["qk_rope_head_dim"], b["v_head_dim"]
    lin = b["linear_num_heads"] * b["linear_head_dim"]
    taps = b["short_conv_kernel_size"]
    text_vocab = model["num_text_tokens"] + model["text_seq_len"]
    text, image = model["text_seq_len"], model["image_fmap_size"] ** 2
    latent = (d * h * qk + d * (b["kv_lora_rank"] + b["qk_rope_head_dim"])
              + b["kv_lora_rank"] * h * (b["qk_nope_head_dim"] + dv)
              + d * h + h * dv * d)
    return {
        "d": d, "kinds": kinds, "h": h, "qk": qk, "dv": dv,
        "text": text, "image": image, "n": text + image,
        "held": model.get("experts_held") or b["n_routed_experts"],
        "dense_layers": b["first_dense_layers"], "mtp": model["mtp_depth"],
        "text_vocab": text_vocab,
        "vocab": text_vocab + model["image_vocab_size"],
        # the matrices a token is multiplied by, per layer kind
        "mla": latent,
        "kda": (3 * d * lin + 2 * d * lin + d * b["linear_num_heads"]
                + lin * d),
        # what a layer holds besides: the latent's norm and the two head
        # norms; three filters, A, the decay's bias, the head norm's scale
        "mla_vectors": b["kv_lora_rank"] + 2 * qk,
        "kda_vectors": (3 * taps * lin + b["linear_num_heads"] + lin
                        + b["linear_head_dim"]),
        "dense": 3 * d * b["intermediate_size"],
        "expert": 3 * d * b["moe_intermediate_size"],
        "shared": 3 * d * b["moe_intermediate_size"] * b["n_shared_experts"],
        "router": d * b["n_routed_experts"],
        "router_bias": b["n_routed_experts"],
        "merge": 2 * d * d,
    }


def _routed(s: dict) -> int:
    """What a routed layer holds besides its attention and norms."""
    return (s["router"] + s["router_bias"] + s["shared"]
            + s["held"] * s["expert"])


def held_param_count(model: dict) -> int:
    """Every parameter the program holds for this share: the two input
    tables, per layer the attention of its kind, the two norms and the dense
    MLP or the router, its bias, the shared expert and the held experts; the
    final norm and the vocabulary head with its bias; the
    multi-token-prediction block (two input norms, the merge, a latent
    layer, a routed layer, their two norms, its final norm)."""
    s = _sizes(model)
    layers = sum(s[k] + s[f"{k}_vectors"] + 2 * s["d"]
                 + (s["dense"] if i < s["dense_layers"] else _routed(s))
                 for i, k in enumerate(s["kinds"]))
    mtp = s["mtp"] * (2 * s["d"] + s["merge"] + s["mla"] + s["mla_vectors"]
                      + 2 * s["d"] + _routed(s) + s["d"])
    return (s["vocab"] * s["d"] + layers + s["d"]
            + s["d"] * s["vocab"] + s["vocab"] + mtp)


def head_columns_per_token(model: dict) -> float:
    """Columns of the vocabulary head a position's logits are built from,
    averaged over a sequence and summed over the head's passes: the main
    pass takes a text position against the text vocabulary alone and an
    image position against the codebook alone (``models/dalle.py``
    ``loss_segments``); the multi-token-prediction pass has one position
    fewer and its boundary one position earlier."""
    s = _sizes(model)
    image_cols = s["vocab"] - s["text_vocab"]
    main = s["text"] * s["text_vocab"] + s["image"] * image_cols
    ahead = (s["text"] - 1) * s["text_vocab"] + s["image"] * image_cols
    return (main + s["mtp"] * ahead) / s["n"]


def product_params_per_token(model: dict,
                             routed_pairs_per_token: float) -> float:
    """Parameters of the matrices a token is multiplied by on this chip: each
    layer's projections and gates, the dense MLP or the router and the
    shared expert whole, one routed expert for each (token, expert) pair
    computed here (``routed_pairs_per_token``: the step's counted
    ``moe_rows_held`` over its tokens, summed over the stack's routed layers
    and the multi-token-prediction block's), the block's merge, latent layer,
    router and shared expert (over n - 1 of n positions), and each head pass
    by its segment's own columns. Tables, filters, norms and the router's
    bias are not products and do not count."""
    s = _sizes(model)
    layers = sum(s[k] + (s["dense"] if i < s["dense_layers"]
                         else s["router"] + s["shared"])
                 for i, k in enumerate(s["kinds"]))
    mtp = (s["mtp"] * (s["merge"] + s["mla"] + s["router"] + s["shared"])
           * (s["n"] - 1) / s["n"])
    return (layers + mtp + routed_pairs_per_token * s["expert"]
            + s["d"] * head_columns_per_token(model))


def train_flops_per_token(model: dict, routed_pairs_per_token: float) -> float:
    """6 x the parameters a token's products touch, plus 3 x the forward
    products of causal softmax attention over the causal half at the two
    head widths (2 h (qk + dv) n / 2 a position a latent layer, the
    multi-token-prediction block's too) and of the chunked delta rule (a
    linear layer). Recomputed operations do not count."""
    s, b = _sizes(model), model["block"]
    softmax = s["h"] * (s["qk"] + s["dv"]) * s["n"]
    linear = b["linear_num_heads"] * delta_rule_flops_per_token(
        b["linear_head_dim"], b["linear_head_dim"])
    own = (sum(linear if k == "kda" else softmax for k in s["kinds"])
           + s["mtp"] * softmax)
    return (6.0 * product_params_per_token(model, routed_pairs_per_token)
            + 3.0 * own)


def flash_attention_cost(model: dict, batch: int, *, backward: bool,
                         bytes_per_el: int = 2) -> dict:
    """Operations and bytes of one latent layer's causal attention, from
    shapes. Forward: q k^T at the query/key width and p v at the value
    width over the causal half. Backward: the scores again, dq and dk at
    the query/key width, dp and dv at the value width: five products.
    Bytes: q and k (and their gradients) at the query/key width, v and the
    output (and theirs) at the value width, each moved once."""
    s = _sizes(model)
    half = s["n"] * (s["n"] + 1) / 2.0
    widths = (3 * s["qk"] + 2 * s["dv"]) if backward else (s["qk"] + s["dv"])
    one = batch * s["h"] * s["n"] * bytes_per_el
    tensors = 2 * s["qk"] + 2 * s["dv"]          # q, k | v, o
    return {"flops": 2.0 * half * widths * batch * s["h"],
            "bytes": float(one * tensors * (2 if backward else 1))}


def latent_layers(model: dict) -> int:
    """Latent-attention layers a step: the stack's and the
    multi-token-prediction block's."""
    s = _sizes(model)
    return sum(k == "mla" for k in s["kinds"]) + s["mtp"]
