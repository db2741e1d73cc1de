"""The arithmetic of one chip's share of a DeepSeek-V2 stack (latent
attention, routed + shared experts) as the DALL-E transformer: parameters
held, parameters a token's products touch, FLOPs a token from the counted
rows, and the grouped product's operations and bytes. ``model`` is a
configuration file's ``model`` object; the block's sizes are under ``block``
by the source's names."""

from __future__ import annotations


def _sizes(model: dict) -> dict:
    b = model["block"]
    d = model["dim"]
    h = model.get("heads_held") or model["heads"]
    qk = b["qk_nope_head_dim"] + b["qk_rope_head_dim"]
    held = model.get("experts_held") or b["n_routed_experts"]
    moe_layers = model["depth"] - b["first_dense_layers"]
    text_vocab = model["num_text_tokens"] + model["text_seq_len"]
    return {
        "d": d, "h": h, "qk": qk, "held": held, "moe_layers": moe_layers,
        "dense_layers": b["first_dense_layers"],
        "text_vocab": text_vocab,
        "vocab": text_vocab + model["image_vocab_size"],
        # the matrices of latent attention over the held heads
        "attn": (d * b["q_lora_rank"] + b["q_lora_rank"] * h * qk
                 + d * (b["kv_lora_rank"] + b["qk_rope_head_dim"])
                 + b["kv_lora_rank"] * h * (b["qk_nope_head_dim"]
                                            + b["v_head_dim"])
                 + h * b["v_head_dim"] * d),
        "attn_norms": d + b["q_lora_rank"] + b["kv_lora_rank"],
        "dense_mlp": 3 * d * b["intermediate_size"],
        "expert": 3 * d * b["moe_intermediate_size"],
        "shared": 3 * d * b["moe_intermediate_size"] * b["n_shared_experts"],
        "router": d * b["n_routed_experts"],
    }


def held_param_count(model: dict) -> int:
    """Every parameter the program holds for this share: the two input
    tables, per layer the attention, the norms and the feed-forward (the
    held experts, the shared ones and the router in an expert layer), the
    final norm and the vocabulary head with its bias."""
    s = _sizes(model)
    per_layer = s["attn"] + s["attn_norms"] + s["d"]          # + ff's norm
    layers = (model["depth"] * per_layer
              + s["dense_layers"] * s["dense_mlp"]
              + s["moe_layers"] * (s["held"] * s["expert"] + s["shared"]
                                   + s["router"]))
    return (s["vocab"] * s["d"] + layers + s["d"]
            + s["d"] * s["vocab"] + s["vocab"])


def product_params_per_token(model: dict, routed_pairs_per_token: float) -> float:
    """Parameters of the matrices a token is multiplied by on this chip:
    attention, the dense MLP, the shared experts and the router whole, the
    vocabulary head, and one routed expert for each (token, expert) pair
    computed here. ``routed_pairs_per_token`` is summed over the expert
    layers (the step's counted ``moe_rows_held`` over its tokens). The input
    tables are looked up, not multiplied, and do not count."""
    s = _sizes(model)
    return (model["depth"] * s["attn"] + s["dense_layers"] * s["dense_mlp"]
            + s["moe_layers"] * (s["shared"] + s["router"])
            + routed_pairs_per_token * s["expert"] + s["d"] * s["vocab"])


def train_flops_per_token(model: dict, routed_pairs_per_token: float) -> float:
    """PaLM's convention for what this chip computes: 6 x the parameters a
    token's products touch, plus attention's scores and values over the held
    heads, 12 L h n (qk width + value width) / 2. Recomputed operations do
    not count."""
    s, b = _sizes(model), model["block"]
    n = model["text_seq_len"] + model["image_fmap_size"] ** 2
    attention = (12.0 * model["depth"] * s["h"] * n
                 * (s["qk"] + b["v_head_dim"]) / 2.0)
    return 6.0 * product_params_per_token(model,
                                          routed_pairs_per_token) + attention


def grouped_product_cost(model: dict, rows: float, *, backward: bool,
                         bytes_per_el: int = 2) -> dict:
    """Operations and bytes one expert layer's three grouped products (gate,
    up, down) need for ``rows`` routed rows. Forward: 2 x rows x 3 d f
    operations; the held weights read once, each product's rows read and
    written once. Backward: twice the operations (the rows' gradients and the
    weights' gradients); the weights read and their gradients written, the
    rows, their activations and both gradients read or written once."""
    b = model["block"]
    d, f = model["dim"], b["moe_intermediate_size"]
    held = model.get("experts_held") or b["n_routed_experts"]
    weights = 3 * held * d * f
    flops = 2.0 * rows * 3 * d * f
    # gate, up: d in, f out; down: f in, d out
    row_elements = rows * (2 * (d + f) + (f + d))
    if backward:
        return {"flops": 2.0 * flops,
                "bytes": float((2 * weights + 2 * row_elements)
                               * bytes_per_el)}
    return {"flops": flops,
            "bytes": float((weights + row_elements) * bytes_per_el)}
