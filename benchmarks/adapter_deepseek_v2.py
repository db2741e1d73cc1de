"""``adapter.py`` for the DeepSeek-V2 block: the one place that knows how the
program lays out that block's flax parameters. Weights are made by
``reference/deepseek_v2.py``'s ``init_params`` from the seed and handed to
the program in this layout; trees shaped like the program's parameters are
read back by the reference's leaf names (``q_a.3`` is layer 3's)."""

from __future__ import annotations

from benchmarks.reference import deepseek_v2 as ref


def program_tree(shapes: ref.Shapes, pick) -> dict:
    """The program's parameter tree (flax names), every leaf given by
    ``pick(reference leaf name, layer or None)``."""
    def dense(name, l):
        return {"kernel": pick(name, l)}

    t = {}
    for l in range(shapes.depth):
        t[f"attn_{l}"] = {
            "q_a": dense("q_a", l), "q_norm": {"scale": pick("q_norm_g", l)},
            "q_b": dense("q_b", l), "kv_a": dense("kv_a", l),
            "kv_norm": {"scale": pick("kv_norm_g", l)},
            "kv_b": dense("kv_b", l), "o": dense("o", l)}
        if shapes.is_moe(l):
            ff = {"router": pick("router", l), "e_gate": pick("e_gate", l),
                  "e_up": pick("e_up", l), "e_down": pick("e_down", l)}
            if shapes.n_shared_experts:
                ff["shared"] = {"w_gate": dense("s_gate", l),
                                "w_up": dense("s_up", l),
                                "w_down": dense("s_down", l)}
        else:
            ff = {"w_gate": dense("w_gate", l), "w_up": dense("w_up", l),
                  "w_down": dense("w_down", l)}
        t[f"ff_{l}"] = ff
        t[f"layer_attn_{l}"] = {"norm": {"scale": pick("attn_norm_g", l)}}
        t[f"layer_ff_{l}"] = {"norm": {"scale": pick("ff_norm_g", l)}}
    return {"params": {
        "final_norm": {"scale": pick("final_norm_g", None)},
        "image_emb": {"embedding": pick("image_emb", None)},
        "text_emb": {"embedding": pick("text_emb", None)},
        "to_logits": {"kernel": pick("w_logits", None),
                      "bias": pick("b_logits", None)},
        "transformer": t}}


def name_pick(name, layer):
    return name if layer is None else f"{name}.{layer}"


def array_pick(params: dict):
    return lambda name, layer: params[name_pick(name, layer)]


def named_leaves(shapes: ref.Shapes, tree) -> dict:
    """{reference leaf name: leaf} of a tree shaped like the program's
    parameters (the parameters, Adafactor's factors)."""
    import jax
    names = jax.tree.leaves(program_tree(shapes, name_pick))
    leaves = jax.tree.leaves(tree)
    if len(names) != len(leaves):
        raise RuntimeError(f"the program's tree has {len(leaves)} leaves, "
                           f"the benchmark names {len(names)}")
    return dict(zip(names, leaves))


def make_weights(shapes: ref.Shapes, seed: int, like=None):
    """The program's parameter tree, made on the device in one jitted call
    from the seed, in float32 (the masters). ``like`` (the program's own
    tree, or its ``jax.ShapeDtypeStruct``s with shardings) gives the
    placement and is checked leaf by leaf."""
    import jax
    shardings = (None if like is None
                 else jax.tree.map(lambda x: x.sharding, like))
    new = jax.jit(lambda key: program_tree(
        shapes, array_pick(ref.init_params(shapes, key))),
        out_shardings=shardings)(ref.seed_key(seed))
    if like is not None:
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(like)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise RuntimeError(f"weights {a.shape} {a.dtype} do not fit "
                                   f"the program's {b.shape} {b.dtype}")
    return new
