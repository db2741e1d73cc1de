"""``adapter.py`` for the Olmo-Hybrid stack: the one place that knows how the
program lays out these layers' flax parameters. Weights are made by
``reference/olmo_hybrid.py``'s ``init_params`` from the seed and handed to
the program in this layout; trees shaped like the program's parameters are
read back by the reference's leaf names (``l_q.3`` is layer 3's)."""

from __future__ import annotations

from benchmarks.adapter_deepseek_v2 import array_pick, name_pick
from benchmarks.reference import olmo_hybrid as ref


def program_tree(shapes: ref.Shapes, pick) -> dict:
    """The program's parameter tree (flax names), every leaf given by
    ``pick(reference leaf name, layer or None)``."""
    def dense(name, l):
        return {"kernel": pick(name, l)}

    t = {}
    for l in range(shapes.depth):
        if shapes.kind(l) == "gdn":
            attn = {"q": dense("l_q", l), "k": dense("l_k", l),
                    "v": dense("l_v", l), "conv_q": pick("conv_q", l),
                    "conv_k": pick("conv_k", l), "conv_v": pick("conv_v", l),
                    "a": dense("w_a", l), "a_log": pick("a_log", l),
                    "dt_bias": pick("dt_bias", l), "b": dense("w_b", l),
                    "g": dense("w_g", l), "o_norm": pick("o_norm_g", l),
                    "o": dense("l_o", l)}
        else:
            attn = {"q": dense("s_q", l), "k": dense("s_k", l),
                    "v": dense("s_v", l),
                    "q_norm": {"scale": pick("q_norm_g", l)},
                    "k_norm": {"scale": pick("k_norm_g", l)},
                    "o": dense("s_o", l)}
        t[f"attn_{l}"] = attn
        t[f"ff_{l}"] = {"w_gate": dense("w_gate", l),
                        "w_up": dense("w_up", l),
                        "w_down": dense("w_down", l)}
        t[f"layer_attn_{l}"] = {"norm": {"scale": pick("attn_norm_g", l)}}
        t[f"layer_ff_{l}"] = {"norm": {"scale": pick("ff_norm_g", l)}}
    return {"params": {
        "final_norm": {"scale": pick("final_norm_g", None)},
        "image_emb": {"embedding": pick("image_emb", None)},
        "text_emb": {"embedding": pick("text_emb", None)},
        "to_logits": {"kernel": pick("w_logits", None),
                      "bias": pick("b_logits", None)},
        "transformer": t}}


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
