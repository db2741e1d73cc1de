"""``adapter.py`` for the Solar-Open2 stack: the one place that knows how the
program lays out these layers' flax parameters. Weights are made by
``reference/solar_open2.py``'s ``init_params`` from the seed and handed to
the program in this layout; trees shaped like the program's parameters are
read back by the reference's leaf names (``l_q.3`` is layer 3's)."""

from __future__ import annotations

from benchmarks.adapter_deepseek_v2 import array_pick, name_pick
from benchmarks.reference import solar_open2 as ref


def program_tree(shapes: ref.Shapes, pick) -> dict:
    """The program's parameter tree (flax names), every leaf given by
    ``pick(reference leaf name, layer or None)``."""
    def dense(name, l):
        return {"kernel": pick(name, l)}

    t = {}
    for l in range(shapes.depth):
        if shapes.kind(l) == "kda":
            attn = {"q": dense("l_q", l), "k": dense("l_k", l),
                    "v": dense("l_v", l), "conv_q": pick("conv_q", l),
                    "conv_k": pick("conv_k", l), "conv_v": pick("conv_v", l),
                    "f_down": dense("f_down", l), "f_up": dense("f_up", l),
                    "a_log": pick("a_log", l),
                    "decay_bias": pick("decay_bias", l),
                    "beta": dense("w_beta", l),
                    "g_down": dense("g_down", l), "g_up": dense("g_up", l),
                    "o_norm": pick("o_norm_g", l),
                    "o": dense("l_o", l)}
        else:
            attn = {"q": dense("g_q", l), "k": dense("g_k", l),
                    "v": dense("g_v", l), "gate": dense("g_gate", l),
                    "o": dense("g_o", l)}
        t[f"attn_{l}"] = attn
        ff = {"router": pick("router", l), "e_gate": pick("e_gate", l),
              "e_up": pick("e_up", l), "e_down": pick("e_down", l)}
        if shapes.n_shared_experts:
            ff["shared"] = {"w_gate": dense("s_gate", l),
                            "w_up": dense("s_up", l),
                            "w_down": dense("s_down", l)}
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
