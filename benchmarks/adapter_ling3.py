"""``adapter.py`` for the Ling-3.0-flash stack: the one place that knows how
the program lays out these layers' flax parameters. Weights are made by
``reference/ling3.py``'s ``init_params`` from the seed and handed to the
program in this layout; trees shaped like the program's parameters are read
back by the reference's leaf names (``l_q.3`` is layer 3's; the
multi-token-prediction block's layer is number ``depth``)."""

from __future__ import annotations

from benchmarks.adapter_deepseek_v2 import array_pick, name_pick
from benchmarks.reference import ling3 as ref


def layer_tree(shapes: ref.Shapes, pick, l: int, at: int) -> dict:
    """The four subtrees of the reference's layer ``l`` as the program names
    them at index ``at`` of a stack."""
    def dense(name):
        return {"kernel": pick(name, l)}

    if shapes.kind(l) == "kda":
        attn = {"q": dense("l_q"), "k": dense("l_k"), "v": dense("l_v"),
                "conv_q": pick("conv_q", l), "conv_k": pick("conv_k", l),
                "conv_v": pick("conv_v", l), "f_up": dense("w_f"),
                "a_log": pick("a_log", l),
                "decay_bias": pick("decay_bias", l), "beta": dense("w_beta"),
                "g_up": dense("w_g"), "o_norm": pick("o_norm_g", l),
                "o": dense("l_o")}
    else:
        attn = {"q": dense("m_q"), "kv_a": dense("m_kv_a"),
                "kv_norm": {"scale": pick("m_kv_norm_g", l)},
                "kv_b": dense("m_kv_b"),
                "q_head_norm": {"scale": pick("m_q_norm_g", l)},
                "k_head_norm": {"scale": pick("m_k_norm_g", l)},
                "gate": dense("m_gate"), "o": dense("m_o")}
    if shapes.is_moe(l) or l >= shapes.depth:
        ff = {"router": pick("router", l),
              "router_bias": pick("router_bias", l),
              "e_gate": pick("e_gate", l), "e_up": pick("e_up", l),
              "e_down": pick("e_down", l)}
        if shapes.n_shared_experts:
            ff["shared"] = {"w_gate": dense("s_gate"), "w_up": dense("s_up"),
                            "w_down": dense("s_down")}
    else:
        ff = {"w_gate": dense("w_gate"), "w_up": dense("w_up"),
              "w_down": dense("w_down")}
    return {f"attn_{at}": attn, f"ff_{at}": ff,
            f"layer_attn_{at}": {"norm": {"scale": pick("attn_norm_g", l)}},
            f"layer_ff_{at}": {"norm": {"scale": pick("ff_norm_g", l)}}}


def program_tree(shapes: ref.Shapes, pick) -> dict:
    """The program's parameter tree (flax names), every leaf given by
    ``pick(reference leaf name, layer or None)``."""
    stack = {}
    for l in range(shapes.depth):
        stack.update(layer_tree(shapes, pick, l, l))
    params = {
        "final_norm": {"scale": pick("final_norm_g", None)},
        "image_emb": {"embedding": pick("image_emb", None)},
        "text_emb": {"embedding": pick("text_emb", None)},
        "to_logits": {"kernel": pick("w_logits", None),
                      "bias": pick("b_logits", None)},
        "transformer": stack}
    if shapes.mtp_depth:
        params.update({
            "mtp_norm_h": {"scale": pick("mtp_norm_h_g", None)},
            "mtp_norm_e": {"scale": pick("mtp_norm_e_g", None)},
            "mtp_merge": {"kernel": pick("mtp_merge", None)},
            "mtp_final_norm": {"scale": pick("mtp_final_norm_g", None)},
            "mtp_block": layer_tree(shapes, pick, shapes.depth, 0)})
    return {"params": params}


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
