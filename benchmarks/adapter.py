"""Between the benchmark's own names and the program's parameter tree: the
one place that knows how the program lays out its flax parameters. Weights
are made by the reference's ``init_params`` from the seed and handed to the
program in this layout; trees shaped like the program's parameters (the
parameters, Adam's moments, Adafactor's factors) are read back by name."""

from __future__ import annotations

from benchmarks.reference import dalle as ref


def program_tree(shapes: ref.Shapes, pick) -> dict:
    """The program's parameter tree (flax names), every leaf given by
    ``pick(reference leaf name, layer or None)``."""
    t = {}
    for l in range(shapes.depth):
        t[f"attn_{l}"] = {
            "to_qkv": {"kernel": pick("w_qkv", l)},
            "to_out": {"kernel": pick("w_out", l), "bias": pick("b_out", l)}}
        t[f"ff_{l}"] = {
            "w1": {"kernel": pick("w1", l), "bias": pick("b1", l)},
            "w2": {"kernel": pick("w2", l), "bias": pick("b2", l)}}
        t[f"layer_attn_{l}"] = {
            "norm": {"scale": pick("attn_norm_g", l),
                     "bias": pick("attn_norm_b", l)},
            "scale": pick("attn_scale", l)}
        t[f"layer_ff_{l}"] = {
            "norm": {"scale": pick("ff_norm_g", l),
                     "bias": pick("ff_norm_b", l)},
            "scale": pick("ff_scale", l)}
    return {"params": {
        "final_norm": {"scale": pick("final_norm_g", None),
                       "bias": pick("final_norm_b", None)},
        "image_emb": {"embedding": pick("image_emb", None)},
        "text_emb": {"embedding": pick("text_emb", None)},
        "to_logits": {"kernel": pick("w_logits", None),
                      "bias": pick("b_logits", None)},
        "transformer": t}}


def array_pick(params: dict):
    def pick(name, layer):
        if layer is None:
            return params[name]
        x = params["layers"][name][layer]
        # the program keeps LayerScale as (1, 1, dim)
        return x[None, None] if name.endswith("_scale") else x
    return pick


def name_pick(name, layer):
    return name if layer is None else f"{name}.{layer}"


def named_leaves(shapes: ref.Shapes, tree) -> dict:
    """{benchmark leaf name: leaf} of a tree shaped like the program's
    parameters (the parameters, Adam's moments, Adafactor's factors)."""
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
    # the key is an argument, not a constant: one program for every seed
    new = jax.jit(lambda key: program_tree(
        shapes, array_pick(ref.init_params(shapes, key))),
        out_shardings=shardings)(ref.seed_key(seed))
    if like is not None:
        for a, b in zip(jax.tree.leaves(new), jax.tree.leaves(like)):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise RuntimeError(f"weights {a.shape} {a.dtype} do not fit "
                                   f"the program's {b.shape} {b.dtype}")
    return new
