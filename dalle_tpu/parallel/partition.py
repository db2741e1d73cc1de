"""Parameter partitioning: regex path rules → NamedSharding over the mesh.

This is where the reference's entire parallelism story (replicated model +
allreduced grads via DeepSpeed/Horovod, SURVEY.md §2.6) collapses into sharding
annotations: with params replicated and the batch sharded over ``dp``, XLA's SPMD
partitioner inserts the gradient psum over ICI automatically — there is no
explicit allreduce anywhere in the framework.

On top of DP parity we add:
  * ``fsdp`` — ZeRO-like sharding of params/grads/optimizer state along the model's
    largest dimension (reference got this from DeepSpeed ZeRO config,
    legacy/train_dalle.py:502-507).
  * ``tp`` — Megatron-style tensor parallelism on attention heads and FF hidden dim.
  * ``sp`` — sequence parallelism; activations shard along sequence (ring attention
    in parallel/ring_attention.py).
"""

from __future__ import annotations

import re
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# Rules: (path_regex, PartitionSpec). First match wins. Paths are '/'-joined
# flax param paths, e.g. "transformer/layers_0/attn/to_qkv/kernel".
#
# Conventions:
#   - Linear kernels are (in, out).
#   - QKV/out projections: shard the head-structured dim over tp.
#   - FF in/out: shard hidden dim over tp.
#   - Embeddings: shard vocab over tp (gives sharded logits matmul).
#   - fsdp shards the *other* large dim (ZeRO-style), composable with tp.
DEFAULT_RULES: Tuple[Tuple[str, P], ...] = (
    # attention projections
    (r".*attn.*(to_qkv|to_q|to_kv|query|key|value)/kernel$", P("fsdp", "tp")),
    (r".*attn.*(to_out|out_proj)/kernel$",                   P("tp", "fsdp")),
    # latent attention (models/latent_moe.py): the per-head expansions and
    # the output projection shard their head-structured dim over tp like
    # to_qkv / to_out; the latents' down-projections have no head dim
    (r".*attn.*(q_b|kv_b)/kernel$",                          P("fsdp", "tp")),
    (r".*attn.*(q_a|kv_a)/kernel$",                          P("fsdp", None)),
    (r".*attn.*/o/kernel$",                                  P("tp", "fsdp")),
    # feed-forward
    (r".*(ff|mlp).*(w1|wi|fc1|dense_in|w_gate|w_up)/kernel$", P("fsdp", "tp")),
    (r".*(ff|mlp).*(w2|wo|fc2|dense_out|w_down)/kernel$",    P("tp", "fsdp")),
    # routed experts: three stacked (held, in, out) leaves. There is no
    # expert axis yet, so the leading axis is whole on every device and the
    # matrices shard like a feed-forward's; the grouped product runs on a
    # one-device mesh only (train/trainer_dalle.py refuses others). The
    # router is small and replicated.
    (r".*ff.*/(e_gate|e_up)$",                               P(None, "fsdp", "tp")),
    (r".*ff.*/e_down$",                                      P(None, "tp", "fsdp")),
    (r".*ff.*/router$",                                      P()),
    # embeddings + output head. Vocab shards over BOTH axes with the feature
    # dim replicated: a gather from a vocab-sharded table emits a replicated
    # feature dim, so activations stay batch-sharded at remat-block boundaries
    # (feature-sharded tables force an involuntary full-remat reshard in the
    # SPMD partitioner: dim-over-fsdp gather output vs batch-over-(dp,fsdp)
    # block inputs).
    # The image-token table (8,192 x dim, <60 MB in f32) is replicated
    # instead: sharded four ways at dim 1792 its 2,048-row bf16 shard falls
    # in a size window where the TPU compiler (libtpu 0.0.34) keeps the
    # gather's operand in VMEM and then fails the whole step's compile
    # ("Ran out of memory in memory space vmem ... exceeded scoped vmem
    # limit by 396.0K") — 1,024- and 4,096-row shards compile.
    (r".*image_emb/embedding$",                              P()),
    (r".*(tok_emb|text_emb|embedding)/embedding$",           P(("tp", "fsdp"),)),
    (r".*(to_logits|logits|head)/kernel$",                   P("fsdp", "tp")),
    # conv kernels (dVAE/VQGAN): shard output channels over fsdp only
    (r".*conv.*/kernel$",                                    P(None, None, None, "fsdp")),
    # biases / norms / scales: replicate ('g' only as a full component name)
    (r".*(bias|scale|embedding_pos)$|(^|.*/)g$",             P()),
)


def spec_for(path: str, shape: Tuple[int, ...],
             rules: Optional[Sequence[Tuple[str, P]]] = None,
             mesh: Optional[Mesh] = None) -> P:
    rules = DEFAULT_RULES if rules is None else rules
    for pat, spec in rules:
        if re.match(pat, path):
            spec = _fit_spec(spec, shape, mesh)
            return spec
    return P()


def _fit_spec(spec: P, shape: Tuple[int, ...], mesh: Optional[Mesh]) -> P:
    """Clip a spec to the array rank and drop axes that don't divide the dim
    (falls back to replication on that dim, like t5x's logical-axis fallback).

    Size-1 mesh axes are dropped from tuple entries — ``('tp', 'fsdp')`` on a
    tp=1 mesh becomes ``'fsdp'``. Placement is identical either way, but the
    spelling matters: GSPMD emits the normalized form on a jitted step's
    OUTPUTS, so a second same-config trainer built with the un-normalized
    input spelling would miss the executable cache and recompile the whole
    step (~seconds) for a byte-identical program."""
    parts = list(spec)
    parts = parts[: len(shape)] + [None] * (len(shape) - len(parts))
    if mesh is not None:
        for i, ax in enumerate(parts):
            if ax is None:
                continue
            axes = tuple(a for a in (ax if isinstance(ax, tuple) else (ax,))
                         if mesh.shape.get(a, 1) > 1)
            size = 1
            for a in axes:
                size *= mesh.shape[a]
            if size == 1 or shape[i] % size != 0:
                parts[i] = None
            else:
                parts[i] = axes[0] if len(axes) == 1 else axes
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def tree_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for kp, leaf in flat:
        path = "/".join(_key_str(k) for k in kp)
        out.append((path, leaf))
    return out


def _key_str(k):
    if hasattr(k, "key"):
        return str(k.key)
    if hasattr(k, "idx"):
        return str(k.idx)
    return str(k)


def make_param_shardings(mesh: Mesh, params,
                         rules: Optional[Sequence[Tuple[str, P]]] = None):
    """A pytree of NamedSharding matching ``params``' structure."""
    def per_path(kp, leaf):
        path = "/".join(_key_str(k) for k in kp)
        shape = getattr(leaf, "shape", ())
        return NamedSharding(mesh, spec_for(path, shape, rules, mesh))
    return jax.tree_util.tree_map_with_path(per_path, params)


def shard_params(mesh: Mesh, params, rules=None):
    """Place a (host or single-device) param tree onto the mesh per the rules."""
    shardings = make_param_shardings(mesh, params, rules)
    return jax.tree.map(jax.device_put, params, shardings)


def constrain(mesh: Mesh, x, *spec_axes):
    """Sharding constraint helper for activations inside jitted steps."""
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P(*spec_axes)))


def commit_to_mesh(mesh: Mesh, tree):
    """Replicate every leaf that is not already committed to a mesh sharding.

    ``TrainState.create`` builds the step counter and the optimizer's count
    scalars eagerly (``jnp.zeros``) — uncommitted single-device arrays. The
    params (and the mu/nu moments derived from them) are mesh-committed, so
    the FIRST train_step call carries a mixed signature, while its outputs
    come back fully mesh-committed: the second call then misses the
    executable cache and recompiles the whole program once (graftir caught
    this as a one-step retrace on every trainer). Committing the stray
    leaves up front makes the first call's signature the steady-state one —
    one compile for the life of the trainer."""
    repl = NamedSharding(mesh, P())

    def place(x):
        if isinstance(x, jax.Array) and not x.committed:
            return jax.device_put(x, repl)
        return x

    return jax.tree.map(place, tree)
