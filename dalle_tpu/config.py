"""Typed configuration tree for the whole framework.

One dataclass tree serves the three roles the reference spreads over argparse flags,
in-script DeepSpeed config dicts, and checkpoint-embedded hparams
(reference: legacy/train_dalle.py:88-138, 481-500, 535-582):

  * CLI: every leaf field can be set from the command line via ``add_args``/``from_args``.
  * Run config: the config object is what models/trainers consume.
  * Checkpoint metadata: ``to_dict``/``from_dict`` round-trip losslessly, so model
    identity travels inside the checkpoint exactly like the reference's ``hparams``.

Design is TPU-first: configs carry mesh/sharding/precision fields that have no
reference counterpart (the reference is data-parallel only, SURVEY.md §2.6).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
from dataclasses import dataclass, field, fields, is_dataclass
from typing import Any, Optional, Tuple


def _unwrap_optional(tp):
    """Optional[X] → X (leaves other types untouched)."""
    origin = getattr(tp, "__origin__", None)
    if origin is not None and origin is not tuple:
        args = [a for a in tp.__args__ if a is not type(None)]
        if len(args) == 1:
            return args[0]
    return tp


def _coerce(tp, value):
    """Best-effort coercion of JSON/CLI values into annotated field types."""
    if value is None:
        return None
    origin = getattr(tp, "__origin__", None)
    if origin is tuple:
        args = tp.__args__
        if len(args) == 2 and args[1] is Ellipsis:
            return tuple(_coerce(args[0], v) for v in value)
        return tuple(_coerce(a, v) for a, v in zip(args, value))
    if origin is not None:  # Optional[...] and friends
        args = [a for a in tp.__args__ if a is not type(None)]
        if len(args) == 1:
            return _coerce(args[0], value)
        return value
    if is_dataclass(tp) and isinstance(value, dict):
        return config_from_dict(tp, value)
    if tp in (int, float, str, bool) and not isinstance(value, tp):
        if tp is bool and isinstance(value, str):
            return value.lower() in ("1", "true", "yes", "on")
        return tp(value)
    return value


def config_to_dict(cfg) -> dict:
    out = {}
    for f in fields(cfg):
        v = getattr(cfg, f.name)
        if is_dataclass(v):
            out[f.name] = config_to_dict(v)
        elif isinstance(v, tuple):
            out[f.name] = list(v)
        else:
            out[f.name] = v
    return out


def config_from_dict(cls, d: dict):
    import typing
    hints = typing.get_type_hints(cls)
    kwargs = {}
    for f in fields(cls):
        if f.name in d:
            kwargs[f.name] = _coerce(hints[f.name], d[f.name])
    return cls(**kwargs)


class ConfigBase:
    """Mixin: dict/json round-trip + argparse wiring for flat overrides."""

    def to_dict(self) -> dict:
        return config_to_dict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict):
        return config_from_dict(cls, d)

    @classmethod
    def from_json(cls, s: str):
        return cls.from_dict(json.loads(s))

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)

    @classmethod
    def add_args(cls, parser: argparse.ArgumentParser, prefix: str = ""):
        """Add one ``--prefix.field`` flag per leaf field (dotted paths for nesting)."""
        import typing
        hints = typing.get_type_hints(cls)
        for f in fields(cls):
            tp = _unwrap_optional(hints[f.name])
            name = f"{prefix}{f.name}"
            if is_dataclass(tp):
                tp.add_args(parser, prefix=f"{name}.")
                continue
            origin = getattr(tp, "__origin__", None)
            if origin is tuple:
                parser.add_argument(f"--{name}", type=str, default=None,
                                    help=f"(comma list) default={getattr(cls, f.name, None)}")
            elif tp is bool:
                parser.add_argument(f"--{name}", type=str, default=None, metavar="BOOL")
            elif tp in (int, float, str):
                parser.add_argument(f"--{name}", type=tp, default=None)
            else:
                parser.add_argument(f"--{name}", type=str, default=None)

    @classmethod
    def from_args(cls, args: argparse.Namespace, base=None, prefix: str = ""):
        """Apply any ``--a.b.c`` overrides from an argparse namespace onto ``base``."""
        cfg = base if base is not None else cls()
        d = config_to_dict(cfg)

        def apply(cls_, sub: dict, pfx: str):
            import typing
            hints = typing.get_type_hints(cls_)
            for f in fields(cls_):
                tp = _unwrap_optional(hints[f.name])
                name = f"{pfx}{f.name}"
                if is_dataclass(tp):
                    apply(tp, sub[f.name], f"{name}.")
                    continue
                v = getattr(args, name, None)
                if v is None:
                    continue
                origin = getattr(tp, "__origin__", None)
                if origin is tuple and isinstance(v, str):
                    v = [s for s in v.split(",") if s]
                sub[f.name] = v

        apply(cls, d, prefix)
        return cls.from_dict(d)


# ---------------------------------------------------------------------------
# Mesh / parallelism
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MeshConfig(ConfigBase):
    """Logical device mesh. Axes: dp (data), fsdp (param/opt-state sharding, ZeRO-like),
    tp (tensor/model), sp (sequence/context for ring attention).

    The reference supports data parallelism only (SURVEY.md §2.6); tp/sp/fsdp are
    TPU-native additions, laid out so collectives ride ICI.
    """
    dp: int = 1
    fsdp: int = 1
    tp: int = 1
    sp: int = 1
    # names, in mesh order (outer→inner = DCN→ICI friendliness)
    axis_names: Tuple[str, ...] = ("dp", "fsdp", "sp", "tp")

    @property
    def size(self) -> int:
        return self.dp * self.fsdp * self.tp * self.sp

    def shape(self) -> Tuple[int, ...]:
        m = {"dp": self.dp, "fsdp": self.fsdp, "tp": self.tp, "sp": self.sp}
        return tuple(m[a] for a in self.axis_names)


@dataclass(frozen=True)
class PrecisionConfig(ConfigBase):
    """Mixed-precision policy (replaces the reference's Apex AMP / DeepSpeed fp16,
    legacy/train_dalle.py:481-500). bf16 is the TPU-native choice."""
    params: str = "float32"
    compute: str = "bfloat16"
    output: str = "float32"


# ---------------------------------------------------------------------------
# Models
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class DVAEConfig(ConfigBase):
    """Discrete VAE (reference: dalle_pytorch/dalle_pytorch.py:101-252)."""
    image_size: int = 128
    num_tokens: int = 8192       # codebook vocabulary
    codebook_dim: int = 512
    num_layers: int = 3          # conv downsamples; image_seq = (image_size/2**num_layers)**2
    num_resnet_blocks: int = 1
    hidden_dim: int = 64
    channels: int = 3
    smooth_l1_loss: bool = False
    kl_div_loss_weight: float = 0.0
    straight_through: bool = False
    # per-channel (means, stds); reference default is 0.5/0.5 (dalle_pytorch.py:116)
    normalization: Optional[Tuple[Tuple[float, float, float], Tuple[float, float, float]]] = (
        (0.5, 0.5, 0.5), (0.5, 0.5, 0.5))
    temperature: float = 0.9

    @property
    def image_seq_len(self) -> int:
        return (self.image_size // (2 ** self.num_layers)) ** 2

    @property
    def fmap_size(self) -> int:
        return self.image_size // (2 ** self.num_layers)


@dataclass(frozen=True)
class BlockConfig(ConfigBase):
    """What a transformer layer is built from: attention kind x feed-forward
    kind x norm, and how positions enter. The defaults are DALLE-pytorch's
    block (multi-head attention, GEGLU, LayerNorm, LayerScale, the text +
    axial rotary table) and build today's parameter tree leaf for leaf.

    The other kinds are DeepSeek-V2's (arXiv:2405.04434), sized by the keys
    of its ``config.json``: ``mla`` (latent attention: queries through a
    ``q_lora_rank`` latent, keys and values through a ``kv_lora_rank`` latent
    plus one rotary key part shared by all heads), ``swiglu``, ``moe``
    (``n_routed_experts`` SwiGLU experts of ``moe_intermediate_size``, of
    which a token takes ``num_experts_per_tok`` by group-limited greedy
    routing, plus ``n_shared_experts`` shared ones; the first
    ``first_dense_layers`` layers keep a ``swiglu`` of ``intermediate_size``),
    ``rmsnorm``, and ``seq_yarn`` positions (rotary over 0..n-1 with YaRN's
    frequency blend). They have no biases. Which heads and experts a chip
    holds is not the block's business: ``heads_held`` / ``experts_held`` on
    the model's config.

    A stack of more than one attention kind gives ``attention_layers``, a
    cyclic per-layer tuple (as ``TransformerConfig.attn_types`` is for mask
    kinds). Its further kinds are Solar-Open2's (``model_type: solar_open2``):
    ``gqa_gated`` (``heads`` query heads over ``num_key_value_heads`` key and
    value heads, a sigmoid gate on the attention output) and ``kda`` (Kimi
    delta attention, arXiv:2510.26692: ``linear_num_heads`` heads of
    ``linear_head_dim`` behind causal convolutions of
    ``short_conv_kernel_size``, per-channel decay and output gates through
    ``linear_gate_rank`` latents); ``positions: "none"`` adds no positional
    term anywhere; the router scores by ``scoring_func`` and, with
    ``norm_topk_prob``, renormalises a token's weights to sum 1.

    One stack may also mix ``kda`` with ``mla`` layers (``model_type:
    bailing_hybrid``): ``positions: "seq_yarn"`` then turns the latent
    layers' rotary parts and the linear layers take no notice of it.
    ``q_lora_rank: 0`` projects the queries directly; ``qk_norm`` puts a
    learned RMSNorm of a head's width on every query and key ahead of the
    rotation; ``attention_gate: "head_wise"`` multiplies each head's output
    by a sigmoid of the layer's input, one gate a head. For ``kda``,
    ``linear_gate_rank: 0`` makes the decay's and the output gate's
    projections whole (no latent), ``kda_lower_bound`` < 0 bounds the
    log-decay from below, ``g = lower_bound * sigmoid(exp(A) (W_f x + b))``
    (0: ``g = -exp(A) softplus(W_f x + b)``), and ``kda_beta_max`` is
    beta's range, ``beta = kda_beta_max * sigmoid(W_beta x)``. The router's
    ``topk_method``: ``group_limited_greedy`` (a group's score is its best
    expert's, and the scores select and weigh) or ``noaux_tc`` (DeepSeek-V3,
    arXiv:2412.19437: a per-expert bias, a leaf no gradient reaches, is
    added to the scores that select, a group's score is the sum of its two
    best, and the unbiased scores weigh).

    Olmo-Hybrid's layers (``model_type: olmo_hybrid``) add ``gdn`` (Gated
    DeltaNet, arXiv:2412.06464: ``linear_num_heads`` heads whose keys are
    ``linear_key_head_dim`` wide and values ``linear_value_head_dim``, one
    decay a head ``g = -exp(A_h) softplus(W_a x + dt_bias_h)``, beta =
    ``kda_beta_max * sigmoid(W_b x)``, 2 for the published
    ``linear_allow_neg_eigval``, a SiLU output gate from a whole projection)
    and ``gqa`` (grouped-query softmax attention without an output gate;
    with ``qk_norm`` a learned RMSNorm over the whole query and key
    projections, OLMo 2's). The two widths default to ``linear_head_dim``.
    ``norm_position: "post"`` is OLMo 2's block, ``x + norm(f(x))`` with no
    norm ahead of ``f``; ``"pre"`` (every other block) is ``x + f(norm(x))``."""
    attention: str = "mha"             # mha | mla | gqa_gated | kda | gdn | gqa
    # cyclic per-layer attention kinds; () is ``attention`` in every layer
    attention_layers: Tuple[str, ...] = ()
    feed_forward: str = "geglu"        # geglu | swiglu | moe
    norm: str = "layernorm"            # layernorm | rmsnorm
    norm_position: str = "pre"         # pre | post
    layerscale: bool = True
    positions: str = "dalle_axial"     # dalle_axial | seq_yarn | none
    first_dense_layers: int = 0
    rms_norm_eps: float = 1e-6
    # mla (q_lora_rank 0: queries without a latent)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    qk_norm: bool = False
    attention_gate: str = "none"       # none | head_wise
    # gqa_gated (0 = one key/value head a query head)
    num_key_value_heads: int = 0
    # kda
    linear_num_heads: int = 0
    linear_head_dim: int = 0
    short_conv_kernel_size: int = 4
    linear_gate_rank: int = 0          # 0: whole projections, no latent
    kda_lower_bound: float = 0.0       # < 0: the bounded decay
    kda_beta_max: float = 2.0          # beta's range, kda and gdn
    # gdn (0: linear_head_dim)
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    # seq_yarn (yarn_factor 1 is plain rotary)
    rope_theta: float = 10000.0
    yarn_factor: float = 1.0
    yarn_original_max_position: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    # swiglu / moe
    intermediate_size: int = 0
    moe_intermediate_size: int = 0
    n_routed_experts: int = 0
    n_shared_experts: int = 0
    num_experts_per_tok: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    scoring_func: str = "softmax"      # softmax | sigmoid
    norm_topk_prob: bool = False
    topk_method: str = "group_limited_greedy"   # | noaux_tc

    KINDS = {"attention": ("mha", "mla", "gqa_gated", "kda", "gdn", "gqa"),
             "feed_forward": ("geglu", "swiglu", "moe"),
             "norm": ("layernorm", "rmsnorm"),
             "norm_position": ("pre", "post"),
             "positions": ("dalle_axial", "seq_yarn", "none"),
             "scoring_func": ("softmax", "sigmoid"),
             "attention_gate": ("none", "head_wise"),
             "topk_method": ("group_limited_greedy", "noaux_tc")}

    def __post_init__(self):
        for field_name, kinds in self.KINDS.items():
            if getattr(self, field_name) not in kinds:
                raise ValueError(f"block.{field_name} must be one of {kinds}, "
                                 f"got {getattr(self, field_name)!r}")
        for kind in self.attention_layers:
            if kind not in self.KINDS["attention"]:
                raise ValueError(
                    f"block.attention_layers holds {kind!r}, not one of "
                    f"{self.KINDS['attention']}")

    @property
    def attention_kinds(self) -> Tuple[str, ...]:
        """One period of the stack's attention kinds; layer ``i`` is kind
        ``i % len``."""
        return tuple(self.attention_layers) or (self.attention,)

    @property
    def is_default(self) -> bool:
        return (self.attention_kinds, self.feed_forward, self.norm,
                self.norm_position, self.positions, self.layerscale) == (
                    ("mha",), "geglu", "layernorm", "pre", "dalle_axial",
                    True)

    @property
    def linear_widths(self) -> Tuple[int, int]:
        """(key, value) width of a linear-attention head."""
        return (self.linear_key_head_dim or self.linear_head_dim,
                self.linear_value_head_dim or self.linear_head_dim)

    @property
    def name(self) -> str:
        """The block kind, for messages: ``mla+moe``, ``gqa_gated/kda+moe``."""
        kinds = "/".join(dict.fromkeys(self.attention_kinds))
        return f"{kinds}+{self.feed_forward}"


@dataclass(frozen=True)
class TransformerConfig(ConfigBase):
    """Transformer stack (reference: dalle_pytorch/transformer.py:204-328)."""
    seq_len: int = 512           # total text+image sequence length (no bos slot)
    causal: bool = True
    dim: int = 512
    depth: int = 12
    heads: int = 8
    dim_head: int = 64
    ff_mult: int = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    # cyclic per-layer attention kinds: full | axial_row | axial_col | conv_like | sparse
    attn_types: Tuple[str, ...] = ("full",)
    image_fmap_size: int = 32
    sparse_attn_kernel: int = 5          # conv_like unfold kernel
    sparse_block_size: int = 128         # block-sparse tile (TPU lane-adapted; ref uses 16)
    sparse_num_random_blocks: int = 0    # 0 → seq_len // block // 4 like the reference
    # base seed for 'sparse' random-block patterns; each sparse layer draws
    # its own pattern from seed + layer_index (DeepSpeed
    # VariableSparsityConfig parity — per-layer variation, not one shared
    # pattern)
    sparse_mask_seed: int = 0
    reversible: bool = False
    use_remat: bool = True               # jax.checkpoint over blocks
    stable: bool = False                 # stable softmax + DivideMax
    sandwich_norm: bool = False
    shift_tokens: bool = False
    rotary_emb: bool = True
    shared_attn_ids: Optional[Tuple[int, ...]] = None
    shared_ff_ids: Optional[Tuple[int, ...]] = None
    optimize_for_inference: bool = False  # sparse→dense+static-mask swap
    # auto | off. "auto": ops.attention.attention_tier chooses the training
    # attention kernel from seq_len, heads, dim_head and the backend (flash,
    # fused or dense); "off" (or False) is the dense tier everywhere
    use_pallas: str = "auto"
    # f32 attention softmax is the safe default; False keeps scores bf16 —
    # the dominant HBM tensor (big train-throughput win, tiny numeric delta)
    attn_softmax_f32: bool = True
    block: BlockConfig = BlockConfig()
    # the share of a layer this chip holds (0 = all): heads of ``heads``
    # (mla), routed experts of ``block.n_routed_experts`` from index 0 (moe)
    heads_held: int = 0
    experts_held: int = 0


@dataclass(frozen=True)
class DalleConfig(ConfigBase):
    """DALL·E AR model (reference: dalle_pytorch/dalle_pytorch.py:336-440)."""
    num_text_tokens: int = 10000
    text_seq_len: int = 256
    dim: int = 512
    depth: int = 12
    heads: int = 8
    dim_head: int = 64
    ff_mult: int = 4
    attn_dropout: float = 0.0
    ff_dropout: float = 0.0
    attn_types: Tuple[str, ...] = ("full",)
    loss_img_weight: float = 7.0
    # >0: compute the vocab-head + cross-entropy in rematerialized sequence
    # chunks of this size — the (b, n, total_tokens) logits tensor never
    # materializes, trading one extra head matmul in backward for the HBM
    # that otherwise caps the batch size (total_tokens ≈ 58k with the CLIP
    # vocab makes full logits the largest activation in the step)
    loss_chunk: int = 0
    stable: bool = False
    sandwich_norm: bool = False
    shift_tokens: bool = False
    rotary_emb: bool = True
    shared_attn_ids: Optional[Tuple[int, ...]] = None
    shared_ff_ids: Optional[Tuple[int, ...]] = None
    share_input_output_emb: bool = False
    reversible: bool = False
    use_remat: bool = True
    use_pallas: str = "auto"   # auto | off (ops.attention.attention_tier)
    attn_softmax_f32: bool = True
    sparse_block_size: int = 128
    sparse_attn_kernel: int = 5
    sparse_mask_seed: int = 0   # per-layer patterns: seed + layer_index
    # filled from the vae at model build time
    image_size: int = 128
    image_vocab_size: int = 8192   # vae num_tokens
    image_fmap_size: int = 16      # image_size / 2**vae_layers
    # the layer's kinds (see BlockConfig) and this chip's share of a layer
    block: BlockConfig = BlockConfig()
    heads_held: int = 0
    experts_held: int = 0
    # multi-token prediction (DeepSeek-V3, arXiv:2412.19437 section 2.2):
    # ``mtp_depth`` blocks (0 or 1) of one mla + moe layer behind the stack
    # predict the token after the next through the main head;
    # loss = main + mtp_loss_weight * the block's
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.1

    def __post_init__(self):
        if isinstance(self.block, dict):     # DalleConfig(**a JSON object)
            object.__setattr__(self, "block", BlockConfig.from_dict(self.block))
        b = self.block
        if not b.is_default and (self.reversible or self.shift_tokens
                                 or self.sandwich_norm or self.stable
                                 or self.share_input_output_emb
                                 or tuple(self.attn_types) != ("full",)
                                 or self.shared_attn_ids or self.shared_ff_ids):
            raise ValueError(
                f"the {b.name} block runs full causal attention in a plain "
                f"pre-norm stack: reversible, shift_tokens, sandwich_norm, "
                f"stable, shared layers, tied embeddings and sparse "
                f"attn_types belong to the default block")
        if "mla" in b.attention_kinds and self.dim_head != b.v_head_dim:
            raise ValueError(f"mla: dim_head ({self.dim_head}) is the value "
                             f"head width, block.v_head_dim ({b.v_head_dim})")
        if self.mtp_depth not in (0, 1):
            raise ValueError(f"mtp_depth {self.mtp_depth}: one "
                             f"multi-token-prediction block or none")
        if self.mtp_depth and (b.feed_forward != "moe"
                               or "mla" not in b.attention_kinds):
            raise ValueError(
                f"the multi-token-prediction block is a latent-attention "
                f"layer and a routed layer: the {b.name} block has not both")

    @property
    def image_seq_len(self) -> int:
        return self.image_fmap_size ** 2

    @property
    def total_seq_len(self) -> int:
        return self.text_seq_len + self.image_seq_len

    @property
    def total_tokens(self) -> int:
        # text vocab reserves one unique pad token per text position (ref :370)
        return self.num_text_tokens + self.text_seq_len + self.image_vocab_size

    def transformer(self) -> TransformerConfig:
        return TransformerConfig(
            seq_len=self.total_seq_len, causal=True,
            dim=self.dim, depth=self.depth, heads=self.heads, dim_head=self.dim_head,
            ff_mult=self.ff_mult, attn_dropout=self.attn_dropout, ff_dropout=self.ff_dropout,
            attn_types=self.attn_types, image_fmap_size=self.image_fmap_size,
            reversible=self.reversible, use_remat=self.use_remat, stable=self.stable,
            sandwich_norm=self.sandwich_norm, shift_tokens=self.shift_tokens,
            rotary_emb=self.rotary_emb, shared_attn_ids=self.shared_attn_ids,
            shared_ff_ids=self.shared_ff_ids, use_pallas=self.use_pallas,
            attn_softmax_f32=self.attn_softmax_f32,
            sparse_block_size=self.sparse_block_size, sparse_attn_kernel=self.sparse_attn_kernel,
            sparse_mask_seed=self.sparse_mask_seed,
            block=self.block, heads_held=self.heads_held,
            experts_held=self.experts_held,
        )


    def mtp_transformer(self) -> TransformerConfig:
        """The multi-token-prediction block as a stack of its own: one
        latent-attention layer and one routed layer at the model's widths,
        positions and share."""
        return dataclasses.replace(
            self.transformer(), depth=1, block=dataclasses.replace(
                self.block, attention="mla", attention_layers=(),
                first_dense_layers=0))


@dataclass(frozen=True)
class ClipConfig(ConfigBase):
    """CLIP reranker (reference: dalle_pytorch/dalle_pytorch.py:256-332)."""
    dim_text: int = 512
    dim_image: int = 512
    dim_latent: int = 512
    num_text_tokens: int = 10000
    text_enc_depth: int = 6
    text_seq_len: int = 256
    text_heads: int = 8
    num_visual_tokens: int = 512
    visual_enc_depth: int = 6
    visual_heads: int = 8
    visual_image_size: int = 256
    visual_patch_size: int = 32
    channels: int = 3


@dataclass(frozen=True)
class VQGANConfig(ConfigBase):
    """VQGAN autoencoder (reference: dalle_pytorch/taming/models/vqgan.py +
    taming/modules/diffusionmodules/model.py:342-537)."""
    embed_dim: int = 256
    n_embed: int = 1024
    double_z: bool = False
    z_channels: int = 256
    resolution: int = 256
    in_channels: int = 3
    out_ch: int = 3
    ch: int = 128
    ch_mult: Tuple[int, ...] = (1, 1, 2, 2, 4)
    num_res_blocks: int = 2
    attn_resolutions: Tuple[int, ...] = (16,)
    dropout: float = 0.0
    quantizer: str = "vq"     # vq | gumbel
    beta: float = 0.25        # commitment cost
    gumbel_kl_weight: float = 5e-4
    straight_through: bool = True
    # index remapping onto a used-codes subset (taming quantize.py:303-310
    # remap/sane_index_shape): interface indices live in [0, len(remap_used))
    # with unknown codes mapped per remap_unknown ('random' | 'extra' | int).
    # Our indices are already (b, h, w)-shaped internally, so the reference's
    # sane_index_shape flag is inherently true.
    remap_used: Optional[Tuple[int, ...]] = None
    remap_unknown: str = "random"

    @property
    def num_layers(self) -> int:
        import math
        return int(math.log2(self.resolution) - math.log2(self.attn_resolutions[0]))


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimConfig(ConfigBase):
    optimizer: str = "adam"
    learning_rate: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.999
    eps: float = 1e-8
    weight_decay: float = 0.0
    grad_clip_norm: float = 0.5          # ref: legacy/train_dalle.py --clip_grad_norm
    grad_accum_steps: int = 1            # ref: --ga_steps
    lr_decay: bool = False               # ReduceLROnPlateau equivalent (cosine here)
    lr_decay_rate: float = 0.98          # exponential schedule gamma (ref --lr_decay_rate)
    lr_transition_steps: int = 1000      # steps per exponential decay application
    warmup_steps: int = 0
    total_steps: int = 100_000
    lr_scheduler: str = "constant"       # constant | cosine | exponential | plateau
    # plateau (ReduceLROnPlateau parity, ref legacy/train_dalle.py:444-459:
    # factor 0.5, patience 10, cooldown 10, min_lr 1e-6) — applied in-graph
    # via optax.contrib.reduce_on_plateau on the step's loss
    plateau_factor: float = 0.5
    plateau_patience: int = 10
    plateau_cooldown: int = 10
    plateau_min_scale: float = 1e-3      # min lr as a fraction of base lr


@dataclass(frozen=True)
class ObsConfig(ConfigBase):
    """grafttrace runtime telemetry (dalle_tpu/obs/, docs/OBSERVABILITY.md).
    Everything defaults off/cheap: the per-step breakdown metrics are always
    computed (host-side perf_counter math), but span collection, the
    watchdog, and the Prometheus textfile each need an explicit opt-in."""
    trace: bool = False            # collect spans into the ring buffer
    trace_dir: str = ""            # export dir ("" → <checkpoint_dir>/obs)
    ring_capacity: int = 65536     # spans kept; overflow is counted, not silent
    # no completed step within this many seconds → stall report (open spans +
    # thread stacks). 0 disables. Set well above worst expected XLA compile.
    watchdog_deadline_s: float = 0.0
    watchdog_dump_stacks: bool = True
    # poll HBM/compile gauges every N host steps (at metrics boundaries);
    # 0 disables device polling
    device_poll_every: int = 10
    prometheus_path: str = ""      # node-exporter textfile target ("" = off)
    # -- graftpulse model-health telemetry (obs/health.py, obs/anomaly.py) --
    # fuse per-layer-group grad/param/update/nonfinite taps (and codebook
    # vitals on the VAE trainers) into the jitted train step; the scalars
    # ride the existing metrics fetch — zero added host syncs. Changes the
    # compiled program, so the graftir goldens pin it (contracts build with
    # health on).
    health: bool = False
    # pytree path depth for layer groups (after dropping flax "params"
    # levels): 1 = model subtrees (transformer/encoder/decoder/...)
    health_group_depth: int = 1
    # anomaly-sentry thresholds (obs/anomaly.py): loss z-score, grad-norm
    # explosion factor over the EMA, absolute codebook-perplexity collapse
    # floor, and the warmup observations before any detector may fire
    health_loss_z: float = 6.0
    health_grad_factor: float = 10.0
    health_perplexity_floor: float = 4.0
    health_min_samples: int = 5


@dataclass(frozen=True)
class TrainConfig(ConfigBase):
    batch_size: int = 64                 # global batch
    epochs: int = 20
    seed: int = 42
    log_every: int = 10
    # fetch step metrics to host every N steps. 1 = every step (exact NaN
    # detection, but the device_get syncs the pipeline each step); larger
    # values let steps queue back-to-back on the chip — NaN rollback then
    # triggers up to N-1 steps late, still restoring the last good snapshot
    metrics_every: int = 1
    save_every_steps: int = 1000
    keep_n_checkpoints: Optional[int] = None
    checkpoint_dir: str = "./checkpoints"
    resume: bool = False
    # async orbax saves (docs/PERFORMANCE.md): a mid-run save() returns after
    # the device→host snapshot; serialize+write happen on a background thread.
    # The manager drains (wait_until_finished) at preflight, restore,
    # SIGUSR1-latch saves, fit() exit, and close()/atexit, so durability
    # points stay synchronous while steady-state saves leave the step loop.
    async_checkpointing: bool = True
    nan_rollback: bool = True            # ref fork: vae.py:100-110
    # where the NaN-rollback snapshot of (params, opt_state) lives:
    #   "device" — donated-safe on-device copy (no host fetch: the snapshot
    #              costs one HBM copy instead of a multi-second device_get at
    #              flagship scale), "host" — the pre-PR3 host device_get,
    #   "auto"   — device when the HBM headroom gauge shows the copy fits
    #              (bytes_limit known and in_use + 1.15×snapshot < limit,
    #              or no limit reported, e.g. CPU), else host
    rollback_snapshot: str = "auto"
    # graftmend (train/actions.py, docs/RESILIENCE.md): give TrainState a
    # runtime lr_scale data leaf so breach actions can cut the learning
    # rate host-side without a recompile. Opt-in (armed by the CLIs'
    # --breach_actions): the leaf adds one multiply per param leaf to the
    # compiled step, which is free at runtime but measurably taxes
    # COMPILE time across the suite's fleet of trainer programs, and
    # arming must happen at state creation (a mid-run treedef change
    # would break the step's pinned out_shardings)
    runtime_lr_scale: bool = False
    # double-buffered device prefetch depth for fit(): while step N runs, the
    # next `device_prefetch` batches are already converted + device_put with
    # their target shardings, so batch-wait + H2D leave the device critical
    # path. 0 disables (fit pulls and puts inline, the pre-PR3 behavior)
    device_prefetch: int = 2
    preflight_checkpoint: bool = True    # ref: legacy/train_dalle.py:591-594
    sample_every_steps: int = 0
    profile_step: int = 0                # >0 → dump a jax.profiler trace + MFU report
    # >1: run k optimizer steps per device dispatch (lax.scan over stacked
    # microbatches — trainers' train_steps). Amortizes per-dispatch host
    # overhead; host-side events (metrics fetch, NaN check, checkpointing)
    # then happen at k-step granularity. Note: a NaN rollback rewinds the
    # whole k-step group, so larger k widens the rollback blast radius
    # (up to k batches of progress lost per rollback vs 1 at k=1)
    scan_steps: int = 1
    # upload each saved checkpoint as a wandb artifact through the metrics
    # writer (ref legacy/train_dalle.py:584-587,667-669); no-op without wandb
    log_artifacts: bool = False
    optim: OptimConfig = field(default_factory=OptimConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    precision: PrecisionConfig = field(default_factory=PrecisionConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)


# temperature annealing for dVAE training (ref: legacy/train_vae.py:269-271)
@dataclass(frozen=True)
class AnnealConfig(ConfigBase):
    starting_temp: float = 1.0
    temp_min: float = 0.5
    anneal_rate: float = 1e-6
