"""The stack of two attention kinds (config.BlockConfig ``attention_layers``:
Kimi delta attention and gated grouped-query attention, models/
hybrid_attention.py, ops/kda.py) with the sigmoid router, on the CPU at a
tiny size, against the plain reference ``benchmarks/reference/
solar_open2.py``, which imports nothing of the program and computes linear
attention one position at a time.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import adapter_solar_open2 as adapter
from benchmarks.kinds import train
from benchmarks.reference import solar_open2 as ref
from dalle_tpu.config import (BlockConfig, DalleConfig, OptimConfig,
                              PrecisionConfig, TrainConfig)
from dalle_tpu.models import latent_moe
from dalle_tpu.models.dalle import DALLE, init_dalle
from dalle_tpu.models.hybrid_attention import (GatedGQAttention,
                                               KimiDeltaAttention)
from dalle_tpu.models.latent_moe import MoEFeedForward
from dalle_tpu.models.transformer import Transformer, stack_layers

BLOCK = dict(
    attention_layers=("gqa_gated", "kda", "kda", "kda"), feed_forward="moe",
    norm="rmsnorm", layerscale=False, positions="none", rms_norm_eps=1e-5,
    num_key_value_heads=2, linear_num_heads=4, linear_head_dim=8,
    short_conv_kernel_size=4, linear_gate_rank=8, moe_intermediate_size=16,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
    routed_scaling_factor=1.0, scoring_func="sigmoid", norm_topk_prob=True)
# two periods; 8 + 8 x 8 = 72 positions: two chunks of 64, the second padded
MODEL = dict(num_text_tokens=40, text_seq_len=8, dim=32, depth=8, heads=4,
             dim_head=8, image_vocab_size=32, image_fmap_size=8,
             image_size=64, block=BLOCK, experts_held=4, loss_chunk=8)
# the trainer's tests: one layer of each kind
SHORT = {**MODEL, "depth": 2,
         "block": {**BLOCK, "attention_layers": ("gqa_gated", "kda")}}
SEED = 2 ** 31 + 99


def model_dict(**over) -> dict:
    """The configuration as a file would hold it (``cfg['model']``)."""
    return dataclasses.asdict(DalleConfig(**{**MODEL, **over}))


def a_batch(rows: int = 2):
    rng = np.random.default_rng(5)
    return (jnp.asarray(rng.integers(0, 40, (rows, 8)), jnp.int32),
            jnp.asarray(rng.integers(0, 32, (rows, 64)), jnp.int32))


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("over,within", [
    ({"depth": 2, "block": SHORT["block"]}, 1e-5),
    ({"depth": 4}, 5e-5), ({}, 1e-4)],
    ids=["one_layer_of_each_kind", "one_period", "two_periods"])
def test_loss_and_every_leaf_gradient_match_the_reference_in_float32(
        over, within):
    """Per leaf, the gap's norm over the leaf's. ISSUE 32 asked for 1e-5:
    one layer of each kind meets it (worst leaf 5e-6). Through more
    recurrent layers float32's rounding grows, in the reference's own two
    orders of summation as much as between it and the program: the three
    linear layers of one period, the cell's stack, read 2.3e-5, the six of
    two periods 2e-5 to 6e-5."""
    cfg = DalleConfig(**{**MODEL, **over})
    shapes = ref.Shapes.from_model(model_dict(**over))
    model = DALLE(cfg)
    weights = adapter.make_weights(shapes, SEED)
    text, ids = a_batch()
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, ids, return_loss=True),
        has_aux=True))(weights)
    theirs = ref.init_params(shapes, ref.seed_key(SEED))
    (ref_loss, routed), ref_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(shapes, p, text, ids, chunk=8),
        has_aux=True))(theirs)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    ours = adapter.named_leaves(shapes, grads)
    assert set(ours) == set(ref_grads)
    for name, g in ref_grads.items():
        gap = np.linalg.norm(np.asarray(ours[name]) - np.asarray(g))
        assert gap <= within * max(np.linalg.norm(np.asarray(g)), 1e-3), name
    # the counters are the reference's routing; every kda layer counted
    assert float(aux["moe_rows_held"]) == sum(int((w > 0).sum())
                                              for w in routed)
    assert float(aux["moe_rows_dropped"]) == 0.0
    assert float(aux["kda_logdecay_min"]) < 0.0


def a_trainer(compute: str, batch: int = 2):
    from dalle_tpu.config import MeshConfig
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    tc = TrainConfig(
        batch_size=batch, preflight_checkpoint=False, save_every_steps=0,
        log_every=10 ** 9, metrics_every=1, scan_steps=1,
        precision=PrecisionConfig(compute=compute),
        optim=OptimConfig(optimizer="adafactor", learning_rate=3e-4,
                          grad_clip_norm=0.5))
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    return DalleTrainer(DalleConfig(**SHORT), tc, mesh=mesh)


CELL = {"name": "t", "recipe": {"optimizer": "adafactor",
                                "learning_rate": 3e-4, "grad_clip_norm": 0.5},
        "traffic": {"batch": 2, "text_tokens": [2, 8]}}
# bfloat16 against the float32 reference at this width: rounding and the few
# routing choices that flip with it
BANDS = {"float32": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                     "leaf_grad_gap": 2e-3, "leaf_change_gap": 2e-2},
         "bfloat16": {"loss_gap": 0.05, "grad_norm_gap": 0.15,
                      "leaf_grad_gap": 0.6, "leaf_change_gap": 0.6}}


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_three_adafactor_steps_through_fit_follow_the_reference(compute):
    """The same trainer, loss head, optimizer and records as every other
    cell: ``DalleTrainer.fit`` with the kind's own hooks."""
    from benchmarks.kinds import train_hybrid
    cfg = {"model": dataclasses.asdict(DalleConfig(**SHORT)), "name": "tiny"}
    trainer = a_trainer(compute)
    program = train_hybrid._run.program_first_steps(trainer, CELL, cfg, SEED)
    reference = train_hybrid._run.reference_numbers(CELL, cfg, SEED)
    compared = train.compare(program, reference)
    for name, band in BANDS[compute].items():
        assert compared[name] <= band, (name, compared[name])
    assert program["moe_rows_dropped"] == [0.0] * 3
    assert all(low < 0 for low in program["kda_logdecay_min"])


# -- the linear layer ---------------------------------------------------------

def test_a_key_mask_is_refused_by_name_by_the_linear_layer():
    layer = KimiDeltaAttention(32, 4, 8, gate_rank=8)
    x = jnp.ones((1, 16, 32))
    params = layer.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="kda: a key mask"):
        layer.apply(params, x, key_mask=jnp.ones((1, 16), bool))


# -- grouped-query attention with the gate -------------------------------------

def plain_gated_gqa(params, x, heads, kv_heads, d):
    p = params["params"]
    b, n, _ = x.shape
    q = (x @ p["q"]["kernel"]).reshape(b, n, heads, d)
    k = (x @ p["k"]["kernel"]).reshape(b, n, kv_heads, d)
    v = (x @ p["v"]["kernel"]).reshape(b, n, kv_heads, d)
    rows = []
    for i in range(heads):
        j = i // (heads // kv_heads)
        dots = jnp.einsum("bid,bjd->bij", q[:, :, i], k[:, :, j]) * d ** -0.5
        dots = jnp.where(jnp.tril(jnp.ones((n, n), bool)), dots, -jnp.inf)
        rows.append(jax.nn.softmax(dots, -1) @ v[:, :, j])
    out = jnp.stack(rows, 2).reshape(b, n, heads * d)
    return (out * jax.nn.sigmoid(x @ p["gate"]["kernel"])) @ p["o"]["kernel"]


@pytest.mark.parametrize("tier", ["dense", "flash"])
def test_gated_grouped_query_attention_is_the_plain_form_on_its_tiers(tier):
    """8 query heads over 2 key/value heads; the flash tier runs its kernels
    in interpret mode here, forward and backward."""
    layer = GatedGQAttention(32, 8, 2, 16, tier=tier)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 130, 32))
    params = layer.init(jax.random.PRNGKey(0), x)
    assert params["params"]["k"]["kernel"].shape == (32, 2 * 16)
    assert params["params"]["gate"]["kernel"].shape == (32, 8 * 16)
    np.testing.assert_allclose(layer.apply(params, x),
                               plain_gated_gqa(params, x, 8, 2, 16),
                               atol=2e-5)
    ours = jax.grad(lambda p: jnp.sum(jnp.sin(layer.apply(p, x))))(params)
    theirs = jax.grad(lambda p: jnp.sum(jnp.sin(
        plain_gated_gqa(p, x, 8, 2, 16))))(params)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_softmax_layers_take_the_tier_chosen_for_the_configured_length(
        monkeypatch):
    """One chooser, asked once for the stack; the linear layers have no
    tier."""
    from dalle_tpu.models import transformer
    asked = []

    def tier(use_pallas, seq_len, heads, dim_head, backend=None):
        asked.append((seq_len, heads, dim_head))
        return "flash"
    monkeypatch.setattr(transformer, "attention_tier", tier)
    cfg = DalleConfig(**MODEL)
    stack = Transformer(cfg.transformer()).bind({})
    kinds = [type(layer.fn) for layer in stack.attn_layers]
    assert asked and set(asked) == {(72, 4, 8)}
    assert kinds == [GatedGQAttention, KimiDeltaAttention,
                     KimiDeltaAttention, KimiDeltaAttention] * 2
    assert [layer.fn.tier for layer in stack.attn_layers[::4]] == ["flash"] * 2
    layers = stack_layers(cfg.transformer())
    assert layers["kinds"] == ["gqa_gated", "kda", "kda", "kda"] * 2
    assert layers["tier"] == "flash"
    assert layers["gqa_gated"] == {"heads": 4, "head_dim": 8, "kv_heads": 2}
    assert layers["kda"] == {"heads": 4, "head_dim": 8, "chunk": 64,
                             "chunks": 2}
    # the flash kernels' walk, per softmax layer: 72 positions are one
    # block, cut by the diagonal and the padded tail
    assert layers["flash"] == [
        {"layer": i, "visited": 1, "full": 0, "total": 1} for i in (0, 4)]
    # at the cell's 4352 positions: 17 x 17 blocks of 256, the 136 under
    # the diagonal wholly visible
    cell = DalleConfig(**{**MODEL, "depth": 4, "text_seq_len": 256,
                          "image_fmap_size": 64, "image_size": 512})
    assert cell.total_seq_len == 4352
    assert stack_layers(cell.transformer())["flash"] == [
        {"layer": 0, "visited": 153, "full": 136, "total": 289}]


# -- the router and the share ---------------------------------------------------

def test_the_router_by_hand_sigmoid_scores_renormalised_over_the_chosen():
    layer = MoEFeedForward(dim=8, inner=4, experts_held=6, n_routed_experts=6,
                           n_group=1, topk_group=1, top_k=2, routed_scale=1.0,
                           n_shared=0, scoring="sigmoid", norm_topk=True)
    x = jnp.ones((1, 3, 8))
    params = layer.init(jax.random.PRNGKey(0), x)
    router = jnp.zeros((8, 6)).at[0].set(
        jnp.asarray([2.0, -1.0, 0.5, 3.0, 0.0, -2.0]))
    params = {"params": {**params["params"], "router": router}}
    weights, idx = layer.apply(params, jnp.ones((3, 8)),
                               method=MoEFeedForward.route)
    s = jax.nn.sigmoid(jnp.asarray([3.0, 2.0]))
    np.testing.assert_array_equal(idx, [[3, 0]] * 3)
    np.testing.assert_allclose(weights, jnp.broadcast_to(s / s.sum(), (3, 2)),
                               rtol=1e-6)
    assert float(weights.sum(-1)[0]) == pytest.approx(1.0)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test for this router: 4 chips hold 4 of 16 experts
    each; every chip routes over all 16 and renormalises over a token's 4
    choices, held or not. The four routed parts plus the shared expert once
    equal what the reference gives for the whole layer."""
    shapes = ref.Shapes.from_model(model_dict(experts_held=0))
    lp = {k: ref.init_leaf(ref.seed_key(SEED), f"{k}.0", spec)
          for k, spec in ref.layer_leaf_specs(shapes, "kda").items()}
    rows = jax.random.normal(jax.random.PRNGKey(2), (24, 32))
    whole, _ = ref.moe(shapes, rows, lp, "f32")
    shared = ref.swiglu(rows, lp["s_gate"], lp["s_up"], lp["s_down"], "f32")
    total = jnp.zeros_like(whole)
    for first in range(0, 16, 4):
        layer = MoEFeedForward(
            dim=32, inner=16, experts_held=4, n_routed_experts=16, n_group=1,
            topk_group=1, top_k=4, routed_scale=1.0, n_shared=1,
            first_expert=first, scoring="sigmoid", norm_topk=True)
        held = slice(first, first + 4)
        params = {"params": {
            "router": lp["router"], "e_gate": lp["e_gate"][held],
            "e_up": lp["e_up"][held], "e_down": lp["e_down"][held],
            "shared": {"w_gate": {"kernel": lp["s_gate"]},
                       "w_up": {"kernel": lp["s_up"]},
                       "w_down": {"kernel": lp["s_down"]}}}}
        out, counters = layer.apply(params, rows[None])
        assert float(counters["moe_rows_dropped"]) == 0.0
        total = total + (out[0] - shared)
    np.testing.assert_allclose(total + shared, whole, atol=2e-5)


# -- the defaults, the refusals ------------------------------------------------

def test_the_new_fields_at_their_defaults_are_the_old_block():
    assert BlockConfig().is_default and BlockConfig().attention_kinds == ("mha",)
    assert BlockConfig().name == "mha+geglu"
    assert BlockConfig(**BLOCK).name == "gqa_gated/kda+moe"
    assert not BlockConfig(**BLOCK).is_default
    cfg = DalleConfig(**MODEL)
    assert DalleConfig.from_dict(cfg.to_dict()) == cfg
    with pytest.raises(ValueError, match="attention_layers"):
        BlockConfig(attention_layers=("kda", "lstm"))
    with pytest.raises(ValueError, match="block.scoring_func"):
        BlockConfig(scoring_func="tanh")
    # a kind asks for its positions by name
    with pytest.raises(ValueError,
                       match="gqa_gated is causal, takes no positional"):
        init_dalle(DalleConfig(**{**MODEL, "block": {
            **BLOCK, "positions": "dalle_axial"}}), jax.random.PRNGKey(0))


def test_the_cached_paths_and_the_engine_refuse_the_new_kinds_by_name():
    from dalle_tpu.serve.engine import DecodeEngine
    cfg = DalleConfig(**{**MODEL, "depth": 4})
    model, params = init_dalle(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match=r"gqa_gated/kda\+moe"):
        DecodeEngine(model, params, slots=2)
    text = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match=r"gqa_gated/kda\+moe"):
        model.apply(params, text, jax.random.PRNGKey(1),
                    method=DALLE.generate_images_tokens)
    stack = Transformer(cfg.transformer()).bind({})
    for path in ("init_cache", "prefill", "decode_step", "decode_window"):
        with pytest.raises(NotImplementedError, match="recurrent state"):
            stack._refuse_cached(path)
    # a dense stack of either new kind alone is refused as well
    for kind in ("kda", "gqa_gated"):
        one = DalleConfig(**{**MODEL, "depth": 1, "block": {
            **BLOCK, "attention_layers": (kind,), "feed_forward": "swiglu",
            "intermediate_size": 16}})
        with pytest.raises(NotImplementedError, match=kind):
            Transformer(one.transformer()).bind({})._refuse_cached("prefill")


def test_init_sees_a_prefix_and_builds_every_leaf_at_its_size(monkeypatch):
    """Un-jitted init runs the cores on the first positions only: the
    parameter tree is that of a full-length trace."""
    cfg = DalleConfig(**{**MODEL, "depth": 4})
    monkeypatch.setattr(latent_moe, "INIT_POSITIONS", 16)
    _, short = init_dalle(cfg, jax.random.PRNGKey(0))
    model = DALLE(cfg)
    text, ids = a_batch(1)
    full = jax.eval_shape(lambda k: model.init(
        {"params": k, "cfg": k}, text, ids, return_loss=True),
        jax.random.PRNGKey(0))
    assert jax.tree.map(lambda x: x.shape, short) == \
        jax.tree.map(lambda x: x.shape, full)
    from benchmarks import arith_hybrid
    assert sum(x.size for x in jax.tree.leaves(short)) == \
        arith_hybrid.held_param_count(model_dict(depth=4))
