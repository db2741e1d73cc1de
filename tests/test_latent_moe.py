"""The latent-attention + routed-experts block (config.BlockConfig,
models/latent_moe.py, ops/grouped_matmul.py) on the CPU at a tiny size,
against the plain reference ``benchmarks/reference/deepseek_v2.py``, which
imports nothing of the program.

The share test ties a chip's share to the model: the partial results that
all the shares give, with what every chip computes alike (the shared
experts) counted once, add up to what the uncut reference gives for the
whole layer.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import adapter_deepseek_v2 as adapter
from benchmarks.kinds import train, train_moe
from benchmarks.reference import deepseek_v2 as ref
from dalle_tpu.config import (BlockConfig, DalleConfig, OptimConfig,
                              PrecisionConfig, TrainConfig)
from dalle_tpu.models.dalle import DALLE, init_dalle
from dalle_tpu.models.latent_moe import (MLAttention, MoEFeedForward,
                                         group_limited_top_k,
                                         row_buffer_size)
from dalle_tpu.ops.attention import attend
from dalle_tpu.ops.grouped_matmul import (combine_rows, default_tiling,
                                          gather_rows, grouped_matmul)
from dalle_tpu.ops.rotary import seq_yarn_table, yarn_mscale

BLOCK = dict(
    attention="mla", feed_forward="moe", norm="rmsnorm", layerscale=False,
    positions="seq_yarn", first_dense_layers=1, q_lora_rank=24,
    kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    yarn_factor=40.0, yarn_mscale=0.707, yarn_mscale_all_dim=0.707,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=16,
    n_shared_experts=2, num_experts_per_tok=3, n_group=4, topk_group=2,
    routed_scaling_factor=16.0)
MODEL = dict(num_text_tokens=50, text_seq_len=8, dim=32, depth=3, heads=8,
             dim_head=8, image_vocab_size=32, image_fmap_size=4,
             image_size=32, block=BLOCK, heads_held=2, experts_held=4,
             loss_chunk=8)
SEED = 2 ** 31 + 77


def model_dict(**over) -> dict:
    """The configuration as a file would hold it (``cfg['model']``)."""
    return dataclasses.asdict(DalleConfig(**{**MODEL, **over}))


def a_batch(rows: int = 2):
    rng = np.random.default_rng(3)
    return (jnp.asarray(rng.integers(0, 50, (rows, 8)), jnp.int32),
            jnp.asarray(rng.integers(0, 32, (rows, 16)), jnp.int32))


# -- the program against the reference ---------------------------------------

def test_loss_and_every_leaf_gradient_match_the_reference_in_float32():
    cfg = DalleConfig(**MODEL)
    shapes = ref.Shapes.from_model(model_dict())
    model = DALLE(cfg)
    weights = adapter.make_weights(shapes, SEED)
    text, ids = a_batch()
    (loss, aux), grads = jax.value_and_grad(
        lambda p: model.apply(p, text, ids, return_loss=True),
        has_aux=True)(weights)
    theirs = ref.init_params(shapes, ref.seed_key(SEED))
    (ref_loss, routed), ref_grads = jax.value_and_grad(
        lambda p: ref.loss_fn(shapes, p, text, ids, chunk=8),
        has_aux=True)(theirs)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    ours = adapter.named_leaves(shapes, grads)
    assert set(ours) == set(ref_grads)
    for name, g in ref_grads.items():
        np.testing.assert_allclose(np.asarray(ours[name]), np.asarray(g),
                                   atol=2e-5, err_msg=name)
    # the counters are the reference's routing
    assert float(aux["moe_rows_held"]) == sum(int((w > 0).sum())
                                              for w in routed)
    assert float(aux["moe_rows_dropped"]) == 0.0


def a_trainer(compute: str, batch: int = 4):
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    from dalle_tpu.config import MeshConfig
    tc = TrainConfig(
        batch_size=batch, preflight_checkpoint=False, save_every_steps=0,
        log_every=10 ** 9, metrics_every=1, scan_steps=1,
        precision=PrecisionConfig(compute=compute),
        optim=OptimConfig(optimizer="adafactor", learning_rate=3e-4,
                          grad_clip_norm=0.5))
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    return DalleTrainer(DalleConfig(**MODEL), tc, mesh=mesh)


CELL = {"name": "t", "recipe": {"optimizer": "adafactor",
                                "learning_rate": 3e-4, "grad_clip_norm": 0.5},
        "traffic": {"batch": 4, "text_tokens": [2, 8]}}

# bfloat16 against the float32 reference at this width: the gaps are of
# rounding and of the few routing choices that flip with it (measured 0.008,
# 0.005, 0.15 on the loss, the gradient norm and the worst leaf's change)
BANDS = {"float32": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                     "leaf_grad_gap": 1e-3, "leaf_change_gap": 2e-2},
         "bfloat16": {"loss_gap": 0.05, "grad_norm_gap": 0.1,
                      "leaf_grad_gap": 0.5, "leaf_change_gap": 0.5}}


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_three_adafactor_steps_through_fit_follow_the_reference(compute):
    """``DalleTrainer.fit``, the normal path: losses, gradient norms, the
    first gradient per leaf off Adafactor's moments and the parameters'
    change after three steps."""
    cfg = {"model": model_dict()}
    trainer = a_trainer(compute)
    program = train_moe.program_first_steps(trainer, CELL, cfg, SEED)
    reference = train_moe.reference_numbers(CELL, cfg, SEED)
    compared = train.compare(program, reference)
    for name, limit in BANDS[compute].items():
        assert compared[name] <= limit, (name, compared[name])
    assert program["moe_rows_dropped"] == [0.0] * 3
    if compute == "float32":
        assert program["moe_rows_held"][0] == sum(
            sum(layer) for layer in reference["rows_per_expert"])
    assert trainer.num_params == sum(
        math.prod(spec[0]) for spec in ref.leaf_specs(
            ref.Shapes.from_model(cfg["model"])).values())


# -- the share ----------------------------------------------------------------

UNCUT = ref.Shapes.from_model(model_dict(heads_held=0, experts_held=0))
SHARES = 4


def uncut_layer():
    params = ref.init_params(UNCUT, ref.seed_key(SEED))
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, UNCUT.dim))
    return ref.layer_params(params, 1), x


def test_the_shares_attention_partial_sums_add_up_to_the_uncut_layer():
    s, (lp, x) = UNCUT, uncut_layer()
    table = ref.rotary_table(s, x.shape[1])
    whole = ref.mla(s, table, x, lp, "f32")
    h = s.heads // SHARES
    angles, _ = seq_yarn_table(
        x.shape[1], s.qk_rope_head_dim, s.rope_theta,
        {"factor": s.yarn_factor, "original_max_position": 4096,
         "beta_fast": 32.0, "beta_slow": 1.0, "mscale": s.yarn_mscale,
         "mscale_all_dim": s.yarn_mscale_all_dim})
    layer = MLAttention(
        s.dim, h, s.heads, s.q_lora_rank, s.kv_lora_rank, s.qk_nope_head_dim,
        s.qk_rope_head_dim, s.v_head_dim,
        softmax_scale=ref.softmax_scale(s))
    total = 0.0
    for i in range(SHARES):
        def cols(w, width):        # this share's heads of a per-head axis
            return w[:, i * h * width:(i + 1) * h * width]
        p = {"q_a": {"kernel": lp["q_a"]}, "q_norm": {"scale": lp["q_norm_g"]},
             "q_b": {"kernel": cols(lp["q_b"], s.qk_head_dim)},
             "kv_a": {"kernel": lp["kv_a"]},
             "kv_norm": {"scale": lp["kv_norm_g"]},
             "kv_b": {"kernel": cols(lp["kv_b"],
                                     s.qk_nope_head_dim + s.v_head_dim)},
             "o": {"kernel": cols(lp["o"].T, s.v_head_dim).T}}
        total = total + layer.apply({"params": p}, x,
                                    rotary=jnp.asarray(angles))
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)


def test_the_shares_expert_partial_sums_add_up_to_the_uncut_layer():
    """Routed experts split four ways, the shared experts counted once."""
    s, (lp, x) = UNCUT, uncut_layer()
    whole, _ = jax.vmap(lambda r: ref.moe(s, r, lp, "f32"))(x)
    e = s.n_routed_experts // SHARES
    total, rows = 0.0, 0.0
    for i in range(SHARES):
        layer = MoEFeedForward(
            s.dim, s.moe_intermediate_size, experts_held=e,
            n_routed_experts=s.n_routed_experts, n_group=s.n_group,
            topk_group=s.topk_group, top_k=s.num_experts_per_tok,
            routed_scale=s.routed_scaling_factor,
            n_shared=s.n_shared_experts if i == 0 else 0, first_expert=i * e)
        p = {"router": lp["router"],
             **{k: lp[k][i * e:(i + 1) * e]
                for k in ("e_gate", "e_up", "e_down")}}
        if i == 0:
            p["shared"] = {"w_gate": {"kernel": lp["s_gate"]},
                           "w_up": {"kernel": lp["s_up"]},
                           "w_down": {"kernel": lp["s_down"]}}
        out, counters = layer.apply({"params": p}, x)
        total, rows = total + out, rows + float(counters["moe_rows_held"])
        assert float(counters["moe_rows_dropped"]) == 0.0
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               atol=2e-5)
    # every (token, choice) pair was computed by exactly one share
    assert rows == x.shape[0] * x.shape[1] * s.num_experts_per_tok


# -- the router ---------------------------------------------------------------

def test_the_router_by_hand_group_limit_no_renormalisation_times_scale():
    # 8 experts in 4 groups of 2; the 2 best groups stay, then the 3 best
    scores = jnp.asarray([[0.30, 0.02, 0.05, 0.25, 0.20, 0.01, 0.10, 0.07]])
    # groups' best: 0.30, 0.25, 0.20, 0.10 -> groups 0 and 1 stay; expert 4
    # (0.20, third best overall) is in group 2 and may not be taken
    weights, idx = group_limited_top_k(scores, n_group=4, topk_group=2,
                                       top_k=3)
    assert idx.tolist() == [[0, 3, 2]]
    np.testing.assert_allclose(np.asarray(weights), [[0.30, 0.25, 0.05]])
    layer = MoEFeedForward(4, 4, experts_held=8, n_routed_experts=8,
                           n_group=4, topk_group=2, top_k=3,
                           routed_scale=16.0, n_shared=0)
    router = jnp.log(scores[0])[None] * jnp.asarray([[1.0], [0], [0], [0]])
    p = {"router": router, "e_gate": jnp.zeros((8, 4, 4)),
         "e_up": jnp.zeros((8, 4, 4)), "e_down": jnp.zeros((8, 4, 4))}
    w, i = layer.apply({"params": p}, jnp.asarray([[1.0, 0, 0, 0]]),
                       method=MoEFeedForward.route)
    assert i.tolist() == [[0, 3, 2]]
    # the probabilities themselves (they sum to 1 over the 8), x 16
    np.testing.assert_allclose(np.asarray(w), [[4.8, 4.0, 0.8]], rtol=1e-5)


@pytest.mark.parametrize("winner", [2, 0])
def test_forced_imbalance_drops_nothing_and_equals_the_dense_reference(
        winner):
    """Every token's first choice is the one held expert ``winner``: the
    group of that expert holds every row and the others are empty."""
    s, (lp, x) = UNCUT, uncut_layer()
    x = jnp.abs(x)
    lp = dict(lp, router=lp["router"].at[:, winner].add(20.0))
    held = ref.Shapes.from_model(model_dict(heads_held=0, experts_held=4))
    held_lp = {k: (v[:4] if k.startswith("e_") else v) for k, v in lp.items()}
    whole, weights = jax.vmap(lambda r: ref.moe(held, r, held_lp, "f32"))(x)
    assert bool(jnp.all(weights[..., winner] > 0))
    layer = MoEFeedForward(
        s.dim, s.moe_intermediate_size, experts_held=4,
        n_routed_experts=s.n_routed_experts, n_group=s.n_group,
        topk_group=s.topk_group, top_k=s.num_experts_per_tok,
        routed_scale=s.routed_scaling_factor, n_shared=s.n_shared_experts)
    p = {"router": held_lp["router"], "e_gate": held_lp["e_gate"],
         "e_up": held_lp["e_up"], "e_down": held_lp["e_down"],
         "shared": {"w_gate": {"kernel": lp["s_gate"]},
                    "w_up": {"kernel": lp["s_up"]},
                    "w_down": {"kernel": lp["s_down"]}}}
    out, counters = layer.apply({"params": p}, x)
    assert float(counters["moe_rows_dropped"]) == 0.0
    # counted by choice: a choice whose probability underflowed to 0 is
    # still a row computed here
    idx, _ = ref.route(held, x.reshape(-1, s.dim), held_lp["router"], "f32")
    assert float(counters["moe_rows_held"]) == float(jnp.sum(idx < 4))
    assert float(counters["moe_load_max_over_mean"]) >= 4 * 24 / 72
    np.testing.assert_allclose(np.asarray(out), np.asarray(whole), atol=2e-5)


def test_routing_collapsed_onto_the_held_expert_counts_what_it_drops():
    """One held expert of 16 and every one of 64 tokens sent to it: the
    buffer holds ROW_BUFFER x the uniform share, 48 rows, and the other 16
    are counted."""
    s, (lp, _) = UNCUT, uncut_layer()
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (4, 16, s.dim)))
    layer = MoEFeedForward(
        s.dim, s.moe_intermediate_size, experts_held=1,
        n_routed_experts=16, n_group=s.n_group, topk_group=s.topk_group,
        top_k=3, routed_scale=16.0, n_shared=0)
    p = {"router": lp["router"].at[:, 0].add(20.0),
         **{k: lp[k][:1] for k in ("e_gate", "e_up", "e_down")}}
    _, counters = layer.apply({"params": p}, x)
    size = row_buffer_size(64, 3, 1, 16)
    assert size == 48 and float(counters["moe_rows_held"]) == size
    assert float(counters["moe_rows_dropped"]) == 64 - size


def test_the_row_buffer_is_whole_tiles_and_at_most_the_worst_case():
    assert row_buffer_size(10240, 6, 10, 160) == 15360      # the cell's
    assert row_buffer_size(10240, 6, 80, 160) == 61440      # the worst case
    assert row_buffer_size(10240, 6, 4, 160) == 6144
    assert row_buffer_size(10240, 6, 4, 8) == 40960         # min(6, 4)
    assert row_buffer_size(24, 3, 4, 16) == 72


# -- the grouped product -------------------------------------------------------

@pytest.mark.parametrize("sizes", [[10, 0, 25, 13], [0, 0, 64, 0],
                                   [16, 16, 16, 16], [3, 5, 0, 1]],
                         ids=["an_empty_group", "one_group_has_all",
                              "on_the_tiles", "mostly_no_group"])
def test_the_kernels_in_interpret_mode_match_ragged_dot(sizes):
    m, k, n = 64, 32, 48
    lhs = jax.random.normal(jax.random.PRNGKey(0), (m, k))
    rhs = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n))
    sizes = jnp.asarray(sizes, jnp.int32)

    def both(use_kernel):
        def loss(lhs, rhs):
            out = grouped_matmul(
                lhs, rhs, sizes, use_kernel=use_kernel, interpret=True,
                tiling=(16, 16, 16) if use_kernel else None)
            return jnp.sum(out * jnp.cos(out)), out
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(lhs, rhs)

    (_, out), (dlhs, drhs) = both(True)
    (_, ref_out), (ref_dlhs, ref_drhs) = both(False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=1e-4)
    np.testing.assert_allclose(np.asarray(dlhs), np.asarray(ref_dlhs),
                               atol=1e-3)
    np.testing.assert_allclose(np.asarray(drhs), np.asarray(ref_drhs),
                               atol=1e-3)
    owned = int(sizes.sum())
    assert not np.asarray(out)[owned:].any()        # rows of no group: zeros
    assert not np.asarray(dlhs)[owned:].any()
    assert not np.asarray(drhs)[np.asarray(sizes) == 0].any()


@pytest.mark.parametrize("n_rows", [0, 5, 16, 37, 48])
def test_rows_move_into_the_buffer_and_back_as_plain_indexing_does(n_rows):
    """``gather_rows`` / ``combine_rows`` walk only the chunks that hold a
    row (here chunks of 16); values and all three gradients are those of
    plain indexing over the first ``n_rows`` entries."""
    table = jax.random.normal(jax.random.PRNGKey(0), (10, 6))
    rows = jax.random.normal(jax.random.PRNGKey(1), (48, 6))
    weight = jax.random.normal(jax.random.PRNGKey(3), (48,))
    index = jax.random.randint(jax.random.PRNGKey(2), (48,), 0, 10)
    owned = (jnp.arange(48) < n_rows)[:, None]

    def ours(table, rows, weight):
        g = gather_rows(table, index, jnp.int32(n_rows))
        s = combine_rows(rows, weight, index, jnp.int32(n_rows), 10)
        return jnp.sum(jnp.sin(g)) + jnp.sum(jnp.cos(s)), (g, s)

    def plain(table, rows, weight):
        g = jnp.where(owned, table[index], 0.0)
        s = jnp.zeros((10, 6)).at[index].add(
            jnp.where(owned, rows * weight[:, None], 0.0))
        return jnp.sum(jnp.sin(g)) + jnp.sum(jnp.cos(s)), (g, s)

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1, 2), has_aux=True))(
        table, rows, weight)
    want = jax.value_and_grad(plain, argnums=(0, 1, 2), has_aux=True)(
        table, rows, weight)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_the_cells_shapes_tile_and_small_ones_fall_back():
    assert default_tiling(15360, 5120, 1536) == (256, 2560, 512)
    assert default_tiling(15360, 1536, 5120) == (256, 1536, 512)
    assert default_tiling(15360, 5120, 1536, max_k=1024) == (256, 1024, 512)
    assert default_tiling(64, 32, 48) is None


# -- positions ----------------------------------------------------------------

def test_the_yarn_table_against_the_closed_form():
    dim, theta, n = 64, 10000.0, 40
    scaling = {"factor": 40.0, "original_max_position": 4096,
               "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 0.707,
               "mscale_all_dim": 0.707}
    angles, scale = seq_yarn_table(n, dim, theta, scaling)
    assert angles.shape == (n, dim) and scale == 1.0
    # the dimension at which 4096 positions make r rotations
    def at(r):
        return dim * math.log(4096 / (r * 2 * math.pi)) / (2 * math.log(theta))
    low, high = math.floor(at(32.0)), math.ceil(at(1.0))
    assert (low, high) == (10, 23)
    for i in range(dim // 2):
        inv = theta ** (-2 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        expected = inv / 40.0 * ramp + inv * (1 - ramp)
        assert angles[7, 2 * i] == pytest.approx(7 * expected, rel=1e-6)
        assert angles[7, 2 * i + 1] == angles[7, 2 * i]   # adjacent pairs
    plain, one = seq_yarn_table(n, dim, theta, None)
    assert one == 1.0 and plain[3, 0] == pytest.approx(3.0)
    assert yarn_mscale(40.0, 0.707) == pytest.approx(
        0.1 * 0.707 * math.log(40.0) + 1)
    assert yarn_mscale(1.0, 0.707) == 1.0
    # the reference's own table is the same angles
    np.testing.assert_allclose(
        np.cos(angles), np.asarray(ref.rotary_table(
            ref.Shapes.from_model(model_dict(
                block=dict(BLOCK, qk_rope_head_dim=64))), n)[0]), atol=1e-5)


def test_attend_takes_a_value_width_of_its_own():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, 12))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 5, 12))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 2, 5, 7))
    out = attend(q, k, v, causal=True, scale=0.3)
    dots = jnp.einsum("bhid,bhjd->bhij", q, k) * 0.3
    dots = jnp.where(jnp.tril(jnp.ones((5, 5), bool)), dots, -jnp.inf)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(jax.nn.softmax(dots, -1) @ v), atol=1e-5)
    assert out.shape == (1, 2, 5, 7)


# -- what must not change, and what is refused ---------------------------------

def test_the_default_config_builds_the_old_parameter_tree():
    from benchmarks import arith
    cfg = DalleConfig(num_text_tokens=100, text_seq_len=16, dim=64, depth=2,
                      heads=2, dim_head=32, image_vocab_size=64,
                      image_fmap_size=4, image_size=16)
    assert cfg.block == BlockConfig() and cfg.block.is_default
    _, params = init_dalle(cfg, jax.random.PRNGKey(0))
    paths = sorted("/".join(str(k.key) for k in path) for path, _ in
                   jax.tree_util.tree_flatten_with_path(params["params"])[0])
    per_layer = ["attn_{l}/to_out/bias", "attn_{l}/to_out/kernel",
                 "attn_{l}/to_qkv/kernel", "ff_{l}/w1/bias",
                 "ff_{l}/w1/kernel", "ff_{l}/w2/bias", "ff_{l}/w2/kernel",
                 "layer_attn_{l}/norm/bias", "layer_attn_{l}/norm/scale",
                 "layer_attn_{l}/scale", "layer_ff_{l}/norm/bias",
                 "layer_ff_{l}/norm/scale", "layer_ff_{l}/scale"]
    assert paths == sorted(
        ["final_norm/bias", "final_norm/scale", "image_emb/embedding",
         "text_emb/embedding", "to_logits/bias", "to_logits/kernel"]
        + [f"transformer/{p.format(l=l)}" for l in range(2)
           for p in per_layer])
    assert sum(x.size for x in jax.tree.leaves(params)) == \
        arith.dalle_param_count(dataclasses.asdict(cfg))
    # and the config round-trips through a checkpoint's dict
    assert DalleConfig.from_dict(cfg.to_dict()) == cfg
    assert DalleConfig.from_dict(DalleConfig(**MODEL).to_dict()) == \
        DalleConfig(**MODEL)


def test_the_cached_paths_and_the_engine_refuse_the_block_by_name():
    from dalle_tpu.serve.engine import DecodeEngine
    cfg = DalleConfig(**MODEL)
    model, params = init_dalle(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match=r"mla\+moe"):
        DecodeEngine(model, params, slots=2)
    text = jnp.ones((1, 8), jnp.int32)
    with pytest.raises(NotImplementedError, match=r"mla\+moe"):
        model.apply(params, text, jax.random.PRNGKey(0),
                    method=DALLE.generate_images_tokens)
    with pytest.raises(ValueError, match="reversible"):
        DalleConfig(**{**MODEL, "reversible": True})
    with pytest.raises(ValueError, match="block.attention"):
        BlockConfig(attention="swa")      # "gqa" is a kind since PR 38


def test_latent_attention_is_the_dense_tier_whatever_the_length(monkeypatch):
    """A latent-attention stack asks for its tier like every softmax layer
    (PR 34): ``flash`` from ``FLASH_MIN_SEQ`` up, where the flash kernels
    take its two head widths, and the dense ``attend`` at every shorter
    length whatever the chooser answers there (the fused kernel takes mha's
    merged qkv). Off the TPU it is dense at any length, as before."""
    from dalle_tpu.models import transformer
    from dalle_tpu.models.transformer import Transformer
    chooser = transformer.attention_tier

    def built(fmap):
        cfg = DalleConfig(**{**MODEL, "image_fmap_size": fmap,
                             "image_size": 8 * fmap})
        stack = Transformer(cfg.transformer()).bind({})
        assert len(stack.attn_layers) == cfg.depth
        assert all(type(layer.fn) is MLAttention
                   for layer in stack.attn_layers)
        return {layer.fn.tier for layer in stack.attn_layers}
    for fmap in (4, 32, 64):        # sequences of 24, 1032 and 4104
        assert built(fmap) == {"dense"}
    monkeypatch.setattr(
        "dalle_tpu.models.transformer.attention_tier",
        lambda *a, **kw: chooser(*a, backend="tpu"))
    assert [built(fmap) for fmap in (4, 32, 64)] == [
        {"dense"}, {"dense"}, {"flash"}]


def test_a_routed_block_is_refused_on_a_mesh_of_several_devices():
    from dalle_tpu.config import MeshConfig
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    mesh = build_mesh(MeshConfig(dp=2), devices=jax.devices()[:2])
    with pytest.raises(NotImplementedError, match="expert axis"):
        DalleTrainer(DalleConfig(**MODEL), TrainConfig(batch_size=2),
                     mesh=mesh)


def test_the_trainer_stops_on_a_record_that_counts_a_dropped_row():
    trainer = a_trainer("float32", batch=2)
    real = trainer.step_fn

    def step(state, text, ids, key):
        state, metrics = real(state, text, ids, key)
        return state, dict(metrics,
                           moe_rows_dropped=metrics["moe_rows_dropped"] + 3)
    text, ids = a_batch()
    assert trainer.train_step(np.asarray(text), np.asarray(ids))[
        "moe_rows_dropped"] == 0.0
    trainer.step_fn = step
    with pytest.raises(RuntimeError, match="dropped 3 routed rows"):
        trainer.train_step(np.asarray(text), np.asarray(ids))
