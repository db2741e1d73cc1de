"""Ling-3.0-flash's forms (PR 34): Kimi delta attention with whole gate
projections and a bounded decay, latent attention without a query latent,
with per-head q/k norms, a head-wise gate and the flash tier at two head
widths, ``noaux_tc`` routing, a dense first layer under a per-layer pattern
and the multi-token-prediction block; on the CPU at a tiny size, against the
plain reference ``benchmarks/reference/ling3.py``, which imports nothing of
the program and computes linear attention one position at a time and latent
attention with the whole score matrix a head.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import adapter_ling3 as adapter
from benchmarks.kinds import train
from benchmarks.reference import ling3 as ref
from dalle_tpu.config import (BlockConfig, DalleConfig, OptimConfig,
                              PrecisionConfig, TrainConfig)
from dalle_tpu.models.dalle import DALLE, loss_head, loss_segments
from dalle_tpu.models.latent_moe import (MLAttention, MoEFeedForward,
                                         group_limited_top_k)
from dalle_tpu.models.transformer import Transformer, stack_layers
from dalle_tpu.ops.kda import CHUNK, kda_chunked

BLOCK = dict(
    attention_layers=("kda", "kda", "mla"), feed_forward="moe",
    norm="rmsnorm", layerscale=False, positions="seq_yarn",
    first_dense_layers=1, rms_norm_eps=1e-6, q_lora_rank=0, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, qk_norm=True,
    attention_gate="head_wise", linear_num_heads=4, linear_head_dim=8,
    short_conv_kernel_size=4, linear_gate_rank=0, kda_lower_bound=-5.0,
    kda_beta_max=1.0, rope_theta=6e6, intermediate_size=48,
    moe_intermediate_size=16, n_routed_experts=16, n_shared_experts=1,
    num_experts_per_tok=4, n_group=4, topk_group=2,
    routed_scaling_factor=2.5, scoring_func="sigmoid", norm_topk_prob=True,
    topk_method="noaux_tc")
# one period; 8 + 8 x 8 = 72 positions: two chunks of 64, the second padded
MODEL = dict(num_text_tokens=40, text_seq_len=8, dim=32, depth=3, heads=4,
             dim_head=8, image_vocab_size=32, image_fmap_size=8,
             image_size=64, block=BLOCK, experts_held=4, loss_chunk=8,
             mtp_depth=1, mtp_loss_weight=0.1)
# one layer of each kind: a linear layer with the dense MLP, a latent layer
# with the routed experts
SHORT = {**MODEL, "depth": 2, "mtp_depth": 0,
         "block": {**BLOCK, "attention_layers": ("kda", "mla")}}
SEED = 2 ** 31 + 3434


def model_dict(**over) -> dict:
    """The configuration as a file would hold it (``cfg['model']``)."""
    return dataclasses.asdict(DalleConfig(**{**MODEL, **over}))


def a_batch(rows: int = 2):
    rng = np.random.default_rng(5)
    return (jnp.asarray(rng.integers(0, 40, (rows, 8)), jnp.int32),
            jnp.asarray(rng.integers(0, 32, (rows, 64)), jnp.int32))


def program_and_reference(over: dict):
    cfg = DalleConfig(**{**MODEL, **over})
    shapes = ref.Shapes.from_model(model_dict(**over))
    model = DALLE(cfg)
    text, ids = a_batch()
    ours = jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, ids, return_loss=True),
        has_aux=True))(adapter.make_weights(shapes, SEED))
    theirs = jax.jit(jax.value_and_grad(
        lambda p: ref.loss_fn(shapes, p, text, ids, chunk=8),
        has_aux=True))(ref.init_params(shapes, ref.seed_key(SEED)))
    return shapes, ours, theirs


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("over,within", [
    ({"depth": SHORT["depth"], "mtp_depth": 0, "block": SHORT["block"]},
     1e-5),
    ({"mtp_depth": 0}, 5e-5), ({}, 5e-5)],
    ids=["one_layer_of_each_kind", "one_period_with_its_dense_first_layer",
         "the_period_and_the_mtp_block"])
def test_loss_and_every_leaf_gradient_match_the_reference_in_float32(
        over, within):
    """Per leaf, the gap's norm over the leaf's: 1e-5 through one layer of
    each kind, 5e-5 through the period and the multi-token-prediction block
    (readings 4e-6: float32 rounding through the recurrent layers). Both
    losses; the counters are the reference's routing, the block's routed
    layer counted too."""
    shapes, ((loss, aux), grads), ((ref_loss, (routed, ref_mtp)),
                                   ref_grads) = program_and_reference(over)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    if shapes.mtp_depth:
        assert float(aux["loss_mtp"]) == pytest.approx(float(ref_mtp),
                                                       rel=1e-5)
        assert float(ref_mtp) > 1.0
    else:
        assert "loss_mtp" not in aux
    ours = adapter.named_leaves(shapes, grads)
    assert set(ours) == set(ref_grads)
    for name, g in ref_grads.items():
        gap = np.linalg.norm(np.asarray(ours[name]) - np.asarray(g))
        assert gap <= within * max(np.linalg.norm(np.asarray(g)), 1e-3), name
        # no gradient reaches the router's bias, in either
        if name.startswith("router_bias"):
            assert not np.any(np.asarray(g)) and not np.any(
                np.asarray(ours[name]))
    assert len(routed) == (shapes.depth - shapes.first_dense_layers
                           + shapes.mtp_depth)
    assert float(aux["moe_rows_held"]) == sum(int((w > 0).sum())
                                              for w in routed)
    assert float(aux["moe_rows_dropped"]) == 0.0
    assert CHUNK * BLOCK["kda_lower_bound"] <= float(
        aux["kda_logdecay_min"]) < 0.0


def test_a_weight_of_zero_gives_the_main_loss_to_the_bit_and_a_dead_block():
    """``mtp_loss_weight`` 0: the loss is the stack's own to the bit, the
    block's leaves get zero gradients, and ``loss_mtp`` is still reported."""
    text, ids = a_batch()
    shapes = ref.Shapes.from_model(model_dict())
    weights = adapter.make_weights(shapes, SEED)

    def of(cfg, params):
        model = DALLE(DalleConfig(**cfg))
        return jax.jit(jax.value_and_grad(
            lambda p: model.apply(p, text, ids, return_loss=True),
            has_aux=True))(params)
    (loss, aux), grads = of({**MODEL, "mtp_loss_weight": 0.0}, weights)
    main = {"params": {k: v for k, v in weights["params"].items()
                       if not k.startswith("mtp_")}}
    (alone, _), alone_grads = of({**MODEL, "mtp_depth": 0}, main)
    assert float(loss) == float(alone)
    assert float(aux["loss_mtp"]) > 1.0
    for name, g in grads["params"].items():
        if name.startswith("mtp_"):
            assert not any(np.any(np.asarray(x)) for x in jax.tree.leaves(g))
    np.testing.assert_array_equal(
        np.asarray(grads["params"]["to_logits"]["kernel"]),
        np.asarray(alone_grads["params"]["to_logits"]["kernel"]))


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
    return DalleTrainer(DalleConfig(**MODEL), tc, mesh=mesh)


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
    cell: ``DalleTrainer.fit`` with the kind's own hooks; ``fit()``'s
    records carry ``loss_mtp`` and the counters."""
    from benchmarks.kinds import train_hybrid_mtp
    cfg = {"model": model_dict(), "name": "tiny"}
    trainer = a_trainer(compute)
    program = train_hybrid_mtp._run.program_first_steps(trainer, CELL, cfg,
                                                        SEED)
    reference = train_hybrid_mtp._run.reference_numbers(CELL, cfg, SEED)
    compared = train.compare(program, reference)
    for name, band in BANDS[compute].items():
        assert compared[name] <= band, (name, compared[name])
    assert program["moe_rows_dropped"] == [0.0] * 3
    assert all(CHUNK * -5.0 <= low < 0 for low in program["kda_logdecay_min"])
    for ours, theirs in zip(program["loss_mtp"], reference["loss_mtp"]):
        assert ours == pytest.approx(theirs, rel=BANDS[compute]["loss_gap"])
    # the bias is a leaf the optimizer leaves where it was (its 16 numbers
    # of about 0.01 made again from the seed, to their last bit or two)
    assert program["leaf_change_norms"]["router_bias.1"] < 1e-8
    assert reference["leaf_change_norms"]["router_bias.1"] < 1e-8
    assert program["leaf_change_norms"]["router.1"] > 1e-4


# -- the bounded decay --------------------------------------------------------

def bounded_inputs(n: int, rate: float = 1.0, h: int = 3, d: int = 8,
                   dv: int = 5):
    """``rate`` 1: gates all over (-5, 0); 16: the sigmoid saturated both
    ways, most channels decaying by exp(-5) a position."""
    ks = jax.random.split(jax.random.PRNGKey(n), 7)
    q, k = (jax.random.normal(key, (2, n, h, d)) for key in ks[:2])
    f = jax.random.normal(ks[2], (2, n, h, d))
    v = jax.random.normal(ks[3], (2, n, h, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (2, n, h)))
    a_log = jnp.log(rate * jnp.array([4.0, 0.25, 1.0]))
    bias = 0.3 * jax.random.normal(ks[5], (h, d))
    scale = 1.0 + 0.1 * jax.random.normal(ks[6], (dv,))
    return q, k, v, f, beta, a_log, bias, scale


def recurrent_bounded(q, k, v, f, beta, a_log, bias, scale, bound, eps=1e-5,
                      raw: bool = False):
    """The delta rule one position at a time under the bounded decay, and
    the head's RMS norm of what the state returns (``raw``: what it
    returns)."""
    hi = jax.lax.Precision.HIGHEST
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = bound * jax.nn.sigmoid(jnp.exp(a_log)[:, None] * (f + bias))

    def position(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = jnp.exp(g_t)[..., None] * state
        u = b_t[..., None] * (v_t - jnp.einsum("bhd,bhdv->bhv", k_t, state,
                                               precision=hi))
        state = state + jnp.einsum("bhd,bhv->bhdv", k_t, u, precision=hi)
        return state, jnp.einsum("bhd,bhdv->bhv", q_t, state, precision=hi)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))
    start = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:])
    o = jnp.moveaxis(jax.lax.scan(position, start, xs)[1], 0, 1)
    if raw:
        return o
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale


@pytest.mark.parametrize("n", [150, 37, 128])
def test_the_bounded_decay_is_the_recurrent_form(n):
    """``lower_bound`` -5 (Ling's ``kda_lower_bound``): the one core with
    the decay's other form against the recurrence one position at a time,
    forward and under ``jax.grad`` with respect to every input and leaf, at
    lengths that are and are not multiples of the chunk; a chunk's
    cumulative log-decay is at least 64 x -5 by construction; no inf, no
    nan behind the end's padding."""
    args = bounded_inputs(n)

    def chunked(*a):
        return kda_chunked(*a[:5], a_log=a[5], bias=a[6], norm_scale=a[7],
                           eps=1e-5, lower_bound=-5.0)
    ours, low = jax.jit(chunked)(*args)
    theirs = recurrent_bounded(*args, -5.0)
    # the recurrence's own numbers (what the norm divided by put back) to
    # 1e-5, then the normalised ones, where a head's output near 0 is a
    # division by little (tests/test_kda.py's two tolerances)
    raw = recurrent_bounded(*args, -5.0, raw=True)
    rms = jnp.sqrt(jnp.mean(raw * raw, -1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(np.asarray(ours / args[-1] * rms),
                               np.asarray(raw), atol=1e-5)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(theirs),
                               atol=1e-4)
    assert CHUNK * -5.0 <= float(low) < -1.0
    # saturated gates: a chunk's sum comes close to its bound and stays
    # inside it, and nothing overflows on the way (the read-out is then
    # float32 noise under the head's norm, so no numbers are compared)
    harsh = bounded_inputs(n, rate=16.0)
    out, low = jax.jit(chunked)(*harsh)
    assert np.all(np.isfinite(np.asarray(out)))
    assert CHUNK * -5.0 <= float(low) < 0.6 * min(n, CHUNK) * -5.0
    w = jax.random.normal(jax.random.PRNGKey(9), theirs.shape)
    grads = jax.jit(jax.grad(lambda *a: jnp.sum(chunked(*a)[0] * w),
                             argnums=tuple(range(8))))(*args)
    wants = jax.jit(jax.grad(
        lambda *a: jnp.sum(recurrent_bounded(*a, -5.0) * w),
        argnums=tuple(range(8))))(*args)
    for got, want in zip(grads, wants):
        assert np.all(np.isfinite(np.asarray(got)))
        assert float(jnp.max(jnp.abs(got - want))) <= 2e-4 * max(
            1.0, float(jnp.max(jnp.abs(want))))


def test_the_decays_default_form_is_solars():
    """``lower_bound`` 0 (the default) is ``-exp(A) softplus``: unbounded."""
    from dalle_tpu.ops.kda import _log_decay
    f = jnp.full((1, 2, 4), 30.0)
    a_log, bias = jnp.log(jnp.array([16.0, 1.0])), jnp.zeros((2, 4))
    np.testing.assert_allclose(np.asarray(_log_decay(f, a_log, bias)[0, :, 0]),
                               [-480.0, -30.0], rtol=1e-6)
    bounded = _log_decay(f, a_log, bias, -5.0)
    assert float(bounded.min()) >= -5.0 and float(bounded.max()) < 0.0


# -- the router ---------------------------------------------------------------

def test_noaux_tc_scores_a_group_by_its_two_best():
    # 8 experts in 4 groups of 2. Best alone: groups 0 (0.40) and 1 (0.35)
    # stay. By the two best: 0.42, 0.36, 0.60, 0.20 -> groups 2 and 0
    scores = jnp.asarray([[0.40, 0.02, 0.01, 0.35, 0.30, 0.30, 0.10, 0.10]])
    _, by_best = group_limited_top_k(scores, 4, 2, 3)
    assert by_best.tolist() == [[0, 3, 1]]
    weights, by_two = group_limited_top_k(scores, 4, 2, 3,
                                          group_score="best2",
                                          bias=jnp.zeros((8,)))
    assert by_two.tolist() == [[0, 4, 5]]
    np.testing.assert_allclose(np.asarray(weights), [[0.40, 0.30, 0.30]])
    with pytest.raises(ValueError, match="best2"):
        group_limited_top_k(scores, 4, 2, 3, group_score="mean")


def test_a_bias_changes_the_choice_and_not_the_weights():
    """The bias is added to the scores that select, groups and experts, and
    the weights are the chosen experts' own scores over their sum."""
    scores = jnp.asarray([[0.40, 0.02, 0.01, 0.35, 0.30, 0.30, 0.10, 0.10]])
    bias = jnp.zeros((8,)).at[6].set(0.5).at[7].set(0.3)
    weights, idx = group_limited_top_k(scores, 4, 2, 3, group_score="best2",
                                       bias=bias)
    # groups by two best of s': 0.42, 0.36, 0.60, 1.00 -> groups 3 and 2;
    # experts by s': 6 (0.60), 7 (0.40), then 4 (0.30)
    assert idx.tolist() == [[6, 7, 4]]
    np.testing.assert_allclose(np.asarray(weights), [[0.10, 0.10, 0.30]])
    # a negative bias may sink a kept group's experts under 0: they are
    # still taken before anything outside the kept groups
    sunk = jnp.full((8,), -1.0)
    _, idx = group_limited_top_k(scores, 4, 1, 2, group_score="best2",
                                 bias=sunk)
    assert idx.tolist() == [[4, 5]]
    layer = MoEFeedForward(4, 4, experts_held=8, n_routed_experts=8,
                           n_group=4, topk_group=2, top_k=3,
                           routed_scale=2.5, n_shared=0, scoring="sigmoid",
                           norm_topk=True, topk_method="noaux_tc")
    logits = jnp.log(scores[0] / (1 - scores[0]))
    p = {"router": logits[None] * jnp.asarray([[1.0], [0], [0], [0]]),
         "router_bias": bias, "e_gate": jnp.zeros((8, 4, 4)),
         "e_up": jnp.zeros((8, 4, 4)), "e_down": jnp.zeros((8, 4, 4))}
    x = jnp.asarray([[1.0, 0, 0, 0]])
    w, i = layer.apply({"params": p}, x, method=MoEFeedForward.route)
    assert i.tolist() == [[6, 7, 4]]
    np.testing.assert_allclose(np.asarray(w), 2.5 * np.array(
        [[0.10, 0.10, 0.30]]) / 0.5, rtol=1e-5)
    # no gradient reaches the bias through the weights
    g = jax.grad(lambda p: jnp.sum(layer.apply(
        {"params": p}, x, method=MoEFeedForward.route)[0] ** 2))(p)
    assert not np.any(np.asarray(g["router_bias"]))
    assert np.any(np.asarray(g["router"]))


def test_v2s_routing_keeps_its_form_and_its_leaves():
    """``group_limited_greedy`` (the default): a group's score is its best
    expert's, the scores select and weigh, and the layer has no bias."""
    layer = MoEFeedForward(4, 4, experts_held=8, n_routed_experts=8,
                           n_group=4, topk_group=2, top_k=3,
                           routed_scale=1.0, n_shared=0)
    shapes = jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                            jnp.zeros((1, 2, 4)))["params"]
    assert set(shapes) == {"router", "e_gate", "e_up", "e_down"}
    biased = MoEFeedForward(4, 4, experts_held=8, n_routed_experts=8,
                            n_group=4, topk_group=2, top_k=3,
                            routed_scale=1.0, n_shared=0,
                            topk_method="noaux_tc")
    params = biased.init(jax.random.PRNGKey(0), jnp.zeros((1, 2, 4)))
    assert params["params"]["router_bias"].shape == (8,)
    assert not np.any(np.asarray(params["params"]["router_bias"]))
    with pytest.raises(ValueError, match="topk_method"):
        BlockConfig(topk_method="aux")


# -- the share ----------------------------------------------------------------

def test_the_shares_routed_outputs_add_up_to_the_uncut_layer():
    """4 x 4 of 16 experts under ``noaux_tc``: the routed outputs of all the
    shares, the shared expert counted once, are the uncut reference's
    layer; every (token, choice) pair is computed by exactly one share."""
    uncut = ref.Shapes.from_model(model_dict(experts_held=0))
    params = ref.init_params(uncut, ref.seed_key(SEED))
    lp = ref.layer_params(params, 1)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 12, uncut.dim))
    whole, _ = jax.vmap(lambda r: ref.moe(uncut, r, lp, "f32"))(x)
    shares, e = 4, uncut.n_routed_experts // 4
    total, rows = 0.0, 0.0
    for i in range(shares):
        layer = MoEFeedForward(
            uncut.dim, uncut.moe_intermediate_size, experts_held=e,
            n_routed_experts=uncut.n_routed_experts, n_group=uncut.n_group,
            topk_group=uncut.topk_group, top_k=uncut.num_experts_per_tok,
            routed_scale=uncut.routed_scaling_factor,
            n_shared=uncut.n_shared_experts if i == 0 else 0,
            first_expert=i * e, scoring="sigmoid", norm_topk=True,
            topk_method="noaux_tc")
        p = {"router": lp["router"], "router_bias": lp["router_bias"],
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
    assert rows == x.shape[0] * x.shape[1] * uncut.num_experts_per_tok
    assert np.any(np.asarray(lp["router_bias"]))      # seeded, not zero


# -- latent attention ---------------------------------------------------------

def a_latent_layer(tier: str) -> MLAttention:
    return MLAttention(32, 4, 4, 0, 16, 8, 4, 8, softmax_scale=12 ** -0.5,
                       qk_norm=True, gate="head_wise", tier=tier)


def test_the_latent_layer_on_the_flash_tier_is_the_dense_one():
    """``tier: flash`` runs the flash kernels (interpret mode here) with keys
    of 12 and values of 8 and equals the dense ``attend``, forward and every
    leaf's gradient; 72 positions are no multiple of the kernels' block."""
    from dalle_tpu.ops.rotary import seq_yarn_table
    rot = jnp.asarray(seq_yarn_table(80, 4, 6e6)[0])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 72, 32))
    dense, flash = a_latent_layer("dense"), a_latent_layer("flash")
    params = dense.init(jax.random.PRNGKey(1), x, rotary=rot)
    assert set(params["params"]) == {
        "q", "kv_a", "kv_norm", "kv_b", "q_head_norm", "k_head_norm",
        "gate", "o"}
    assert params["params"]["gate"]["kernel"].shape == (32, 4)
    assert params["params"]["q_head_norm"]["scale"].shape == (12,)

    def loss(layer):
        return lambda p: jnp.sum(jnp.sin(layer.apply(p, x, rotary=rot)))
    want, want_g = jax.value_and_grad(loss(dense))(params)
    got, got_g = jax.value_and_grad(loss(flash))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
    # a key mask keeps the dense tier, as for every softmax layer
    mask = jnp.ones((2, 72), bool).at[:, 3].set(False)
    np.testing.assert_allclose(
        np.asarray(flash.apply(params, x, rotary=rot, key_mask=mask)),
        np.asarray(dense.apply(params, x, rotary=rot, key_mask=mask)),
        atol=1e-6)


def test_the_stack_says_its_kinds_its_tier_and_the_two_widths(monkeypatch):
    cfg = DalleConfig(**MODEL).transformer()
    layers = stack_layers(cfg)
    assert layers["kinds"] == ["kda", "kda", "mla"]
    assert layers["tier"] == "dense"                  # off the TPU
    assert layers["mla"] == {"heads": 4, "qk_dim": 12, "v_dim": 8}
    assert layers["kda"]["chunks"] == 2
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **kw: "flash")
    assert stack_layers(cfg)["tier"] == "flash"
    assert stack_layers(cfg)["flash"] == [
        {"layer": 2, "visited": 1, "full": 0, "total": 1}]
    cell = DalleConfig(**{**MODEL, "depth": 6, "text_seq_len": 256,
                          "image_fmap_size": 64, "image_size": 512})
    assert stack_layers(cell.transformer())["flash"] == [
        {"layer": i, "visited": 153, "full": 136, "total": 289}
        for i in (2, 5)]
    stack = Transformer(cfg).bind({})
    assert [type(layer.fn).__name__ for layer in stack.attn_layers] == [
        "KimiDeltaAttention", "KimiDeltaAttention", "MLAttention"]
    assert stack.attn_layers[2].fn.tier == "flash"
    assert [type(layer.fn).__name__ for layer in stack.ff_layers] == [
        "SwiGLUFeedForward", "MoEFeedForward", "MoEFeedForward"]
    # the multi-token-prediction block: one latent + routed layer
    block = Transformer(DalleConfig(**MODEL).mtp_transformer()).bind({})
    assert [type(layer.fn).__name__ for layer in block.attn_layers
            + block.ff_layers] == ["MLAttention", "MoEFeedForward"]
    # a fused answer is mha's: any other softmax layer is dense
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **kw: "fused")
    assert stack_layers(cfg)["tier"] == "dense"


def test_the_cached_paths_refuse_the_forms_by_name():
    stack = Transformer(DalleConfig(**MODEL).transformer()).bind({})
    for path in ("init_cache", "prefill", "decode_step", "decode_window"):
        with pytest.raises(NotImplementedError, match=r"kda/mla\+moe"):
            stack._refuse_cached(path)
    from dalle_tpu.models.dalle import init_dalle
    model, params = init_dalle(DalleConfig(**MODEL), jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match=r"kda/mla\+moe"):
        model.apply(params, jnp.ones((1, 8), jnp.int32),
                    jax.random.PRNGKey(0),
                    method=DALLE.generate_images_tokens)


# -- the loss's head and the configuration -----------------------------------

def test_the_mtp_pass_takes_the_vocabulary_of_the_position_it_predicts():
    cfg = DalleConfig(**MODEL)          # 8 text + 64 image, chunks of 8
    main = loss_segments(cfg, 8)
    ahead = loss_segments(cfg, 8, shift=1)
    assert main[0] == ((0, 8), (0, 48)) and main[1] == ((8, 16), (48, 80))
    # one row fewer, the boundary one position earlier
    assert ahead[:3] == [((0, 7), (0, 48)), ((7, 8), (48, 80)),
                         ((8, 16), (48, 80))]
    assert ahead[-1] == ((64, 71), (48, 80))
    assert sum(r1 - r0 for (r0, r1), _ in ahead) == 71
    head = loss_head(cfg, 2)
    assert head["mtp"]["elements_computed"] == 7 * 48 + 64 * 32
    assert head["elements_computed"] == 8 * 48 + 64 * 32
    assert len(head["mtp"]["segments"]) == len(ahead)
    assert "mtp" not in loss_head(DalleConfig(**{**MODEL, "mtp_depth": 0}), 2)


def test_the_configuration_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="latent-attention"):
        DalleConfig(mtp_depth=1)                       # the default block
    with pytest.raises(ValueError, match="mtp_depth"):
        DalleConfig(**{**MODEL, "mtp_depth": 2})
    with pytest.raises(ValueError, match="attention_gate"):
        BlockConfig(attention_gate="element_wise")
    # a linear layer takes seq_yarn only beside latent layers
    alone = {**MODEL, "depth": 1, "mtp_depth": 0,
             "block": {**BLOCK, "attention_layers": ("kda",)}}
    with pytest.raises(ValueError, match="no positional"):
        Transformer(DalleConfig(**alone).transformer()).bind({}).attn_layers
    assert DalleConfig().mtp_depth == 0
    assert BlockConfig().kda_lower_bound == 0.0
    assert BlockConfig().kda_beta_max == 2.0
