"""Olmo-Hybrid's forms (PR 38): the chunked delta rule with one decay a head
(``ops/kda.py``'s ``kda_chunked`` given a decay of shape (b, n, h)), Gated
DeltaNet (``gdn``: keys and values of two widths, beta up to 2, a SiLU
output gate), ungated grouped-query attention with RMSNorm over the whole
query and key projections (``gqa``) and OLMo 2's post-norm block; on the CPU
at a tiny size, against the plain reference
``benchmarks/reference/olmo_hybrid.py``, which imports nothing of the
program and computes the delta rule one position at a time.
"""

import dataclasses

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import adapter_olmo_hybrid as adapter
from benchmarks.kinds import train
from benchmarks.reference import olmo_hybrid as ref
from benchmarks.reference.solar_open2 import delta_rule
from dalle_tpu.config import (BlockConfig, DalleConfig, OptimConfig,
                              PrecisionConfig, TrainConfig)
from dalle_tpu.models.dalle import DALLE
from dalle_tpu.models.hybrid_attention import GatedDeltaNet
from dalle_tpu.models.transformer import (Transformer, TransformerLayer,
                                          stack_layers)
from dalle_tpu.ops.kda import CHUNK, kda_chunked

BLOCK = dict(
    attention_layers=("gdn", "gdn", "gdn", "gqa"), feed_forward="swiglu",
    norm="rmsnorm", norm_position="post", layerscale=False, positions="none",
    rms_norm_eps=1e-6, num_key_value_heads=4, qk_norm=True,
    linear_num_heads=4, linear_key_head_dim=12, linear_value_head_dim=20,
    short_conv_kernel_size=4, kda_beta_max=2.0, intermediate_size=48)
# one period; 8 + 8 x 8 = 72 positions: two chunks of 64, the second padded
MODEL = dict(num_text_tokens=40, text_seq_len=8, dim=32, depth=4, heads=4,
             dim_head=8, image_vocab_size=32, image_fmap_size=8,
             image_size=64, block=BLOCK, loss_chunk=8)
SEED = 2 ** 31 + 3838


def model_dict(**over) -> dict:
    """The configuration as a file would hold it (``cfg['model']``)."""
    return dataclasses.asdict(DalleConfig(**{**MODEL, **over}))


def a_batch(rows: int = 2):
    rng = np.random.default_rng(7)
    return (jnp.asarray(rng.integers(0, 40, (rows, 8)), jnp.int32),
            jnp.asarray(rng.integers(0, 32, (rows, 64)), jnp.int32))


# -- the core: one decay a head ------------------------------------------------

def core_inputs(n: int, h: int = 3, dk: int = 12, dv: int = 20):
    """Keys narrower than values, beta over all of (0, 2), decays from the
    public layer's initialisation and from saturated gates alike."""
    ks = jax.random.split(jax.random.PRNGKey(n), 6)
    q, k = (jax.random.normal(key, (2, n, h, dk)) for key in ks[:2])
    v = jax.random.normal(ks[2], (2, n, h, dv))
    f = 2.0 * jax.random.normal(ks[3], (2, n, h))
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (2, n, h)))
    a_log = jnp.log(jnp.array([0.5, 4.0, 15.0]))
    bias = jnp.array([-4.0, -2.0, 0.5])
    scale = 1.0 + 0.1 * jax.random.normal(ks[5], (dv,))
    return q, k, v, f, beta, a_log, bias, scale


def recurrent(q, k, v, f, beta, a_log, bias, scale, eps=1e-6):
    """The reference's delta rule one position at a time (its decay one
    number a head), then the head's RMS norm."""
    unit = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True)
                                       + 1e-6)
    q, k = unit(q) * q.shape[-1] ** -0.5, unit(k)
    g = -jnp.exp(a_log) * jax.nn.softplus(f + bias)
    with jax.default_matmul_precision("highest"):
        o = delta_rule(q, k, v, g[..., None], beta, "f32")
    return o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * scale


def chunked(q, k, v, f, beta, a_log, bias, scale, eps=1e-6):
    return kda_chunked(q, k, v, f, beta, a_log=a_log, bias=bias,
                       norm_scale=scale, eps=eps)[0]


@pytest.mark.parametrize("n", [150, 37, 64])
def test_the_per_head_decay_core_is_the_recurrent_form(n):
    """Outputs and the gradients of every input and parameter, beta up to 2
    (negative eigenvalues), d_k 12 against d_v 20, lengths that are and are
    not a multiple of the chunk."""
    args = core_inputs(n)
    assert float(jnp.max(args[4])) > 1.9 and float(jnp.min(args[4])) < 0.1
    np.testing.assert_allclose(chunked(*args), recurrent(*args), atol=2e-4,
                               rtol=2e-4)
    probe = jax.random.normal(jax.random.PRNGKey(1), (2, n, 3, 20))
    grads = [jax.grad(lambda *a, fn=fn: jnp.sum(fn(*a) * probe),
                      argnums=tuple(range(8)))(*args)
             for fn in (chunked, recurrent)]
    for ours, theirs in zip(*grads):
        np.testing.assert_allclose(ours, theirs, atol=5e-4 * float(
            jnp.max(jnp.abs(theirs))) + 1e-6, rtol=1e-3)


def test_the_per_head_form_is_the_channel_form_with_the_decay_broadcast():
    """The two forms of ``kda_chunked`` agree when the channel form is given
    the same decay in every key channel: the state's step, the inverse and
    the read-out they share, the grams they do not."""
    q, k, v, f, beta, a_log, bias, scale = core_inputs(100)
    dk = q.shape[-1]
    wide = jnp.broadcast_to(f[..., None], f.shape + (dk,))
    wide_bias = jnp.broadcast_to(bias[:, None], bias.shape + (dk,))
    ours, low = kda_chunked(q, k, v, f, beta, a_log=a_log, bias=bias,
                            norm_scale=scale, eps=1e-6)
    theirs, wide_low = kda_chunked(q, k, v, wide, beta, a_log=a_log,
                                   bias=wide_bias, norm_scale=scale, eps=1e-6)
    np.testing.assert_allclose(ours, theirs, atol=1e-4, rtol=1e-4)
    assert float(low) == pytest.approx(float(wide_low), rel=1e-6)

    def loss(q, k, v, f, beta, channel):
        f_in = jnp.broadcast_to(f[..., None], f.shape + (dk,)) if channel else f
        return jnp.sum(jnp.sin(kda_chunked(
            q, k, v, f_in, beta, a_log=a_log,
            bias=wide_bias if channel else bias, norm_scale=scale,
            eps=1e-6)[0]))
    for a, b in zip(jax.grad(loss, argnums=range(5))(q, k, v, f, beta, False),
                    jax.grad(loss, argnums=range(5))(q, k, v, f, beta, True)):
        np.testing.assert_allclose(a, b, atol=2e-4 * float(
            jnp.max(jnp.abs(b))) + 1e-6, rtol=1e-3)


def test_the_per_head_form_runs_under_its_own_scopes():
    """The gradient's operations of the per-head form carry ``attn/gdn_*``
    and none ``attn/kda_*`` (``obs/device.py`` maps them to ``gdn_chunk``
    and ``gdn_state``)."""
    from dalle_tpu.obs.device import scope_layer
    args = core_inputs(70)
    text = jax.jit(jax.grad(lambda *a: jnp.sum(chunked(*a)),
                            argnums=(0, 1, 2))).lower(*args).as_text(
        debug_info=True)
    assert "attn/gdn_chunk" in text and "attn/gdn_state" in text
    assert "attn/kda_" not in text
    assert scope_layer("jit(f)/attn/gdn_chunk/dot_general")[0] == "gdn_chunk"
    assert scope_layer("jit(f)/transpose(jvp(attn/gdn_state))/mul") == (
        "gdn_state", "bwd")
    assert scope_layer("jit(f)/attn/gdn_gates/dot_general")[0] == "attn_proj"


# -- the layers and the block against the reference --------------------------

def program_and_reference(over: dict):
    cfg = DalleConfig(**{**MODEL, **over})
    shapes = ref.Shapes.from_model(model_dict(**over))
    model = DALLE(cfg)
    text, ids = a_batch()
    ours = jax.jit(jax.value_and_grad(
        lambda p: model.apply(p, text, ids, return_loss=True),
        has_aux=True))(adapter.make_weights(shapes, SEED))
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(jax.value_and_grad(
            lambda p: ref.loss_fn(shapes, p, text, ids, chunk=8)))(
            ref.init_params(shapes, ref.seed_key(SEED)))
    return shapes, ours, theirs


@pytest.mark.parametrize("over,within", [
    ({"depth": 1, "block": {**BLOCK, "attention_layers": ("gdn",)}}, 2e-5),
    ({"depth": 1, "block": {**BLOCK, "attention_layers": ("gqa",)}}, 2e-5),
    ({}, 2e-4)],
    ids=["a_gdn_block", "a_qk_normed_gqa_block", "the_period"])
def test_loss_and_every_leaf_gradient_match_the_reference_in_float32(
        over, within):
    """Per leaf, the gap's norm over the leaf's (readings 2e-6 to 2e-5
    through one block, up to 1e-4 through the period, at ``a_log.0``, whose
    gradient is a sum over every position of small terms: float32 rounding
    through the recurrent layers). Every block post-norm."""
    shapes, ((loss, aux), grads), (ref_loss, ref_grads) = (
        program_and_reference(over))
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    ours = adapter.named_leaves(shapes, grads)
    assert set(ours) == set(ref_grads)
    for name, g in ref_grads.items():
        gap = np.linalg.norm(np.asarray(ours[name]) - np.asarray(g))
        assert gap <= within * max(np.linalg.norm(np.asarray(g)), 1e-3), name
    if "gdn" in shapes.attention_layers:
        assert -CHUNK * 16 * 10 < float(aux["gdn_logdecay_min"]) < 0.0
        assert 0.0 < float(aux["gdn_beta_over_one_pct"]) < 100.0
    else:
        assert "gdn_logdecay_min" not in aux


def test_the_gdn_layer_alone_is_the_references():
    """``GatedDeltaNet`` on its own, at 100 positions, against the
    reference's ``gdn`` with the same leaves; its two counters."""
    shapes = ref.Shapes.from_model(model_dict())
    params = ref.init_params(shapes, ref.seed_key(SEED))
    lp = {k[:-2]: v for k, v in params.items() if k.endswith(".0")}
    layer = GatedDeltaNet(32, 4, 12, 20)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 100, 32))
    tree = adapter.program_tree(shapes, lambda name, l: params[
        name if l is None else f"{name}.{l}"])
    weights = {"params": tree["params"]["transformer"]["attn_0"]}
    (y, counters) = layer.apply(weights, x)
    with jax.default_matmul_precision("highest"):
        theirs = ref.gdn(shapes, x, lp, "f32")
    np.testing.assert_allclose(y, theirs, atol=2e-5 * float(
        jnp.max(jnp.abs(theirs))))
    beta = 2.0 * jax.nn.sigmoid(x @ lp["w_b"])
    assert float(counters["gdn_beta_over_one_pct"]) == pytest.approx(
        100.0 * float(jnp.mean(beta > 1.0)), abs=1e-4)
    assert float(counters["gdn_logdecay_min"]) < 0.0


def test_a_post_norm_layer_norms_its_output_and_not_its_input():
    """``TransformerLayer`` with ``norm_position`` "post": ``norm(fn(x))``;
    "pre" (every other block): ``fn(norm(x))``. Same leaves either way."""
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 5, 16)) * 3.0 + 1.0
    dense = nn.Dense(16, use_bias=False)

    def run(position):
        layer = TransformerLayer(16, 1, dense, norm_kind="rmsnorm",
                                 layerscale=False, norm_position=position)
        params = layer.init(jax.random.PRNGKey(0), x)
        return params, layer.apply(params, x)
    post_params, post = run("post")
    pre_params, pre = run("pre")
    assert jax.tree.structure(post_params) == jax.tree.structure(pre_params)
    w = next(leaf for leaf in jax.tree.leaves(post_params)
             if leaf.shape == (16, 16))
    rms = lambda t: t * jax.lax.rsqrt(jnp.mean(t * t, -1, keepdims=True)
                                      + 1e-6)
    np.testing.assert_allclose(post, rms(x @ w), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(pre, rms(x) @ w, rtol=1e-5, atol=1e-5)


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
# bfloat16 against the float32 reference at this width: at 32 wide the
# post-norm stack's gradients move by several % under a weight's rounding
BANDS = {"float32": {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                     "leaf_grad_gap": 2e-3, "leaf_change_gap": 2e-2},
         "bfloat16": {"loss_gap": 0.05, "grad_norm_gap": 0.5,
                      "leaf_grad_gap": 1.0, "leaf_change_gap": 0.8}}


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_three_adafactor_steps_through_fit_follow_the_reference(compute):
    """A 4-layer toy stack through the trainer every cell uses:
    ``DalleTrainer.fit`` with the kind's own hooks, against
    ``reference/olmo_hybrid.py``'s three steps; ``fit()``'s records carry
    the two counters."""
    from benchmarks.kinds import train_olmo_hybrid
    cfg = {"model": model_dict(), "name": "tiny"}
    trainer = a_trainer(compute)
    assert trainer.num_params == train_olmo_hybrid.arith_olmo_hybrid \
        .held_param_count(cfg["model"])
    program = train_olmo_hybrid._run.program_first_steps(trainer, CELL, cfg,
                                                         SEED)
    reference = train_olmo_hybrid._run.reference_numbers(CELL, cfg, SEED)
    compared = train.compare(program, reference)
    for name, band in BANDS[compute].items():
        assert compared[name] <= band, (name, compared[name])


# -- what the stack says of itself, and what it refuses -------------------------

def test_the_stack_says_its_kinds_its_tier_and_the_two_widths(monkeypatch):
    import dalle_tpu.models.transformer as transformer
    cfg = DalleConfig(**{**MODEL, "text_seq_len": 256,
                         "image_fmap_size": 64}).transformer()
    monkeypatch.setattr(transformer, "attention_tier",
                        lambda *a, **k: "flash")
    layers = stack_layers(cfg)
    assert layers["kinds"] == ["gdn", "gdn", "gdn", "gqa"]
    assert layers["tier"] == "flash"
    assert layers["gdn"] == {"heads": 4, "key_dim": 12, "value_dim": 20,
                             "chunk": CHUNK, "chunks": 68}
    assert layers["gqa"] == {"heads": 4, "head_dim": 8, "kv_heads": 4}
    assert [f["layer"] for f in layers["flash"]] == [3]


def test_the_cached_paths_refuse_the_forms_by_name():
    from dalle_tpu.models.dalle import init_dalle
    from dalle_tpu.serve.engine import DecodeEngine
    cfg = DalleConfig(**MODEL)
    model = Transformer(cfg.transformer())
    x = jnp.zeros((1, 72, 32))
    params = model.init(jax.random.PRNGKey(0), x)
    with pytest.raises(NotImplementedError, match="gdn"):
        model.apply(params, 1, method=Transformer.init_cache)
    dalle, weights = init_dalle(cfg, jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match=r"gdn/gqa\+swiglu"):
        DecodeEngine(dalle, weights, slots=2)
    with pytest.raises(NotImplementedError, match="gdn"):
        dalle.apply(weights, jnp.ones((1, 8), jnp.int32),
                    jax.random.PRNGKey(0),
                    method=DALLE.generate_images_tokens)
    # a default attention kind with post-norm blocks is refused too
    post = dataclasses.replace(
        DalleConfig(dim=32, depth=1, heads=2, dim_head=16, text_seq_len=8,
                    image_fmap_size=4, num_text_tokens=40,
                    image_vocab_size=32).transformer(),
        block=BlockConfig(norm_position="post"))
    model = Transformer(post)
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 24, 32)))
    with pytest.raises(NotImplementedError, match="norm"):
        model.apply(params, 1, method=Transformer.init_cache)


def test_the_configuration_refuses_what_it_cannot_build():
    with pytest.raises(ValueError, match="norm_position"):
        BlockConfig(norm_position="sandwich")
    assert not BlockConfig(norm_position="post").is_default
    assert BlockConfig().is_default
    assert BlockConfig(linear_head_dim=64).linear_widths == (64, 64)
    assert BlockConfig(linear_head_dim=64, linear_key_head_dim=96,
                       linear_value_head_dim=192).linear_widths == (96, 192)
