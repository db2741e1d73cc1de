"""DALLE model tests: vocab layout, loss, masks, generation consistency, CLIP."""

import hashlib
import json
import os
import re

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dalle_tpu import obs
from dalle_tpu.config import (BlockConfig, ClipConfig, DalleConfig, MeshConfig,
                              OptimConfig, PrecisionConfig, TrainConfig)
from dalle_tpu.models import dalle as dalle_module
from dalle_tpu.models.clip import CLIP, init_clip
from dalle_tpu.models.dalle import (DALLE, init_dalle, loss_head,
                                    table_grad_paths)
from dalle_tpu.ops import table_lookup

CFG = DalleConfig(num_text_tokens=100, text_seq_len=8, dim=32, depth=2, heads=2,
                  dim_head=16, image_vocab_size=64, image_fmap_size=4,
                  attn_types=("full", "axial_row"))


@pytest.fixture(scope="module")
def dalle():
    return init_dalle(CFG, jax.random.PRNGKey(0), batch=2)


def rand_inputs(key=0, b=2):
    rng = np.random.RandomState(key)
    text = jnp.asarray(rng.randint(1, 100, (b, CFG.text_seq_len)), jnp.int32)
    img = jnp.asarray(rng.randint(0, 64, (b, CFG.image_seq_len)), jnp.int32)
    return text, img


class TestForward:
    def test_loss_and_logits_shapes(self, dalle):
        model, params = dalle
        text, img = rand_inputs()
        loss, aux = model.apply(params, text, img, return_loss=True)
        assert loss.shape == () and jnp.isfinite(loss)
        logits = model.apply(params, text, img)
        assert logits.shape == (2, CFG.total_seq_len, CFG.total_tokens)

    def test_logits_mask_bands(self, dalle):
        """Text positions must only be able to predict text tokens; image
        positions only image tokens (reference logits_mask :428-439)."""
        model, params = dalle
        text, img = rand_inputs()
        logits = np.asarray(model.apply(params, text, img))
        ntt = CFG.num_text_tokens + CFG.text_seq_len
        # text rows: image band masked
        assert (logits[:, :CFG.text_seq_len, ntt:] <= -1e8).all()
        assert (logits[:, :CFG.text_seq_len, :ntt] > -1e8).any()
        # image rows: text band masked
        assert (logits[:, CFG.text_seq_len:, :ntt] <= -1e8).all()
        assert (logits[:, CFG.text_seq_len:, ntt:] > -1e8).any()

    def test_unique_pad_remap_changes_output(self, dalle):
        """0-pads remap to a unique id per position regardless of surrounding
        text (reference :370,578-579), and moving a pad changes the output."""
        model, params = dalle
        _, img = rand_inputs()
        t1 = jnp.asarray([[5, 0, 7, 0, 9, 11, 13, 15]], jnp.int32)
        t2 = jnp.asarray([[21, 0, 33, 0, 45, 47, 49, 51]], jnp.int32)
        r1 = np.asarray(model.apply(params, t1, method=DALLE.remap_and_bos))
        r2 = np.asarray(model.apply(params, t2, method=DALLE.remap_and_bos))
        # bos prepended, real tokens preserved
        assert r1[0, 0] == 0 and r1[0, 1] == 5 and r1[0, 3] == 7
        # pads (input cols 1 and 3 → remapped cols 2 and 4) get per-position
        # unique ids, identical across different texts
        assert r1[0, 2] == CFG.num_text_tokens + 1
        assert r1[0, 4] == CFG.num_text_tokens + 3
        assert r1[0, 2] == r2[0, 2] and r1[0, 4] == r2[0, 4]
        assert r1[0, 2] != r1[0, 4]
        # pad moved to a different position → different representation
        t3 = jnp.asarray([[5, 7, 0, 0, 9, 11, 13, 15]], jnp.int32)
        l1 = model.apply(params, t1, img[:1])
        l3 = model.apply(params, t3, img[:1])
        assert not np.allclose(np.asarray(l1), np.asarray(l3), atol=1e-4)

    def test_loss_weighting(self, dalle):
        model, params = dalle
        text, img = rand_inputs()
        loss, aux = model.apply(params, text, img, return_loss=True)
        expect = (aux["loss_text"] + CFG.loss_img_weight * aux["loss_img"]) / (
            CFG.loss_img_weight + 1)
        np.testing.assert_allclose(float(loss), float(expect), rtol=1e-6)

    def test_cfg_dropout_nulls_text(self, dalle):
        model, params = dalle
        text, img = rand_inputs()
        l_cond = model.apply(params, text, img)
        l_null = model.apply(params, text, img, null_cond_prob=1.0,
                             rngs={"cfg": jax.random.PRNGKey(0)})
        l_pads = model.apply(params, jnp.zeros_like(text), img)
        # full nulling == all-pad text
        np.testing.assert_allclose(np.asarray(l_null), np.asarray(l_pads), atol=1e-5)
        assert not np.allclose(np.asarray(l_null), np.asarray(l_cond), atol=1e-4)

    def test_text_length_assert(self, dalle):
        model, params = dalle
        _, img = rand_inputs()
        with pytest.raises(AssertionError, match="text must be"):
            model.apply(params, jnp.zeros((2, 5), jnp.int32), img)


class TestGeneration:
    def test_greedy_generation_is_self_consistent(self, dalle):
        """Tokens sampled greedily through the cached decode path must be the
        argmax of the full teacher-forced forward at every position — ties the
        generation path to the training path end-to-end."""
        model, params = dalle
        text, _ = rand_inputs(b=1)
        key = jax.random.PRNGKey(3)
        toks = model.apply(params, text, key, temperature=1e-12,
                           filter_thres=0.999, method=DALLE.generate_images_tokens)
        logits = model.apply(params, text, toks)
        ntt = CFG.num_text_tokens + CFG.text_seq_len
        # sequence = [bos, t_1..t_T, img_1..]: row T+k (0-based) predicts image
        # token k, so image rows are logits[:, text_seq_len:]
        img_rows = np.asarray(logits[:, CFG.text_seq_len:, ntt:])
        expect = img_rows.argmax(-1)
        np.testing.assert_array_equal(np.asarray(toks), expect)

    def test_priming_keeps_prefix(self, dalle):
        model, params = dalle
        text, img = rand_inputs(b=1)
        prime = img[:, :7]
        toks = model.apply(params, text, jax.random.PRNGKey(1),
                           image_prime=prime, method=DALLE.generate_images_tokens)
        assert toks.shape == (1, CFG.image_seq_len)
        np.testing.assert_array_equal(np.asarray(toks[:, :7]), np.asarray(prime))

    @pytest.mark.slow  # ~12s; the bf16 decode path runs fast-tier through
    # the generate CLI (--bf16 rerank roundtrip) and the serve-engine bf16
    # exactness tests — the statistical f32-agreement check rides slow
    def test_bf16_decode_tracks_f32_greedy(self, dalle):
        """The bf16 weights+cache decode path (DalleWithVae precision=
        'bfloat16') must produce mostly the same greedy tokens as f32 — it is
        a precision option, not a different sampler (ties the fast path to
        the reference semantics)."""
        import jax.numpy as jnp
        from dalle_tpu.train.train_state import cast_floating
        model, params = dalle
        text, _ = rand_inputs(b=2)
        key = jax.random.PRNGKey(3)
        f32 = model.apply(params, text, key, temperature=1e-12,
                          filter_thres=0.999,
                          method=DALLE.generate_images_tokens)
        bf16 = model.apply(cast_floating(params, jnp.bfloat16), text, key,
                           temperature=1e-12, filter_thres=0.999,
                           cache_dtype=jnp.bfloat16,
                           method=DALLE.generate_images_tokens)
        agree = (np.asarray(f32) == np.asarray(bf16)).mean()
        # greedy argmax under bf16 rounding on an untrained (near-uniform)
        # model is the worst case; real checkpoints agree far more often
        assert agree > 0.5, agree
        assert bf16.shape == f32.shape and bf16.dtype == f32.dtype
        # int8-quantized KV cache (precision='bf16_int8kv'): same contract
        int8 = model.apply(cast_floating(params, jnp.bfloat16), text, key,
                           temperature=1e-12, filter_thres=0.999,
                           cache_dtype=jnp.int8,
                           method=DALLE.generate_images_tokens)
        agree8 = (np.asarray(f32) == np.asarray(int8)).mean()
        assert agree8 > 0.5, agree8
        assert int8.shape == f32.shape and int8.dtype == f32.dtype

    def test_cfg_changes_samples(self, dalle):
        model, params = dalle
        text, _ = rand_inputs(b=1)
        k = jax.random.PRNGKey(5)
        t1 = model.apply(params, text, k, cond_scale=1.0,
                         method=DALLE.generate_images_tokens)
        t2 = model.apply(params, text, k, cond_scale=5.0,
                         method=DALLE.generate_images_tokens)
        assert not np.array_equal(np.asarray(t1), np.asarray(t2))

    def test_generate_texts_tokens_in_text_band(self, dalle):
        model, params = dalle
        out = model.apply(params, jax.random.PRNGKey(2),
                          jnp.asarray([[4, 9]], jnp.int32),
                          method=DALLE.generate_texts_tokens)
        assert out.shape == (1, CFG.text_seq_len)
        assert (np.asarray(out) < CFG.num_text_tokens + CFG.text_seq_len).all()
        np.testing.assert_array_equal(np.asarray(out[:, :2]), [[4, 9]])


class TestCLIP:
    CCFG = ClipConfig(dim_text=32, dim_image=32, dim_latent=32,
                      num_text_tokens=100, text_enc_depth=1, text_seq_len=8,
                      text_heads=2, visual_enc_depth=1, visual_heads=2,
                      visual_image_size=32, visual_patch_size=8)

    def test_loss_and_scores(self):
        model, params = init_clip(self.CCFG, jax.random.PRNGKey(0), batch=2)
        text = jnp.asarray(np.random.RandomState(0).randint(1, 100, (2, 8)), jnp.int32)
        img = jnp.asarray(np.random.RandomState(1).rand(2, 32, 32, 3), jnp.float32)
        loss = model.apply(params, text, img, return_loss=True)
        assert loss.shape == () and jnp.isfinite(loss)
        scores = model.apply(params, text, img)
        assert scores.shape == (2,)

    def test_latents_normalized(self):
        model, params = init_clip(self.CCFG, jax.random.PRNGKey(0))
        text = jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0]], jnp.int32)
        lat = model.apply(params, text, method=CLIP.embed_text)
        np.testing.assert_allclose(float(jnp.linalg.norm(lat)), 1.0, rtol=1e-5)

    def test_text_padding_ignored(self):
        """Pad positions must not affect the text latent: perturbing the pad
        token's embedding row must leave the latent unchanged (key_mask blocks
        pad keys; masked_mean drops pad outputs)."""
        import copy
        model, params = init_clip(self.CCFG, jax.random.PRNGKey(0))
        t1 = jnp.asarray([[1, 2, 3, 0, 0, 0, 0, 0]], jnp.int32)
        lat1 = model.apply(params, t1, method=CLIP.embed_text)
        mutated = copy.deepcopy(jax.device_get(params))
        emb = jnp.asarray(mutated["params"]["text_emb"]["embedding"])
        mutated["params"]["text_emb"]["embedding"] = emb.at[0].add(100.0)
        lat2 = model.apply(mutated, t1, method=CLIP.embed_text)
        np.testing.assert_allclose(np.asarray(lat1), np.asarray(lat2), atol=1e-5)
        # a real token's row, by contrast, must matter
        mutated["params"]["text_emb"]["embedding"] = emb.at[2].add(100.0)
        lat3 = model.apply(mutated, t1, method=CLIP.embed_text)
        assert not np.allclose(np.asarray(lat1), np.asarray(lat3), atol=1e-3)


def test_chunked_loss_matches_full():
    """loss_chunk computes the head+CE in rematerialized chunks; loss and
    grads must equal the full-logits path bit-for-bit (same math, different
    materialization)."""
    import numpy as np
    from dalle_tpu.config import DalleConfig
    from dalle_tpu.models.dalle import init_dalle

    rng = np.random.RandomState(0)
    kw = dict(num_text_tokens=64, text_seq_len=8, dim=32, depth=1, heads=2,
              dim_head=16, image_size=16, image_vocab_size=64,
              image_fmap_size=2)
    text = rng.randint(1, 64, (2, 8))
    ids = rng.randint(0, 64, (2, 4))
    m_full, params = init_dalle(DalleConfig(**kw), jax.random.PRNGKey(0))
    m_chunk, _ = init_dalle(DalleConfig(**kw, loss_chunk=4),
                            jax.random.PRNGKey(0))

    def loss(m):
        return lambda p: m.apply(p, text, ids, return_loss=True)[0]

    assert abs(float(loss(m_full)(params)) - float(loss(m_chunk)(params))) < 1e-5
    g_full = jax.grad(loss(m_full))(params)
    g_chunk = jax.grad(loss(m_chunk))(params)
    for a, b in zip(jax.tree.leaves(g_full), jax.tree.leaves(g_chunk)):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=2e-5, atol=1e-6)


# -- the token tables' lookup and its backward (ops/table_lookup.py) ----------

ROWS = 90          # a tied table of 58 text rows and 32 codes
ID_PATTERNS = {
    "all_equal": lambda r: np.full((130,), 7),
    "first_and_last_row": lambda r: np.array([0, ROWS - 1, 0, ROWS - 1, ROWS - 1]),
    "count_of_1031": lambda r: r.integers(0, ROWS, (1031,)),
    "count_of_one": lambda r: np.array([5]),
    "batch_of_3x50": lambda r: r.integers(0, ROWS, (3, 50)),
    "tied_text_then_codes_offset": lambda r: np.concatenate(
        [r.integers(0, 58, (2, 9)), r.integers(0, 32, (2, 16)) + 58], axis=1),
}


def _steer_to_product(monkeypatch):
    """The rule takes the product for every 16-bit table, whatever its
    width (a test's way to reach the path at a tiny width)."""
    monkeypatch.setattr(table_lookup, "WIDE_ROW", 0)


def _within_one_rounding(got, exact32):
    """``got`` (bfloat16) is the float32 sum rounded once: half a bfloat16
    step of the sum, with the float32 sum's own order-of-addition slack."""
    got = np.asarray(got.astype(jnp.float32))
    np.testing.assert_allclose(got, exact32, rtol=2.0 ** -8, atol=1e-5)


@pytest.mark.parametrize("pattern", sorted(ID_PATTERNS))
@pytest.mark.parametrize("width", [512, 5120])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_table_lookup_forward_is_take_and_backward_is_the_float32_sum(
        pattern, width, dtype, monkeypatch):
    rng = np.random.default_rng(5)
    dtype = jnp.dtype(dtype)
    ids = jnp.asarray(ID_PATTERNS[pattern](rng), jnp.int32)
    table = jnp.asarray(rng.standard_normal((ROWS, width)), dtype)
    # multiples of 1/64: every float32 partial sum is exact in any order
    g = jnp.asarray(rng.integers(-256, 257, ids.shape + (width,)) / 64, dtype)
    exact = np.asarray(jnp.zeros((ROWS, width), jnp.float32)
                       .at[ids.reshape(-1)].add(
                           g.reshape(-1, width).astype(jnp.float32)))

    rows = jnp.take(table, ids, axis=0)
    embed = nn.Embed(ROWS, width, param_dtype=dtype)
    for lookup in (table_lookup.take_rows, table_lookup._take_rows,
                   lambda t, i: embed.apply({"params": {"embedding": t}}, i)):
        got = lookup(table, ids)
        assert got.dtype == dtype and bool((got == rows).all())

    # the product, reached directly: exact in float32, one rounding in bf16
    d_table, = jax.vjp(lambda t: table_lookup._take_rows(t, ids), table)[1](g)
    assert d_table.dtype == dtype and d_table.shape == table.shape
    if dtype == jnp.float32:
        assert bool((np.asarray(d_table) == exact).all())
    else:
        _within_one_rounding(d_table, exact)

    # through the rule: the product where it picks it, else take's own
    path = table_lookup.grad_path(ROWS, width, dtype)
    assert path == ("product" if (width, dtype) == (5120, jnp.bfloat16)
                    else "scatter")
    ruled, = jax.vjp(lambda t: table_lookup.take_rows(t, ids), table)[1](g)
    if path == "product":
        assert bool((ruled == d_table).all())
    else:
        seeds, = jax.vjp(lambda t: jnp.take(t, ids, axis=0), table)[1](g)
        assert bool((ruled == seeds).all())


def test_table_lookup_repeated_ids_sum_in_float32():
    """130 copies of one id, random bfloat16 rows: the product rounds the
    float32 sum once, where a bfloat16 running sum drifts."""
    rng = np.random.default_rng(6)
    ids = jnp.full((130,), 3, jnp.int32)
    g = jnp.asarray(rng.standard_normal((130, 256)), jnp.bfloat16)
    table = jnp.zeros((8, 256), jnp.bfloat16)
    d_table, = jax.vjp(lambda t: table_lookup._take_rows(t, ids), table)[1](g)
    exact = np.zeros((8, 256), np.float32)
    exact[3] = np.asarray(g.astype(jnp.float32)).sum(0, dtype=np.float64)
    _within_one_rounding(d_table, exact)


BENCH_CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                             "configs")


@pytest.mark.parametrize("config, batch, expected", [
    ("rudalle_malevich", 4, {"text_emb": ("scatter", 16512, 2048, 516),
                             "image_emb": ("scatter", 8192, 2048, 4096)}),
    ("dalle_small", 64, {"text_emb": ("scatter", 10256, 512, 16448),
                         "image_emb": ("scatter", 8192, 512, 16384)}),
    ("deepseek_v2_share16", 8, {"text_emb": ("product", 4608, 5120, 2056),
                                "image_emb": ("product", 8192, 5120, 8192)}),
])
def test_grad_path_for_the_benchmarks_real_tables(config, batch, expected):
    with open(os.path.join(BENCH_CONFIGS, f"{config}.json")) as f:
        cfg = DalleConfig(**json.load(f)["model"])
    got = table_grad_paths(cfg, jnp.bfloat16, batch)
    assert {k: tuple(v.values()) for k, v in got.items()} == expected
    # a float32 table would need six MXU passes: it keeps the scatter
    assert {v["path"] for v in table_grad_paths(cfg, jnp.float32,
                                                batch).values()} == {"scatter"}


@pytest.mark.parametrize("rows, width, dtype, expected", [
    (8192, 4096, "bfloat16", "scatter"),     # the scatter's steady side
    (8192, 4224, "bfloat16", "product"),
    (8192, 8192, "float16", "product"),
    (8192, 5120, "float32", "scatter"),      # six MXU passes
    (16384, 6144, "bfloat16", "scatter"),    # the product grows with the rows
    (8192, 5120, "int8", "scatter"),         # the decode path's table
])
def test_grad_path_reads_shapes_and_dtypes(rows, width, dtype, expected):
    assert table_lookup.grad_path(rows, width, dtype) == expected


_MLA = dict(attention="mla", norm="rmsnorm", layerscale=False,
            positions="seq_yarn", q_lora_rank=24, kv_lora_rank=16,
            qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
            intermediate_size=48)
_MOE = dict(_MLA, feed_forward="moe", first_dense_layers=1,
            moe_intermediate_size=16, n_routed_experts=16, n_shared_experts=2,
            num_experts_per_tok=3, n_group=4, topk_group=2,
            routed_scaling_factor=16.0)
_BASE = dict(num_text_tokens=50, text_seq_len=8, dim=32, depth=2, heads=4,
             dim_head=8, image_vocab_size=32, image_fmap_size=4,
             image_size=32, loss_chunk=8)
# configuration, and its parameter tree's digest at the seed commit
# (sorted "path shape dtype" lines, sha256) with the number of leaves
BLOCK_KINDS = {
    "geglu": (DalleConfig(**_BASE), "a79f30d02d14dacb", 32),
    "tied": (DalleConfig(**_BASE, share_input_output_emb=True),
             "4a1c7c57d3b0dced", 30),
    "swiglu": (DalleConfig(**_BASE, block=BlockConfig(feed_forward="swiglu",
                                                      **_MLA)),
               "096b2b668a314735", 29),
    "moe": (DalleConfig(**_BASE, block=BlockConfig(**_MOE), heads_held=2,
                        experts_held=4), "4d3f2b20753a6fe0", 33),
}


def _leaves(tree):
    return {jax.tree_util.keystr(p): v
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("kind", sorted(BLOCK_KINDS))
def test_training_gradient_agrees_with_the_seeds_path(kind, monkeypatch):
    """bfloat16 compute over float32 masters, as the trainer's step: with the
    product steered on, every leaf's gradient is the seed's (``jnp.take``'s
    scatter); the tables' differ by the roundings the scatter adds."""
    cfg, digest, n_leaves = BLOCK_KINDS[kind]
    model, params = init_dalle(cfg, jax.random.PRNGKey(0), batch=2)
    leaves = _leaves(params)
    lines = sorted(f"{k} {tuple(v.shape)} {v.dtype}" for k, v in leaves.items())
    assert len(lines) == n_leaves
    assert hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16] == digest

    rng = np.random.default_rng(7)
    text = jnp.asarray(rng.integers(0, 50, (4, 8)), jnp.int32)   # 0s: pads
    ids = jnp.asarray(rng.integers(0, 32, (4, 16)), jnp.int32)

    def grads():      # a new function each call: traced under the rule as set
        from dalle_tpu.train.train_state import cast_floating
        return jax.jit(jax.grad(lambda p: model.apply(
            cast_floating(p, jnp.bfloat16), text, ids, return_loss=True)[0]))(
                params)

    with monkeypatch.context() as seed:
        seed.setattr(dalle_module, "take_rows",
                     lambda table, i: jnp.take(table, i, axis=0))
        theirs = _leaves(grads())
    _steer_to_product(monkeypatch)
    ours = _leaves(grads())
    tables = [k for k in ours if re.search(r"(text_emb|image_emb)'\]\['embedding|shared_emb", k)]
    assert len(tables) == (1 if kind == "tied" else 2)
    for name, g in ours.items():
        a, b = np.asarray(g), np.asarray(theirs[name])
        if name in tables:
            # a few bfloat16 steps of the largest entry: the scatter rounds
            # after every repeated id (each pad id repeats down the batch)
            np.testing.assert_allclose(a, b, atol=2.0 ** -6 * np.abs(b).max(),
                                       err_msg=name)
            assert np.abs(b).max() > 0
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def _tiny_trainer(tmp_path, mesh_cfg, compute, devices=None):
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    # a width no other test trains, so no cached step is handed over
    cfg = DalleConfig(num_text_tokens=32, text_seq_len=8, dim=40, depth=1,
                      heads=2, dim_head=20, image_size=16, image_vocab_size=32,
                      image_fmap_size=4)
    tc = TrainConfig(batch_size=8, checkpoint_dir=str(tmp_path),
                     preflight_checkpoint=False, mesh=mesh_cfg,
                     precision=PrecisionConfig(compute=compute),
                     optim=OptimConfig(learning_rate=1e-2))
    return DalleTrainer(cfg, tc, mesh=build_mesh(mesh_cfg, devices=devices))


def _step_text(tr):
    """The trainer's step body, lowered: a new body each call, so that no
    trace made under another rule is handed over."""
    from dalle_tpu.train.trainer_dalle import _dalle_step_body
    body = _dalle_step_body.__wrapped__(tr.model, dtype=jnp.bfloat16)
    text = np.ones((8, 8), np.int32)
    ids = np.zeros((8, 16), np.int32)
    return jax.jit(body).lower(tr.state, *tr._put_batch((text, ids)),
                               jax.random.PRNGKey(0)).as_text()


def _table_scatters(stablehlo: str) -> int:
    """scatters into a table-shaped operand: (40, 40) text, (32, 40) codes."""
    return len(re.findall(r"\}\) : \(tensor<(?:40|32)x40xbf16>, "
                          r"tensor<\d+x\d+x1xi32>", stablehlo))


def test_trainer_step_loses_two_scatters_where_the_rule_picks_the_product(
        tmp_path, monkeypatch):
    one = dict(mesh_cfg=MeshConfig(), devices=jax.devices()[:1])
    tracer = obs.configure()
    try:
        rule_keeps = _step_text(_tiny_trainer(tmp_path / "a", compute="bfloat16",
                                              **one))
        with monkeypatch.context() as seed:
            seed.setattr(dalle_module, "take_rows",
                         lambda table, i: jnp.take(table, i, axis=0))
            seeds_text = _step_text(_tiny_trainer(
                tmp_path / "b", compute="bfloat16", **one))
        _steer_to_product(monkeypatch)
        steered = _tiny_trainer(tmp_path / "c", compute="bfloat16", **one)
        rule_picks = _step_text(steered)
        spans = [s for s in tracer.snapshot_spans()
                 if s[0] == "init/build_step"]
    finally:
        obs.disable()
    assert rule_keeps == seeds_text          # unchanged: the seed's
    n = _table_scatters(rule_keeps)
    assert n >= 2 and _table_scatters(rule_picks) == n - 2
    assert rule_picks.count('"stablehlo.scatter"') == \
        rule_keeps.count('"stablehlo.scatter"') - 2
    # the span says what was chosen, per table
    assert spans[0][5]["text_emb"] == {"path": "scatter", "rows": 40,
                                       "width": 40, "ids": 72}
    head = spans[-1][5].pop("head")
    # (what the stack is built from rides the same span since PR 32)
    assert spans[-1][5].pop("layers") == {
        "kinds": ["mha"] * steered.model_cfg.depth, "tier": "dense",
        "mha": {"heads": steered.model_cfg.heads,
                "head_dim": steered.model_cfg.dim_head}}
    assert spans[-1][5] == {
        "text_emb": {"path": "product", "rows": 40, "width": 40, "ids": 72},
        "image_emb": {"path": "product", "rows": 32, "width": 40, "ids": 128}}
    assert head == loss_head(steered.model_cfg, 8)


def test_sharded_product_backward_matches_one_device(tmp_path, monkeypatch):
    """text_emb's rows over ("tp", "fsdp"), image_emb replicated, the batch
    over dp x fsdp: the product's table gradients and the first loss are one
    device's."""
    from dalle_tpu.train.trainer_dalle import _make_dalle_loss_fn
    _steer_to_product(monkeypatch)
    rng = np.random.RandomState(3)
    text = rng.randint(0, 32, (8, 8))
    ids = rng.randint(0, 32, (8, 16))
    got = {}
    for name, mesh_cfg, devices in [
            ("mesh", MeshConfig(dp=2, fsdp=2, tp=2), None),
            ("one", MeshConfig(), jax.devices()[:1])]:
        tr = _tiny_trainer(tmp_path / name, mesh_cfg, "bfloat16", devices)
        emb = tr.state.params["params"]["text_emb"]["embedding"]
        if name == "mesh":
            assert emb.sharding.spec[0] == ("tp", "fsdp")
        loss_fn = _make_dalle_loss_fn(tr.model, null_cond_prob=0.0,
                                      use_dropout=False, dtype=jnp.bfloat16)
        (loss, _), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(
            tr.state.params, *tr._put_batch((text, ids)),
            jax.random.PRNGKey(0))
        got[name] = (float(loss), grads["params"])
        assert tr.train_step(text, ids)["loss"] == pytest.approx(float(loss),
                                                                 rel=1e-6)
    assert got["mesh"][0] == pytest.approx(got["one"][0], rel=2e-3)
    for table in ("text_emb", "image_emb"):
        a = np.asarray(got["mesh"][1][table]["embedding"])
        b = np.asarray(got["one"][1][table]["embedding"])
        np.testing.assert_allclose(a, b, atol=2.0 ** -6 * np.abs(b).max(),
                                   err_msg=table)
        assert np.abs(b).max() > 0


# -- the training loss's head, per segment of positions -----------------------

def _masked_full_width_loss(mdl, text, image_ids):
    """The loss as the full-width head computes it: every position against
    all ``total_tokens`` columns, the static mask written over the forbidden
    ones (``_finish``), one cross-entropy over the whole row."""
    c = mdl.cfg
    text_b = mdl.remap_and_bos(text)
    tokens = jnp.concatenate(
        [mdl.embed_text(text_b), mdl.embed_image(image_ids)],
        axis=1)[:, :c.total_seq_len]
    out = mdl.transformer(mdl._stabilize(tokens), deterministic=True)
    labels = jnp.concatenate(
        [text_b[:, 1:], image_ids + mdl.num_text_tokens], axis=1)
    ce = optax.softmax_cross_entropy_with_integer_labels(
        mdl._finish(out, (0, c.total_seq_len)).astype(jnp.float32), labels)
    loss_text = ce[:, :c.text_seq_len].mean()
    loss_img = ce[:, c.text_seq_len:].mean()
    loss = ((loss_text + c.loss_img_weight * loss_img)
            / (c.loss_img_weight + 1))
    return loss, {"loss_text": loss_text, "loss_img": loss_img}


# 8 text + 16 image positions; 58 text columns (50 + 8 pads) + 44 codes
_SEG = dict(num_text_tokens=50, text_seq_len=8, dim=32, depth=2, heads=4,
            dim_head=8, image_vocab_size=44, image_fmap_size=4, image_size=32)
# tied with an rmsnorm block is no configuration: DalleConfig refuses it
SEGMENT_HEADS = {
    "untied-layernorm": {},
    "tied-layernorm": dict(share_input_output_emb=True),
    "untied-rmsnorm": dict(block=BlockConfig(feed_forward="swiglu", **_MLA)),
    "untied-layernorm-stable": dict(stable=True),
}
# 0: the two halves; 4 divides text_seq_len; 6 divides 24 and [6, 12) straddles 8
SEGMENT_CASES = [(head, chunk) for head in ("untied-layernorm",
                                            "tied-layernorm", "untied-rmsnorm")
                 for chunk in (0, 4, 6)] + [("untied-layernorm-stable", 6)]


@pytest.mark.parametrize("head, chunk", SEGMENT_CASES)
def test_segmented_loss_is_the_masked_full_width_loss(head, chunk):
    """float32: the loss, its two halves and every leaf's gradient are those
    of the masked full-width head up to the order of summation."""
    cfg = DalleConfig(**_SEG, loss_chunk=chunk, **SEGMENT_HEADS[head])
    model, params = init_dalle(cfg, jax.random.PRNGKey(1), batch=2)
    rng = np.random.default_rng(11)
    text = jnp.asarray(rng.integers(0, 50, (3, 8)), jnp.int32)   # 0s: pads
    ids = jnp.asarray(rng.integers(0, 44, (3, 16)), jnp.int32)
    # a head that starts at zero bias would hide a bias cut at the wrong column
    params = jax.tree.map(
        lambda v: v + 0.1 * jax.random.normal(jax.random.PRNGKey(2), v.shape),
        params)

    def both(loss_fn):
        return jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(params)

    (loss, aux), grads = both(lambda p: model.apply(p, text, ids,
                                                    return_loss=True))
    (ref_loss, ref_aux), ref_grads = both(
        lambda p: nn.apply(_masked_full_width_loss, model)(p, text, ids))
    assert float(loss) == pytest.approx(float(ref_loss), abs=1e-6)
    for half in ("loss_text", "loss_img"):
        assert float(aux[half]) == pytest.approx(float(ref_aux[half]),
                                                 abs=1e-6)
    ours, theirs = _leaves(grads), _leaves(ref_grads)
    assert ours.keys() == theirs.keys()
    for name, g in ours.items():
        assert np.abs(np.asarray(theirs[name])).max() > 0, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(theirs[name]),
                                   rtol=0, atol=1e-6, err_msg=name)


def _avals(jaxpr):
    """Every value an equation of ``jaxpr`` or of a jaxpr nested in it makes."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _avals(sub)


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("chunk", [0, 6])
def test_loss_step_builds_no_full_width_logits(chunk, tied):
    """In the loss and its gradient nothing with a batch and a position axis
    is ``total_tokens`` wide: the head's leaves, their casts and their
    gradients are, and the widest logits are one vocabulary's."""
    cfg = DalleConfig(**_SEG, loss_chunk=chunk, share_input_output_emb=tied)
    model, params = init_dalle(cfg, jax.random.PRNGKey(0), batch=2)
    text = jnp.ones((3, 8), jnp.int32)
    ids = jnp.zeros((3, 16), jnp.int32)
    ours, full_width = [
        {a.shape for a in _avals(jax.make_jaxpr(jax.value_and_grad(f))(
            params).jaxpr) if getattr(a, "ndim", 0) >= 3}
        for f in (lambda p: model.apply(p, text, ids, return_loss=True)[0],
                  lambda p: nn.apply(_masked_full_width_loss, model)(
                      p, text, ids)[0])]
    assert (3, 24, cfg.total_tokens) in full_width           # the control
    assert not {s for s in ours if s[-1] == cfg.total_tokens}
    # what is built: each vocabulary's logits, over its own positions only
    text_rows = {s[1] for s in ours if s[0] == 3 and s[-1] == 58}
    code_rows = {s[1] for s in ours if s[0] == 3 and s[-1] == 44}
    assert text_rows and max(text_rows) <= 8
    assert code_rows and max(code_rows) <= 16


@pytest.mark.parametrize("config, batch, segments, computed, full", [
    ("dalle_small", 64, 2, 4_722_688, 9_445_376),
    ("deepseek_v2_share16", 8, 10, 9_568_256, 16_384_000),
    ("rudalle_malevich", 4, 9, 10_502_144, 28_459_008),
])
def test_loss_head_for_the_benchmarks_real_shapes(config, batch, segments,
                                                  computed, full):
    with open(os.path.join(BENCH_CONFIGS, f"{config}.json")) as f:
        cfg = DalleConfig(**json.load(f)["model"])
    got = loss_head(cfg, batch)
    assert (len(got["segments"]), got["batch"], got["elements_computed"],
            got["elements_full"]) == (segments, batch, computed, full)
    text_cols = cfg.num_text_tokens + cfg.text_seq_len
    for s in got["segments"]:      # in order, none across text_seq_len
        text = s["rows"][1] <= cfg.text_seq_len
        assert text or s["rows"][0] >= cfg.text_seq_len
        assert s["cols"] == ([0, text_cols] if text
                             else [text_cols, cfg.total_tokens])
    assert [s["rows"][0] for s in got["segments"]][1:] == \
        [s["rows"][1] for s in got["segments"]][:-1]


def test_build_step_span_carries_the_flash_block_counts(tmp_path,
                                                        monkeypatch):
    """On the flash tier ``layers`` says, per softmax layer, the score blocks
    the kernels walk and how many of them no mask cuts (PR 35):
    here 8 + 16 positions are one block, cut by the diagonal."""
    monkeypatch.setattr("dalle_tpu.models.transformer.attention_tier",
                        lambda *a, **kw: "flash")
    tracer = obs.configure()
    try:
        tr = _tiny_trainer(tmp_path, MeshConfig(), compute="bfloat16",
                           devices=jax.devices()[:1])
        spans = [s for s in tracer.snapshot_spans()
                 if s[0] == "init/build_step"]
    finally:
        obs.disable()
    layers = spans[-1][5]["layers"]
    assert layers["tier"] == "flash"
    assert layers["flash"] == [
        {"layer": i, "visited": 1, "full": 0, "total": 1}
        for i in range(tr.model_cfg.depth)]


def test_build_step_span_carries_the_loss_head(tmp_path):
    """dalle_small's sequence and vocabularies (at a width of 16, one layer):
    half of the full-width head's logits, as two segments."""
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    cfg = DalleConfig(num_text_tokens=10000, text_seq_len=256, dim=16,
                      depth=1, heads=2, dim_head=8, image_size=128,
                      image_vocab_size=8192, image_fmap_size=16)
    tc = TrainConfig(batch_size=64, checkpoint_dir=str(tmp_path),
                     preflight_checkpoint=False, mesh=MeshConfig())
    tracer = obs.configure()
    try:
        DalleTrainer(cfg, tc, mesh=build_mesh(MeshConfig(),
                                              devices=jax.devices()[:1]))
        spans = [s for s in tracer.snapshot_spans()
                 if s[0] == "init/build_step"]
    finally:
        obs.disable()
    assert spans[-1][5]["head"] == {
        "segments": [{"rows": [0, 256], "cols": [0, 10256]},
                     {"rows": [256, 512], "cols": [10256, 18448]}],
        "batch": 64, "elements_computed": 4_722_688,
        "elements_full": 9_445_376}
