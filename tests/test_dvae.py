"""dVAE model + trainer tests: shapes, losses, quantizer path, training descent."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import AnnealConfig, DVAEConfig, MeshConfig, OptimConfig, TrainConfig
from dalle_tpu.data.synthetic import ShapesDataset, batch_iterator
from dalle_tpu.models.dvae import DiscreteVAE, init_dvae
from dalle_tpu.train.trainer_vae import VAETrainer, anneal_temperature

SMALL = DVAEConfig(image_size=32, num_tokens=64, codebook_dim=32, num_layers=2,
                   num_resnet_blocks=1, hidden_dim=16)


@pytest.fixture(scope="module")
def dvae():
    return init_dvae(SMALL, jax.random.PRNGKey(0), batch=2)


class TestModel:
    def test_forward_shapes(self, dvae):
        model, params = dvae
        img = jnp.ones((2, 32, 32, 3)) * 0.5
        out = model.apply(params, img, rngs={"gumbel": jax.random.PRNGKey(1)})
        assert out.shape == (2, 32, 32, 3)

    def test_codebook_indices_shape_and_range(self, dvae):
        model, params = dvae
        img = jnp.linspace(0, 1, 2 * 32 * 32 * 3).reshape(2, 32, 32, 3)
        idx = model.apply(params, img, method=DiscreteVAE.get_codebook_indices)
        assert idx.shape == (2, SMALL.fmap_size ** 2)   # (32/4)^2 = 64
        assert idx.dtype == jnp.int32
        assert (idx >= 0).all() and (idx < SMALL.num_tokens).all()

    def test_decode_roundtrip_shape(self, dvae):
        model, params = dvae
        seq = jnp.zeros((2, SMALL.fmap_size ** 2), jnp.int32)
        img = model.apply(params, seq, method=DiscreteVAE.decode)
        assert img.shape == (2, 32, 32, 3)

    def test_loss_scalar_and_finite(self, dvae):
        model, params = dvae
        img = jnp.ones((2, 32, 32, 3)) * 0.3
        loss = model.apply(params, img, return_loss=True,
                           rngs={"gumbel": jax.random.PRNGKey(2)})
        assert loss.shape == () and jnp.isfinite(loss)

    def test_kl_weight_increases_loss(self):
        cfg = SMALL.replace(kl_div_loss_weight=0.0)
        cfg_kl = SMALL.replace(kl_div_loss_weight=1.0)
        key = jax.random.PRNGKey(0)
        model0, params = init_dvae(cfg, key)
        model1 = DiscreteVAE(cfg_kl)
        img = jax.random.uniform(key, (2, 32, 32, 3))
        l0 = model0.apply(params, img, return_loss=True, rngs={"gumbel": key})
        l1 = model1.apply(params, img, return_loss=True, rngs={"gumbel": key})
        assert float(l1) > float(l0)

    def test_hard_recons_deterministic(self, dvae):
        model, params = dvae
        img = jax.random.uniform(jax.random.PRNGKey(3), (1, 32, 32, 3))
        a = model.apply(params, img, hard_recons=True)
        b = model.apply(params, img, hard_recons=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_gradients_reach_codebook_and_encoder(self, dvae):
        model, params = dvae
        img = jax.random.uniform(jax.random.PRNGKey(4), (2, 32, 32, 3))
        g = jax.grad(lambda p: model.apply(p, img, return_loss=True,
                                           rngs={"gumbel": jax.random.PRNGKey(5)}))(params)
        leaves = {"/".join(str(getattr(k, "key", k)) for k in kp): v
                  for kp, v in jax.tree_util.tree_flatten_with_path(g)[0]}
        cb = [v for p, v in leaves.items() if "codebook" in p][0]
        enc = [v for p, v in leaves.items() if "encoder" in p][0]
        assert float(jnp.abs(cb).sum()) > 0
        assert float(jnp.abs(enc).sum()) > 0


class TestSynthetic:
    def test_dataset_deterministic(self):
        ds = ShapesDataset(image_size=32, variants=2, seed=1)
        a, b = ds[5], ds[5]
        np.testing.assert_array_equal(a.image, b.image)
        assert a.caption == b.caption

    def test_all_shapes_render_nonempty(self):
        from dalle_tpu.data.synthetic import render, SHAPES
        for s in SHAPES:
            img = render(s, "red", "medium", 32)
            assert (img > 0).any(), f"{s} rendered empty"
            assert img.shape == (32, 32, 3)

    def test_batch_iterator(self):
        ds = ShapesDataset(image_size=32)
        it = batch_iterator(ds, 8, epochs=1)
        imgs, caps = next(it)
        assert imgs.shape == (8, 32, 32, 3)
        assert imgs.dtype == np.float32 and imgs.max() <= 1.0
        assert len(caps) == 8


class TestTrainer:
    def test_anneal_schedule(self):
        cfg = AnnealConfig(starting_temp=1.0, temp_min=0.5, anneal_rate=1e-3)
        assert anneal_temperature(cfg, 0) == 1.0
        assert anneal_temperature(cfg, 10**7) == 0.5
        assert 0.5 < anneal_temperature(cfg, 100) < 1.0

    def test_loss_decreases_on_shapes(self, tmp_path):
        tc = TrainConfig(batch_size=8, seed=0, log_every=5, save_every_steps=10**6,
                         checkpoint_dir=str(tmp_path / "ckpt"),
                         preflight_checkpoint=False,
                         optim=OptimConfig(learning_rate=3e-3, grad_clip_norm=0.0),
                         mesh=MeshConfig(dp=1, fsdp=1, tp=1, sp=1))
        trainer = VAETrainer(SMALL, tc)
        ds = ShapesDataset(image_size=32)
        losses = []
        for imgs, caps in batch_iterator(ds, 8, epochs=None):
            m = trainer.train_step(imgs)
            losses.append(m["loss"])
            if len(losses) >= 30:
                break
        first, last = np.mean(losses[:5]), np.mean(losses[-5:])
        assert last < first * 0.8, f"no descent: {first} -> {last}"

    def test_nan_rollback_and_checkpoint(self, tmp_path):
        tc = TrainConfig(batch_size=8, checkpoint_dir=str(tmp_path / "ck"),
                         save_every_steps=2, log_every=1000,
                         optim=OptimConfig(learning_rate=1e-3),
                         mesh=MeshConfig())
        trainer = VAETrainer(SMALL, tc)
        ds = ShapesDataset(image_size=32)

        def gen():
            it = batch_iterator(ds, 8)
            for i in range(10):
                imgs, caps = next(it)
                if i == 3:
                    imgs = imgs * np.nan  # poison one batch
                yield imgs, caps

        trainer.fit(gen(), log=lambda *a: None)
        # params AND optimizer moments survived the poisoned batch (a NaN loss
        # means apply_gradients already wrote NaN into Adam's mu/nu)
        assert all(np.isfinite(x).all() for x in jax.tree.leaves(
            jax.device_get((trainer.state.params, trainer.state.opt_state))))
        # training keeps producing finite losses after the rollback
        m = trainer.train_step(next(batch_iterator(ds, 8))[0])
        assert np.isfinite(m["loss"])
        # checkpoints were written and can be restored
        step = trainer.ckpt.latest_step()
        assert step is not None and step >= 2
        restored, meta = trainer.ckpt.restore(jax.device_get(trainer.state))
        assert meta["model_class"] == "DiscreteVAE"
        assert meta["hparams"]["num_tokens"] == SMALL.num_tokens

    def test_fit_stamps_each_record_with_its_own_temperature(self, tmp_path):
        """fit() hands a step's record back one step late: the temperature
        on it is that step's, not the one of the step just dispatched."""
        tc = TrainConfig(batch_size=8, checkpoint_dir=str(tmp_path / "ck3"),
                         preflight_checkpoint=False, save_every_steps=0,
                         log_every=1000, mesh=MeshConfig())
        trainer = VAETrainer(SMALL, tc, AnnealConfig(
            starting_temp=1.0, temp_min=0.1, anneal_rate=0.2))
        records = []

        class Writer:
            def log(self, step, metrics):
                records.append((step, metrics["temperature"]))

        imgs, _ = ShapesDataset(image_size=32).as_arrays(limit=8)
        trainer.fit(iter([(imgs,)] * 4), log=lambda *a: None,
                    metrics_writer=Writer())
        want = [(s + 1, trainer._temp_at(s)) for s in range(4)]
        assert records == want and len({t for _, t in want}) == 4
        assert trainer.train_step(imgs)["temperature"] == trainer._temp_at(4)

    def test_codebook_histogram(self, tmp_path):
        tc = TrainConfig(batch_size=8, checkpoint_dir=str(tmp_path / "ck2"),
                         preflight_checkpoint=False, mesh=MeshConfig())
        trainer = VAETrainer(SMALL, tc)
        imgs, _ = ShapesDataset(image_size=32).as_arrays(limit=8)
        hist = trainer.codebook_histogram(imgs)
        assert hist.shape == (SMALL.num_tokens,)
        assert hist.sum() == 8 * SMALL.fmap_size ** 2
