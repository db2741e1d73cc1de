"""VQGAN trainer — adversarial autoencoder training as two jitted SPMD steps.

Reference: the Lightning ``VQModel.training_step`` two-optimizer schedule
(taming/models/vqgan.py:83-131: AE vs discriminator by ``optimizer_idx``, Adam
β=(0.5, 0.9)), ``VQLPIPSWithDiscriminator`` (taming/modules/losses/
vqperceptual.py:34-136), and the GumbelVQ per-step temperature scheduler
(vqgan.py:279-303).

TPU design:
  * No optimizer_idx branching: each train step is ONE jitted function that
    runs the AE update then the discriminator update, so XLA fuses both
    backwards with the psum-by-sharding collectives.
  * The discriminator step reuses the generator's pre-update reconstruction
    (detached) instead of re-running encoder+decoder after the AE update —
    that second generator forward is pure HBM/MXU waste; Lightning only
    recomputes it because its loop can't share activations across
    optimizer_idx calls.
  * The adaptive disc weight is exact (grad w.r.t. the decoder's conv_out
    kernel, gan.py) — the extra backward stops at the stop-gradiented
    pre-output activation.
  * LPIPS params are frozen constants (taming keeps LPIPS in eval with no
    grads): they live in the state for checkpointing but no optimizer touches
    them.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import TrainConfig, VQGANConfig
from ..models.gan import (GANLossConfig, NLayerDiscriminator, adaptive_disc_weight,
                          adopt_weight, bce_with_quant_loss, hinge_d_loss,
                          vanilla_d_loss)
from ..models.lpips import LPIPS, init_lpips
from ..models.vqgan import VQModel, init_vqgan
from ..obs import span
from ..parallel import commit_to_mesh, shard_params
from .base_trainer import BaseTrainer
from .metrics import ThroughputMeter, count_params
from .train_state import (TrainState, cast_floating, compute_dtype,
                          jit_step, make_optimizer)


class LambdaWarmUpCosineScheduler:
    """Linear warmup then cosine decay multiplier
    (taming/lr_scheduler.py:4-33) — used by GumbelVQ's temperature schedule."""

    def __init__(self, warm_up_steps: int, lr_min: float, lr_max: float,
                 lr_start: float, max_decay_steps: int):
        self.warm_up_steps = warm_up_steps
        self.lr_min = lr_min
        self.lr_max = lr_max
        self.lr_start = lr_start
        self.max_decay_steps = max_decay_steps

    def __call__(self, n: int) -> float:
        if n < self.warm_up_steps:
            return ((self.lr_max - self.lr_start) / self.warm_up_steps * n
                    + self.lr_start)
        t = (n - self.warm_up_steps) / max(
            self.max_decay_steps - self.warm_up_steps, 1)
        t = min(t, 1.0)
        return self.lr_min + 0.5 * (self.lr_max - self.lr_min) * (
            1 + math.cos(t * math.pi))


@flax.struct.dataclass
class GANTrainState:
    """Generator + discriminator + frozen LPIPS in one checkpointable pytree.
    ``params``/``opt_state`` keep the names BaseTrainer's NaN rollback expects."""
    step: jnp.ndarray
    params: Any          # {"gen", "disc", "lpips"}
    opt_state: Any       # {"gen", "disc"}
    batch_stats: Any     # discriminator BatchNorm running stats
    gen_tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    disc_tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)

    @classmethod
    def create(cls, *, gen_params, disc_params, lpips_params, batch_stats,
               gen_tx, disc_tx):
        return cls(step=jnp.zeros((), jnp.int32),
                   params={"gen": gen_params, "disc": disc_params,
                           "lpips": lpips_params},
                   opt_state={"gen": gen_tx.init(gen_params),
                              "disc": disc_tx.init(disc_params["params"])},
                   batch_stats=batch_stats, gen_tx=gen_tx, disc_tx=disc_tx)


def make_vqgan_train_step(model: VQModel, disc: NLayerDiscriminator,
                          lpips: Optional[LPIPS], loss_cfg: GANLossConfig,
                          dtype=None, scanned: bool = False, state=None,
                          health: bool = False, health_depth: int = 1):
    """Returns step(state, images, key, temp) -> (state, metrics) implementing
    both optimizer updates of vqperceptual.py:76-136 in one XLA program.
    ``state`` pins the output state's shardings to the input's
    (train_state.jit_step). ``scanned``: lift the same body into a
    k-steps-per-dispatch program over stacked (imagess, keys, temps)
    (train_state.make_scanned_steps). ``health`` fuses the graftpulse taps
    (obs/health.py) into the program: codebook vitals from the quantizer's
    own VQOutput plus per-layer-group grad/param/update stats for BOTH
    optimizers (``gen/*`` and ``disc/*`` groups) — scalars in the metrics
    dict, zero added host syncs."""
    lc = loss_cfg
    d_loss_fn = hinge_d_loss if lc.disc_loss == "hinge" else vanilla_d_loss

    def perceptual(lpips_params, x, y):
        if lpips is None or lc.perceptual_weight == 0:
            return jnp.zeros((x.shape[0],), x.dtype)
        return lpips.apply(lpips_params, x, y)

    def ae_loss_fn(gen_params, disc_params, lpips_params, batch_stats, images,
                   key, temp, step):
        # training pass: dropout active, gumbel sampling live (when configured)
        rngs = {"gumbel": key, "dropout": jax.random.fold_in(key, 1)}
        gen_c = cast_floating(gen_params, dtype)
        images_c = images if dtype is None else images.astype(dtype)
        with jax.named_scope("forward"):
            q = model.apply(gen_c, images_c, temp=temp, deterministic=False,
                            method=VQModel.encode, rngs=rngs)
            recon, h_last = model.apply(gen_c, q.quantized, False, True,
                                        method=VQModel.decode, rngs=rngs)

        def nll_of(r):
            # loss reductions in f32 regardless of the compute dtype
            rec = lc.pixelloss_weight * jnp.abs(
                images.astype(jnp.float32) - r.astype(jnp.float32))
            p = perceptual(lpips_params, images, r)
            return jnp.mean(rec) + lc.perceptual_weight * jnp.mean(
                p.astype(jnp.float32))

        def g_of(r):
            logits_fake, _ = disc.apply(
                {"params": disc_params, "batch_stats": batch_stats}, r,
                train=True, mutable=["batch_stats"])
            return -jnp.mean(logits_fake)

        with jax.named_scope("loss"):
            nll = nll_of(recon)
            g_loss = g_of(recon)
            conv_out = gen_c["params"]["decoder"]["conv_out"]
            d_weight = adaptive_disc_weight(nll_of, g_of, h_last, conv_out,
                                            lc.disc_weight)
            disc_factor = adopt_weight(lc.disc_factor, step, lc.disc_start)
            loss = (nll + d_weight * disc_factor * g_loss
                    + lc.codebook_weight * q.loss)
        aux = {"recon": recon, "nll_loss": nll, "g_loss": g_loss,
               "quant_loss": q.loss, "d_weight": d_weight,
               "disc_factor": disc_factor}
        if health:
            # codebook vitals from the encode's own VQOutput — no recompute
            aux["health"] = model.health_taps(q, temp)
        return loss, aux

    def disc_loss_fn(disc_params, batch_stats, images, recon, step):
        variables = {"params": disc_params, "batch_stats": batch_stats}
        with jax.named_scope("forward"):
            logits_real, vars1 = disc.apply(variables, images, train=True,
                                            mutable=["batch_stats"])
            logits_fake, vars2 = disc.apply(
                {"params": disc_params, "batch_stats": vars1["batch_stats"]},
                jax.lax.stop_gradient(recon), train=True,
                mutable=["batch_stats"])
        with jax.named_scope("loss"):
            disc_factor = adopt_weight(lc.disc_factor, step, lc.disc_start)
            d_loss = disc_factor * d_loss_fn(logits_real, logits_fake)
        aux = {"batch_stats": vars2["batch_stats"],
               "logits_real": jnp.mean(logits_real),
               "logits_fake": jnp.mean(logits_fake)}
        return d_loss, aux

    def step(state: GANTrainState, images, key, temp):
        gen_p, disc_p, lpips_p = (state.params["gen"], state.params["disc"],
                                  state.params["lpips"])
        # --- optimizer_idx 0: autoencoder ---------------------------------
        (ae_loss, aux), gen_grads = jax.value_and_grad(ae_loss_fn, has_aux=True)(
            gen_p, disc_p["params"], lpips_p, state.batch_stats, images, key,
            temp, state.step)
        gen_updates, gen_opt = state.gen_tx.update(
            gen_grads, state.opt_state["gen"], gen_p, value=ae_loss)
        with jax.named_scope("optimizer"):
            gen_p = optax.apply_updates(gen_p, gen_updates)
        # --- optimizer_idx 1: discriminator -------------------------------
        (d_loss, d_aux), disc_grads = jax.value_and_grad(
            disc_loss_fn, has_aux=True)(disc_p["params"], state.batch_stats,
                                        images, aux["recon"], state.step)
        disc_updates, disc_opt = state.disc_tx.update(
            disc_grads, state.opt_state["disc"], disc_p["params"], value=d_loss)
        with jax.named_scope("optimizer"):
            disc_p = {"params": optax.apply_updates(disc_p["params"],
                                                    disc_updates)}
        state = state.replace(
            step=state.step + 1,
            params={"gen": gen_p, "disc": disc_p, "lpips": lpips_p},
            opt_state={"gen": gen_opt, "disc": disc_opt},
            batch_stats=d_aux["batch_stats"])
        metrics = {"loss": ae_loss, "disc_loss": d_loss,
                   "nll_loss": aux["nll_loss"], "quant_loss": aux["quant_loss"],
                   "g_loss": aux["g_loss"], "d_weight": aux["d_weight"],
                   "logits_real": d_aux["logits_real"],
                   "logits_fake": d_aux["logits_fake"]}
        if health:
            from ..obs.health import tree_health
            metrics.update(aux["health"])
            # POST-update params (fresh buffers — donation aliasing intact)
            metrics.update(tree_health(gen_grads, gen_p, gen_updates,
                                       depth=health_depth, prefix="gen"))
            metrics.update(tree_health(disc_grads, disc_p["params"],
                                       disc_updates, depth=health_depth,
                                       prefix="disc"))
        return state, metrics

    if scanned:
        from .train_state import make_scanned_steps
        return make_scanned_steps(step)
    return jit_step(step, state)


def make_vq_simple_train_step(model: VQModel, loss_cfg: GANLossConfig,
                              mode: str, dtype=None, scanned: bool = False,
                              state=None, health: bool = False,
                              health_depth: int = 1):
    """Single-optimizer VQ variants (taming vqgan.py:159-258):
    ``nodisc`` — L1 recon + codebook loss (VQNoDiscModel);
    ``segmentation`` — BCE over label-map logits + codebook loss
    (VQSegmentationModel with BCELossWithQuant). ``health`` fuses the
    graftpulse codebook + per-layer-group taps (obs/health.py)."""
    lc = loss_cfg

    def loss_fn(params, images, targets, key, temp):
        rngs = {"gumbel": key, "dropout": jax.random.fold_in(key, 1)}
        p = cast_floating(params, dtype)
        x = images if dtype is None else images.astype(dtype)
        with jax.named_scope("forward"):
            recon, qloss, indices = model.apply(p, x, temp=temp,
                                                deterministic=False, rngs=rngs)
        recon32 = recon.astype(jnp.float32)
        hm = {}
        if health:
            from ..obs.health import codebook_health
            hm = codebook_health(indices, model.cfg.n_embed)
        if mode == "segmentation":
            loss, parts = bce_with_quant_loss(recon32, targets, qloss,
                                              lc.codebook_weight)
            return loss, {"nll_loss": parts["bce_loss"], "quant_loss": qloss,
                          **hm}
        rec = jnp.mean(jnp.abs(targets - recon32)) * lc.pixelloss_weight
        return rec + lc.codebook_weight * qloss, {"nll_loss": rec,
                                                  "quant_loss": qloss, **hm}

    def step(state: TrainState, images, targets, key, temp):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, images, targets, key, temp)
        if health:
            from ..obs.health import tree_health
            state, updates = state.apply_gradients(grads, value=loss,
                                                   return_updates=True)
            aux = {**aux, **tree_health(grads, state.params, updates,
                                        depth=health_depth)}
        else:
            state = state.apply_gradients(grads, value=loss)
        return state, {"loss": loss, **aux}

    if scanned:
        from .train_state import make_scanned_steps
        return make_scanned_steps(step)
    return jit_step(step, state)


class VQGANTrainer(BaseTrainer):
    model_class = "VQModel"

    @span("trainer/init")
    def __init__(self, model_cfg: VQGANConfig, train_cfg: TrainConfig,
                 loss_cfg: Optional[GANLossConfig] = None, mesh=None,
                 backend=None, disc_optim=None,
                 temp_scheduler: Optional[Callable[[int], float]] = None,
                 loss_mode: str = "gan"):
        """``loss_mode``: "gan" (VQModel/GumbelVQ adversarial training),
        "nodisc" (VQNoDiscModel), or "segmentation" (VQSegmentationModel —
        set cfg.out_ch to the label count)."""
        super().__init__(train_cfg, mesh=mesh, backend=backend)
        self.model_cfg = model_cfg
        self.loss_cfg = loss_cfg or GANLossConfig()
        assert loss_mode in ("gan", "nodisc", "segmentation"), loss_mode
        self.loss_mode = loss_mode
        self._health_kw = dict(
            health=bool(train_cfg.obs.health),
            health_depth=train_cfg.obs.health_group_depth)

        with span("init/model"):
            self.model, gen_params = init_vqgan(model_cfg, self.base_key)
        if loss_mode != "gan":
            self.state = self._create_state(gen_params, self.model.apply)
            with span("init/build_step"):
                self.step_fn = make_vq_simple_train_step(
                    self.model, self.loss_cfg, loss_mode,
                    dtype=compute_dtype(train_cfg.precision),
                    state=self.state, **self._health_kw)
            self.disc = self.lpips = None
            self._finish_init(temp_scheduler)
            return
        with span("init/model"):   # the discriminator and LPIPS
            self.disc = NLayerDiscriminator(ndf=self.loss_cfg.disc_ndf,
                                            n_layers=self.loss_cfg.disc_num_layers,
                                            use_actnorm=self.loss_cfg.use_actnorm)
            disc_vars = self.disc.init(
                jax.random.fold_in(self.base_key, 1),
                jnp.zeros((2, model_cfg.resolution, model_cfg.resolution,
                           model_cfg.in_channels), jnp.float32), train=True)
            batch_stats = disc_vars.get("batch_stats", {})
            if self.loss_cfg.perceptual_weight > 0:
                if self.loss_cfg.perceptual_net == "tiny":
                    # the shipped in-repo perceptual weights (real metric, no
                    # egress needed — scripts/train_perceptual.py)
                    from ..models.lpips import load_tiny_perceptual
                    try:
                        self.lpips, lpips_params = load_tiny_perceptual()
                    except FileNotFoundError:
                        import warnings
                        warnings.warn("tiny_perceptual.npz missing — perceptual "
                                      "loss falls back to a random-init net")
                        self.lpips, lpips_params = init_lpips(
                            jax.random.fold_in(self.base_key, 2),
                            model_cfg.resolution)
                else:
                    # torchvision-shaped trunk; import real weights via
                    # models.lpips.load_torch_weights when vgg.pth is on disk
                    self.lpips, lpips_params = init_lpips(
                        jax.random.fold_in(self.base_key, 2), model_cfg.resolution)
            else:
                self.lpips, lpips_params = None, {}

        with span("init/commit_to_mesh"):
            gen_params = shard_params(self.mesh, gen_params)
            disc_params = shard_params(self.mesh,
                                       {"params": disc_vars["params"]})
            lpips_params = shard_params(self.mesh, lpips_params)

        # taming configure_optimizers: both Adam(lr, betas=(0.5, 0.9))
        # (taming/models/vqgan.py:121-131)
        with span("init/optimizer"):
            gen_tx = make_optimizer(train_cfg.optim)
            self.disc_optim = disc_optim or train_cfg.optim
            disc_tx = make_optimizer(self.disc_optim)
            state = GANTrainState.create(
                gen_params=gen_params, disc_params=disc_params,
                lpips_params=lpips_params, batch_stats=batch_stats,
                gen_tx=gen_tx, disc_tx=disc_tx)
        with span("init/commit_to_mesh"):
            self.state = commit_to_mesh(self.mesh, state)
        with span("init/build_step"):
            self.step_fn = make_vqgan_train_step(
                self.model, self.disc, self.lpips, self.loss_cfg,
                dtype=compute_dtype(train_cfg.precision), state=self.state,
                **self._health_kw)
        self._finish_init(temp_scheduler)

    def _finish_init(self, temp_scheduler):
        """Shared tail for both modes: temperature schedule + meter."""
        # GumbelVQ temperature schedule, stepped per train step
        # (taming vqgan.py:279-303)
        self.temp_scheduler = temp_scheduler
        if self.temp_scheduler is None and self.model_cfg.quantizer == "gumbel":
            self.temp_scheduler = LambdaWarmUpCosineScheduler(
                0, 1e-6, 1.0, 1.0, self.train_cfg.optim.total_steps)
        n = count_params(self._gen_params)
        self.meter = ThroughputMeter(
            self.train_cfg.batch_size, self.train_cfg.log_every,
            flops_per_step=6.0 * n * self.train_cfg.batch_size,
            num_chips=self.mesh.size)

    def _put_batch(self, batch, stacked: bool = False):
        """(images[, targets]) → float32 on the mesh (targets only exist for
        the segmentation/nodisc modes)."""
        images, *rest = batch
        return (self._put(images, np.float32, stacked),
                *(self._put(t, np.float32, stacked) if t is not None else t
                  for t in rest))

    def train_step(self, images: np.ndarray, targets=None):
        """``targets``: segmentation one-hots for loss_mode="segmentation";
        defaults to the images themselves for "nodisc"."""
        step_num = self._host_step
        temp = (self.temp_scheduler(step_num) if self.temp_scheduler is not None
                else 1.0)
        key = jax.random.fold_in(self.base_key, step_num)
        with span("vqgan/shard_batch"):
            images = self._put(images, np.float32)
        if self.loss_mode != "gan":
            t = images if targets is None else self._put(targets, np.float32)
            with span("vqgan/step"):
                self.state, metrics = self._run_step(
                    self.step_fn, self.state, images, t, key,
                    jnp.float32(temp))
            return self._finish_step(metrics)
        with span("vqgan/step"):
            self.state, metrics = self._run_step(
                self.step_fn, self.state, images, key, jnp.float32(temp))
        # the stamp travels with this step's record: under fit() the record
        # handed back is the previous boundary's
        return self._finish_step(
            metrics, {"temperature": temp} if self.temp_scheduler is not None
            else None)

    # -- k steps in one device program ---------------------------------------
    def train_steps(self, images: np.ndarray, targets=None):
        """(k, b, H, W, C) stacked microbatches → k steps (both optimizer
        updates each) in one dispatched scan. Key and temperature streams
        match ``train_step`` exactly."""
        assert images.ndim == 5, "train_steps wants stacked (k, b, H, W, C)"
        if getattr(self, "_multi_step_fn", None) is None:
            dt = compute_dtype(self.train_cfg.precision)
            if self.loss_mode == "gan":
                self._multi_step_fn = make_vqgan_train_step(
                    self.model, self.disc, self.lpips, self.loss_cfg,
                    dtype=dt, scanned=True, **self._health_kw)
            else:
                self._multi_step_fn = make_vq_simple_train_step(
                    self.model, self.loss_cfg, self.loss_mode, dtype=dt,
                    scanned=True, **self._health_kw)
        k = images.shape[0]
        steps = self._host_step + np.arange(k)
        temps = [self.temp_scheduler(int(s)) if self.temp_scheduler is not None
                 else 1.0 for s in steps]
        temps_dev = jnp.asarray(temps, jnp.float32)
        keys = self._step_keys(k)
        with span("vqgan/shard_batch", k=k):
            images = self._put(images, np.float32, stacked=True)
        if self.loss_mode != "gan":
            t = (images if targets is None
                 else self._put(targets, np.float32, stacked=True))
            xs = (images, t, keys, temps_dev)
        else:
            xs = (images, keys, temps_dev)
        with span("vqgan/steps", k=k):
            self.state, metrics = self._run_step(self._multi_step_fn,
                                                 self.state, xs)
        self._host_step += k - 1     # _finish_step adds the final +1
        return self._finish_step(
            metrics, {"temperature": float(temps[-1])}
            if self.temp_scheduler is not None else None)

    # -- eval utilities ----------------------------------------------------
    @property
    def _gen_params(self):
        return (self.state.params if self.loss_mode != "gan"
                else self.state.params["gen"])

    def reconstruct(self, images: np.ndarray):
        recon, _, _ = self.model.apply(self._gen_params, jnp.asarray(images),
                                       deterministic=True)
        return recon

    def get_codebook_indices(self, images: np.ndarray):
        return self.model.apply(self._gen_params, jnp.asarray(images),
                                method=VQModel.get_codebook_indices)
