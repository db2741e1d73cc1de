"""CLIP trainer — contrastive text/image training as one jitted SPMD step.

The reference ships the CLIP model with its symmetric-CE loss
(dalle_pytorch/dalle_pytorch.py:292-332) but no training script (CLIP is used
for reranking, generate_images :553-555). This trainer completes the family so
a rerank model can be trained in-framework, with the same shell as every other
trainer (NaN rollback, checkpoints, meter, bf16 compute).
"""

from __future__ import annotations

import functools

import jax
import numpy as np
import optax

from ..config import ClipConfig, TrainConfig
from ..models.clip import CLIP, init_clip
from ..obs import span
from .base_trainer import BaseTrainer
from .metrics import ThroughputMeter, count_params, transformer_train_flops
from .train_state import (TrainState, cast_floating, compute_dtype,
                          jit_step)


@functools.lru_cache(maxsize=64)
def _clip_step_body(model: CLIP, dtype=None, health: bool = False,
                    health_depth: int = 1):
    # memoized on (model-config, dtype, health wiring) so equal-config
    # trainers hand jit_step the SAME body object and share one jitted
    # wrapper. ``health`` fuses the graftpulse per-layer-group taps
    # (obs/health.py) into the program.
    def loss_fn(params, text, images):
        x = images if dtype is None else images.astype(dtype)
        with jax.named_scope("forward"):
            return model.apply(cast_floating(params, dtype), text, x,
                               return_loss=True)

    def step(state: TrainState, text, images):
        loss, grads = jax.value_and_grad(loss_fn)(state.params, text, images)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads)}
        if health:
            from ..obs.health import tree_health
            state, updates = state.apply_gradients(grads, value=loss,
                                                   return_updates=True)
            metrics.update(tree_health(grads, state.params, updates,
                                       depth=health_depth))
        else:
            state = state.apply_gradients(grads, value=loss)
        return state, metrics

    return step


def make_clip_train_step(model: CLIP, dtype=None, state=None,
                         health: bool = False, health_depth: int = 1):
    """Returns step(state, text, images) -> (state, metrics). ``state`` pins
    the output state's shardings (train_state.jit_step)."""
    return jit_step(_clip_step_body(model, dtype, health, health_depth),
                    state)


def make_clip_train_multi_step(model: CLIP, dtype=None, health: bool = False,
                               health_depth: int = 1):
    """k steps per dispatch over stacked (texts, imagess) —
    train_state.make_scanned_steps over the identical step body."""
    from .train_state import make_scanned_steps
    return make_scanned_steps(_clip_step_body(model, dtype, health,
                                              health_depth))


class CLIPTrainer(BaseTrainer):
    model_class = "CLIP"

    @span("trainer/init")
    def __init__(self, model_cfg: ClipConfig, train_cfg: TrainConfig,
                 mesh=None, backend=None):
        super().__init__(train_cfg, mesh=mesh, backend=backend)
        self.model_cfg = model_cfg
        with span("init/model"):
            self.model, params = init_clip(model_cfg, self.base_key)
        self.state = self._create_state(params, self.model.apply)
        self._health_kw = dict(
            health=bool(train_cfg.obs.health),
            health_depth=train_cfg.obs.health_group_depth)
        with span("init/build_step"):
            self.step_fn = make_clip_train_step(
                self.model, dtype=compute_dtype(train_cfg.precision),
                state=self.state, **self._health_kw)
        self._multi_step_fn = None   # built lazily on first train_steps()
        n = count_params(self.state.params)
        self.num_params = n
        tokens_per_sample = (model_cfg.text_seq_len +
                             (model_cfg.visual_image_size //
                              model_cfg.visual_patch_size) ** 2)
        self.meter = ThroughputMeter(
            train_cfg.batch_size, train_cfg.log_every,
            tokens_per_sample=tokens_per_sample,
            flops_per_step=transformer_train_flops(
                n, train_cfg.batch_size * tokens_per_sample),
            num_chips=self.mesh.size)

    def _put_batch(self, batch, stacked: bool = False):
        """(text, images) → int32 text + float32 images on the mesh."""
        text, images = batch
        return (self._put(text, np.int32, stacked),
                self._put(images, np.float32, stacked))

    def train_step(self, text: np.ndarray, images: np.ndarray):
        with span("clip/shard_batch"):
            text, images = self._put_batch((text, images))
        with span("clip/step"):
            self.state, metrics = self._run_step(self.step_fn, self.state,
                                                 text, images)
        return self._finish_step(metrics)

    def train_steps(self, texts: np.ndarray, imagess: np.ndarray):
        """(k, b, ...) stacked microbatches → k steps in one dispatched scan
        (identical math to k single dispatches — the step is rng-free)."""
        assert texts.ndim == 3 and imagess.ndim == 5, (
            "train_steps wants stacked (k, b, seq) / (k, b, H, W, C)")
        if self._multi_step_fn is None:
            self._multi_step_fn = make_clip_train_multi_step(
                self.model, dtype=compute_dtype(self.train_cfg.precision),
                **self._health_kw)
        k = texts.shape[0]
        with span("clip/shard_batch", k=k):
            texts, imagess = self._put_batch((texts, imagess), stacked=True)
        with span("clip/steps", k=k):
            self.state, metrics = self._run_step(
                self._multi_step_fn, self.state, (texts, imagess))
        self._host_step += k - 1     # _finish_step adds the final +1
        return self._finish_step(metrics)

    def similarity(self, text: np.ndarray, images: np.ndarray):
        """Per-pair rerank scores (reference generate_images :553-555)."""
        import jax.numpy as jnp
        return self.model.apply(self.state.params, jnp.asarray(text),
                                jnp.asarray(images))
