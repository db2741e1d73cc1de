"""DALL·E trainer — one jitted SPMD train step + host-side epoch loop.

Reference call stack: legacy/train_dalle.py (SURVEY.md §3.1) — epoch loop with
gradient clipping, grad accumulation via the DeepSpeed engine, loss averaging
over workers (`average_all`, :622), periodic checkpointing with rotation
(:547-550), preflight checkpoint (:591-594), periodic in-training sampling
(:639-649), throughput meter (:601-602,651-654), plus the fork's NaN rollback
(dalle.py:148-151).

TPU design mirrors trainer_vae: the entire step — CFG text dropout, loss,
grads, gradient psum over the dp/fsdp axes (inserted by the SPMD partitioner
from the shardings), clip, optimizer — is ONE jitted function with the state
donated, so params update in place in HBM. Grad accumulation is an optax
MultiSteps transform inside the same program rather than an engine feature.
"""

from __future__ import annotations

import functools
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..config import DalleConfig, TrainConfig
from ..models.dalle import DALLE, init_dalle, loss_head, table_grad_paths
from ..models.transformer import stack_layers
from ..obs import span
from .base_trainer import BaseTrainer
from .metrics import ThroughputMeter, count_params, transformer_train_flops
from .train_state import (TrainState, cast_floating, compute_dtype,
                          jit_step)


def _make_dalle_loss_fn(model: DALLE, *, null_cond_prob: float,
                        use_dropout: bool, dtype):
    def loss_fn(params, text, image_ids, key):
        rngs = {}
        if null_cond_prob > 0:
            rngs["cfg"] = jax.random.fold_in(key, 0)
        if use_dropout:
            rngs["dropout"] = jax.random.fold_in(key, 1)
        # the model scopes its own "loss" inside; what jax.value_and_grad
        # transposes from here shows as transpose(jvp(forward)) on the device
        with jax.named_scope("forward"):
            loss, aux = model.apply(cast_floating(params, dtype), text,
                                    image_ids, return_loss=True,
                                    null_cond_prob=null_cond_prob,
                                    deterministic=not use_dropout,
                                    rngs=rngs or None)
        return loss, aux

    return loss_fn


@functools.lru_cache(maxsize=64)
def _dalle_step_body(model: DALLE, *, null_cond_prob: float = 0.0,
                     use_dropout: bool = False, dtype=None,
                     health: bool = False, health_depth: int = 1):
    # memoized on (model-config, rng wiring, dtype, health wiring) so
    # equal-config trainers hand jit_step the SAME body object and share one
    # jitted wrapper. ``health`` fuses the graftpulse per-layer-group taps
    # (obs/health.py) into the program — scalars in the metrics dict, zero
    # added host syncs.
    loss_fn = _make_dalle_loss_fn(model, null_cond_prob=null_cond_prob,
                                  use_dropout=use_dropout, dtype=dtype)

    def step(state: TrainState, text, image_ids, key):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            state.params, text, image_ids, key)
        metrics = {"loss": loss, "grad_norm": optax.global_norm(grads), **aux}
        if health:
            from ..obs.health import tree_health
            new_state, updates = state.apply_gradients(grads, value=loss,
                                                       return_updates=True)
            metrics.update(tree_health(grads, new_state.params, updates,
                                       depth=health_depth))
        else:
            new_state = state.apply_gradients(grads, value=loss)
        return new_state, metrics

    return step


def make_dalle_train_step(model: DALLE, *, null_cond_prob: float = 0.0,
                          use_dropout: bool = False, dtype=None, state=None,
                          health: bool = False, health_depth: int = 1):
    """Returns step(state, text, image_ids, key) -> (state, metrics). jit-once
    (the (body, shardings)-memoized train_state.jit_step) with the state
    donated; ``null_cond_prob``/``use_dropout`` are compile-time (they select
    rng wiring). ``state`` pins the output state's shardings to the input's —
    see jit_step. ``dtype`` (e.g. bf16) is the compute precision: params are
    cast inside the step, master copies stay f32 — the TPU-native replacement
    for the DeepSpeed fp16 engine (SURVEY.md §2.9 Apex AMP row)."""
    return jit_step(_dalle_step_body(model, null_cond_prob=null_cond_prob,
                                     use_dropout=use_dropout, dtype=dtype,
                                     health=health,
                                     health_depth=health_depth),
                    state)


@functools.lru_cache(maxsize=64)
def make_dalle_train_multi_step(model: DALLE, *, null_cond_prob: float = 0.0,
                                use_dropout: bool = False, dtype=None,
                                health: bool = False, health_depth: int = 1):
    """k optimizer steps in ONE device program: ``lax.scan`` over the step
    body consuming a (k, b, ...) microbatch stack. One host dispatch covers k
    steps, and the k-1 interior state handoffs never touch the host — the
    TPU analogue of a captured CUDA graph replay. Math per step is BIT-identical to
    ``make_dalle_train_step``: the caller precomputes the exact single-step
    key stream (fold_in(base_key, host_step + i)) and it is scanned as an
    input, so toggling scan_steps never changes the rng trajectory even with
    null_cond_prob > 0 or dropout (same pattern as trainer_vae.train_steps)."""
    loss_fn = _make_dalle_loss_fn(model, null_cond_prob=null_cond_prob,
                                  use_dropout=use_dropout, dtype=dtype)

    @partial(jax.jit, donate_argnums=(0,))
    def steps(state: TrainState, texts, image_ids, keys):
        def body(state, xs):
            text, ids, key = xs
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                state.params, text, ids, key)
            metrics = {"loss": loss,
                       "grad_norm": optax.global_norm(grads), **aux}
            if health:
                from ..obs.health import tree_health
                new_state, updates = state.apply_gradients(
                    grads, value=loss, return_updates=True)
                metrics.update(tree_health(grads, new_state.params, updates,
                                           depth=health_depth))
            else:
                new_state = state.apply_gradients(grads, value=loss)
            return new_state, metrics

        state, ms = jax.lax.scan(body, state, (texts, image_ids, keys))
        metrics = jax.tree.map(lambda x: x[-1], ms)   # last step's metrics
        metrics["loss_mean"] = jnp.mean(ms["loss"])
        return state, metrics

    return steps


class DalleTrainer(BaseTrainer):
    """Consumes batches of (text ids, image codebook ids); raw pixels are
    tokenized by the caller through a VAEAdapter (the reference tokenizes
    inside DALLE.forward, :590-597 — here the vae is upstream of the hot loop
    so the train step stays a pure text+ids program)."""

    model_class = "DALLE"

    @span("trainer/init")
    def __init__(self, model_cfg: DalleConfig, train_cfg: TrainConfig,
                 mesh=None, backend=None, null_cond_prob: float = 0.0):
        super().__init__(train_cfg, mesh=mesh, backend=backend)
        self.model_cfg = model_cfg

        sp = dict(self.mesh.shape).get("sp", 1)
        if sp > 1:
            sp_ok = {"full", "axial_row", "axial_col", "conv_like"}
            bad = set(model_cfg.attn_types or ("full",)) - sp_ok
            assert not bad, (
                f"sequence parallelism (sp > 1) supports attn_types {sp_ok}; "
                f"got unsupported {bad} (tabled 'sparse' masks need host-side "
                "block lists the ring cannot shard)")
        if model_cfg.block.feed_forward == "moe" and self.mesh.size > 1:
            raise NotImplementedError(
                f"the {model_cfg.block.name} block trains on a one-device "
                f"mesh: parallel/partition.py has no expert axis and the "
                f"grouped product no exchange across devices yet (mesh "
                f"{dict(self.mesh.shape)})")
        with span("init/model"):
            self.model, params = init_dalle(
                model_cfg, self.base_key,
                sp_mesh=self.mesh if sp > 1 else None)
        self.state = self._create_state(params, self.model.apply)
        use_dropout = (model_cfg.attn_dropout > 0 or model_cfg.ff_dropout > 0)
        dtype = compute_dtype(train_cfg.precision)
        # the tables' backward and the loss's head follow from shapes, once
        # per compile
        with span("init/build_step",
                  head=loss_head(model_cfg, train_cfg.batch_size),
                  layers=stack_layers(model_cfg.transformer()),
                  **table_grad_paths(
                      model_cfg, jnp.float32 if dtype is None else dtype,
                      train_cfg.batch_size)):
            self.step_fn = make_dalle_train_step(
                self.model, null_cond_prob=null_cond_prob,
                use_dropout=use_dropout, dtype=dtype, state=self.state,
                health=bool(train_cfg.obs.health),
                health_depth=train_cfg.obs.health_group_depth)
        self._multi_step_kw = dict(null_cond_prob=null_cond_prob,
                                   use_dropout=use_dropout, dtype=dtype,
                                   health=bool(train_cfg.obs.health),
                                   health_depth=train_cfg.obs.health_group_depth)
        self._multi_step_fn = None   # built lazily on first train_steps()

        n = count_params(self.state.params)
        self.num_params = n
        tokens_per_sample = model_cfg.total_seq_len
        self.meter = ThroughputMeter(
            train_cfg.batch_size, train_cfg.log_every,
            tokens_per_sample=tokens_per_sample,
            flops_per_step=transformer_train_flops(
                n, train_cfg.batch_size * tokens_per_sample),
            num_chips=self.mesh.size)

    def _put_batch(self, batch, stacked: bool = False):
        """(text, image_ids) → int32 on the mesh (the device-prefetch hook;
        already-placed jax Arrays pass through untouched)."""
        text, image_ids = batch
        return (self._put(text, np.int32, stacked),
                self._put(image_ids, np.int32, stacked))

    def _record(self, step, device_metrics, stamp, part):
        """A routed step that dropped rows did not compute the model: every
        fetched record passes through here once, and the first that carries
        ``moe_rows_dropped`` > 0 stops the run."""
        metrics = super()._record(step, device_metrics, stamp, part)
        if metrics.get("moe_rows_dropped", 0) > 0:
            raise RuntimeError(
                f"step {step} dropped {metrics['moe_rows_dropped']:.0f} "
                f"routed rows (moe_rows_dropped): the held experts drew "
                f"more than models/latent_moe.ROW_BUFFER times their "
                f"uniform share; routing has collapsed onto this chip's "
                f"experts")
        return metrics

    # -- single step ---------------------------------------------------------
    def train_step(self, text: np.ndarray, image_ids: np.ndarray):
        key = jax.random.fold_in(self.base_key, self._host_step)
        with span("dalle/shard_batch"):
            text, image_ids = self._put_batch((text, image_ids))
        with span("dalle/step"):
            self.state, metrics = self._run_step(
                self.step_fn, self.state, text, image_ids, key)
        return self._finish_step(metrics)

    # -- k steps in one device program ---------------------------------------
    def train_steps(self, texts: np.ndarray, image_ids: np.ndarray):
        """Run ``k = texts.shape[0]`` optimizer steps from stacked (k, b, ...)
        microbatches in a single dispatched scan (see
        make_dalle_train_multi_step). Returns the last step's metrics dict
        plus ``loss_mean`` over the k steps; the host step advances by k."""
        assert texts.ndim == 3 and image_ids.ndim == 3, (
            "train_steps wants stacked (k, b, seq) microbatches")
        if self._multi_step_fn is None:
            self._multi_step_fn = make_dalle_train_multi_step(
                self.model, **self._multi_step_kw)
        k = texts.shape[0]
        keys = self._step_keys(k)
        with span("dalle/shard_batch", k=k):
            texts, image_ids = self._put_batch((texts, image_ids),
                                               stacked=True)
        with span("dalle/steps", k=k):
            self.state, metrics = self._run_step(
                self._multi_step_fn, self.state, texts, image_ids, keys)
        self._host_step += k - 1     # _finish_step adds the final +1
        return self._finish_step(metrics)
