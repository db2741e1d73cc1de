"""Train state + optimizer construction.

Replaces the reference's torch Adam + ReduceLROnPlateau / ExponentialLR wiring
(legacy/train_dalle.py:439-459, legacy/train_vae.py Exponential decay) with an
optax chain. Gradient clipping and accumulation — which the reference delegates
to the DeepSpeed engine (deepspeed_backend.py:135-163) — are optax transforms
inside the jitted step, so they compile into the same XLA program as the psum.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import flax.struct
import jax
import jax.numpy as jnp
import optax

from ..config import OptimConfig


class _ValueEqMethod:
    """Value-comparable wrapper for a bound method held in a static field.

    Static fields ride the pytree treedef, which jit compares with ``==``.
    Bound methods compare by ``__self__`` IDENTITY, so two equal-config
    trainers passing ``model.apply`` get unequal TrainState treedefs and the
    shared train step silently retraces (and recompiles, seconds per
    program) once per trainer instance. Flax modules compare by config, so
    delegating equality to (underlying function, module) restores cross-
    trainer cache hits while ``state.apply_fn(params, x)`` keeps working."""

    __slots__ = ("_func", "_self")

    def __init__(self, method):
        self._func = method.__func__
        self._self = method.__self__

    def __call__(self, *args, **kwargs):
        return self._func(self._self, *args, **kwargs)

    def __eq__(self, other):
        return (type(other) is _ValueEqMethod and self._func is other._func
                and self._self == other._self)

    def __hash__(self):
        return hash((self._func, self._self))


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray
    params: Any
    opt_state: Any
    # runtime learning-rate multiplier (graftmend breach→action layer,
    # train/actions.py): a (), f32 DATA leaf — the host writes a new value
    # between steps (``state.replace(lr_scale=...)``) without recompiling,
    # which a schedule closed over by the tx (static) cannot do. Updates
    # are multiplied by it after ``tx.update``, which for Adam-family
    # optimizers (update = -lr·normalized ± decay) is exactly a
    # learning-rate scale; moments are untouched, so restoring the scale
    # to 1.0 restores the original trajectory going forward.
    #
    # OPT-IN at creation (``create(..., lr_scale=1.0)``; trainers arm it
    # from ``TrainConfig.runtime_lr_scale``): None means no leaf at all —
    # the compiled program is byte-identical to a scale-less step (the
    # extra per-leaf multiply measurably taxes compile time across the
    # fleet of trainer programs), and arming mid-run is deliberately
    # unsupported because the treedef change would break the pinned
    # out_shardings of an already-jitted step.
    #
    # static fields (no defaults: a direct construction missing them must
    # fail at construction, not later inside apply_gradients); lr_scale is
    # declared last purely for dataclass default ordering — static fields
    # are not pytree leaves, so the leaf order is unchanged
    apply_fn: Callable = flax.struct.field(pytree_node=False)
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)
    lr_scale: Any = None

    @classmethod
    def create(cls, *, apply_fn, params, tx, lr_scale=None):
        import inspect
        if inspect.ismethod(apply_fn):
            apply_fn = _ValueEqMethod(apply_fn)
        return cls(step=jnp.zeros((), jnp.int32), params=params,
                   opt_state=tx.init(params),
                   lr_scale=(None if lr_scale is None
                             else jnp.asarray(lr_scale, jnp.float32)),
                   apply_fn=apply_fn, tx=tx)

    def apply_gradients(self, grads, return_updates: bool = False,
                        **extra_args):
        """``extra_args`` feed GradientTransformationExtraArgs members of the
        chain — e.g. ``value=loss`` drives the plateau schedule; plain
        transforms ignore them (the tx is wrapped with extra-args support).
        ``return_updates=True`` additionally returns the optimizer's update
        tree (the graftpulse health taps derive per-layer-group update
        ratios from it without recomputing ``new - old`` params, which
        would read the donated input buffers) — post-``lr_scale``, i.e. the
        update actually applied."""
        # make_optimizer's transforms scope themselves ("clip", "optimizer")
        updates, opt_state = self.tx.update(grads, self.opt_state, self.params,
                                            **extra_args)
        with jax.named_scope("optimizer"):
            if self.lr_scale is not None:
                scale = self.lr_scale
                updates = jax.tree.map(lambda u: u * scale, updates)
            params = optax.apply_updates(self.params, updates)
        new = self.replace(step=self.step + 1, params=params,
                           opt_state=opt_state)
        return (new, updates) if return_updates else new


def make_lr_schedule(cfg: OptimConfig):
    if cfg.lr_scheduler == "constant":
        sched = optax.constant_schedule(cfg.learning_rate)
    elif cfg.lr_scheduler == "cosine":
        sched = optax.cosine_decay_schedule(cfg.learning_rate,
                                            max(cfg.total_steps - cfg.warmup_steps, 1))
    elif cfg.lr_scheduler == "exponential":
        # reference train_vae uses ExponentialLR(gamma=lr_decay_rate) per epoch;
        # here decay applies every lr_transition_steps steps
        sched = optax.exponential_decay(cfg.learning_rate,
                                        transition_steps=cfg.lr_transition_steps,
                                        decay_rate=cfg.lr_decay_rate)
    elif cfg.lr_scheduler == "plateau":
        # base lr stays constant; the ReduceLROnPlateau behavior is an
        # in-graph update scaler appended by make_optimizer (driven by the
        # step's loss via apply_gradients(value=...))
        sched = optax.constant_schedule(cfg.learning_rate)
    else:
        raise ValueError(f"unknown lr_scheduler {cfg.lr_scheduler!r}")
    if cfg.warmup_steps > 0:
        warm = optax.linear_schedule(0.0, cfg.learning_rate, cfg.warmup_steps)
        sched = optax.join_schedules([warm, sched], [cfg.warmup_steps])
    return sched


@functools.lru_cache(maxsize=128)
def make_optimizer(cfg: OptimConfig) -> optax.GradientTransformation:
    """Memoized on the (frozen, hashable) config: two trainers with equal
    OptimConfigs share ONE GradientTransformation object. This matters
    beyond allocation thrift — optax transforms are NamedTuples of fresh
    closures, and the tx rides TrainState's static treedef, so distinct tx
    objects force jit recompiles of otherwise-identical train steps (the
    test suite builds equal-config trainer pairs constantly; sharing the tx
    makes the second trainer's compile a cache hit)."""
    return _build_optimizer(cfg)


def _scoped(name: str, tx) -> optax.GradientTransformationExtraArgs:
    """``tx`` with its update traced under ``jax.named_scope(name)``, so the
    device's operations carry the name in a profile. Same ``init``, so the
    optimizer state's tree (and every checkpoint) is unchanged; a scope adds
    no primitive."""
    tx = optax.with_extra_args_support(tx)

    def update(updates, state, params=None, **extra_args):
        with jax.named_scope(name):
            return tx.update(updates, state, params, **extra_args)

    return optax.GradientTransformationExtraArgs(tx.init, update)


def _build_optimizer(cfg: OptimConfig) -> optax.GradientTransformation:
    sched = make_lr_schedule(cfg)
    if cfg.optimizer == "adam":
        core = optax.adam(sched, b1=cfg.beta1, b2=cfg.beta2, eps=cfg.eps)
    elif cfg.optimizer == "adamw":
        core = optax.adamw(sched, b1=cfg.beta1, b2=cfg.beta2, eps=cfg.eps,
                           weight_decay=cfg.weight_decay)
    elif cfg.optimizer == "adafactor":
        # factored second moments, no first moment: O(rows+cols) optimizer
        # state instead of Adam's 2x params — the single-chip path to
        # billion-param configs (multi-chip gets the same effect from fsdp
        # sharding of Adam state)
        core = optax.adafactor(sched, momentum=None,
                               weight_decay_rate=cfg.weight_decay or None)
    elif cfg.optimizer == "sgd":
        core = optax.sgd(sched)
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    parts = []
    if cfg.grad_clip_norm and cfg.grad_clip_norm > 0:
        parts.append(_scoped("clip",
                             optax.clip_by_global_norm(cfg.grad_clip_norm)))
    parts.append(_scoped("optimizer", core))
    tx = optax.chain(*parts)
    if cfg.grad_accum_steps > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=cfg.grad_accum_steps)
    if cfg.lr_scheduler == "plateau":
        # ReduceLROnPlateau parity (reference legacy/train_dalle.py:444-459),
        # as an update scaler fed the loss through apply_gradients(value=...).
        # Sits OUTSIDE MultiSteps so it composes with grad accumulation (the
        # reference runs ReduceLROnPlateau together with --ga_steps and steps
        # the scheduler once per data iteration, :100,444-459): the plateau
        # state sees every micro-step's loss; on accumulation micro-steps the
        # emitted updates are zero and scaling them is a no-op.
        from optax import contrib
        tx = optax.chain(optax.with_extra_args_support(tx),
                         contrib.reduce_on_plateau(
                             factor=cfg.plateau_factor,
                             patience=cfg.plateau_patience,
                             cooldown=cfg.plateau_cooldown,
                             min_scale=cfg.plateau_min_scale))
    return optax.with_extra_args_support(tx)


def make_scanned_steps(step_body: Callable):
    """Lift ``step_body(state, *xs_i) -> (state, metrics)`` into ONE jitted
    program running k steps via ``lax.scan`` over stacked per-step inputs
    (each leaf of ``xs`` has a leading k axis). One host dispatch covers k
    steps, and the interior state handoffs never touch the host — the TPU
    analogue of a captured CUDA graph replay. Returns the LAST step's metrics plus
    ``loss_mean`` over the k steps."""

    from functools import partial

    @partial(jax.jit, donate_argnums=(0,))
    def steps(state, xs):
        state, ms = jax.lax.scan(lambda st, x: step_body(st, *x), state, xs)
        metrics = jax.tree.map(lambda a: a[-1], ms)
        metrics["loss_mean"] = jnp.mean(ms["loss"])
        return state, metrics

    return steps


_JIT_STEP_CACHE: dict = {}


def jit_step(body, state=None, *, donate_argnums=(0,)):
    """jit a ``(state, *batch) -> (state, metrics)`` step body, pinning the
    returned state's shardings to the input ``state``'s when it is given.

    Without the pin, GSPMD freely propagates shardings onto output leaves
    whose inputs the partition rules left replicated (a size-1-fallback
    bias next to a tp-sharded kernel, a conv_out kernel whose own dims
    don't divide). The step's state sharding then has no fixed point: the
    first call returns differently-sharded leaves, so the second call
    compiles a SECOND executable, and — because a replicated input buffer
    cannot alias a sharded output — every mismatched donated leaf silently
    loses donation, keeping the old state live in HBM (the graftir donation
    audit counts exactly this). Metrics stay unpinned — every trainer's
    metrics are scalars, replicated either way.

    Memoized on (body, shardings): the step-body factories are lru_cached on
    (model, dtype, ...), so two equal-config trainers pass the SAME body
    object and the same sharding tree — they must get the same jitted
    wrapper back, or the second trainer's first step recompiles the whole
    program (~5 s) for a byte-identical executable."""
    if state is None:
        key = (body, donate_argnums)
        out_shardings = None
    else:
        shardings = jax.tree.map(lambda x: x.sharding, state)
        leaves, treedef = jax.tree.flatten(shardings)
        key = (body, donate_argnums, treedef, tuple(leaves))
        out_shardings = (shardings, None)
    fn = _JIT_STEP_CACHE.get(key)
    if fn is None:
        if out_shardings is None:
            fn = jax.jit(body, donate_argnums=donate_argnums)
        else:
            fn = jax.jit(body, donate_argnums=donate_argnums,
                         out_shardings=out_shardings)
        # bound the cache: un-memoized bodies (the vqgan factories build a
        # fresh closure per trainer) would otherwise pin dead executables
        # forever; insertion-order eviction keeps the recent/live ones
        while len(_JIT_STEP_CACHE) >= 256:
            _JIT_STEP_CACHE.pop(next(iter(_JIT_STEP_CACHE)))
        _JIT_STEP_CACHE[key] = fn
    return fn


def compute_dtype(precision) -> Any:
    """PrecisionConfig.compute → jnp dtype (None when already float32)."""
    name = getattr(precision, "compute", "float32")
    if name in ("float32", "f32", None):
        return None
    return jnp.dtype(name)


def cast_floating(tree, dtype):
    """Cast float leaves to ``dtype`` (params stay f32 in the optimizer; the
    cast copy feeds the forward — standard TPU mixed precision, replacing the
    reference's Apex AMP / DeepSpeed fp16 engine)."""
    if dtype is None:
        return tree
    return jax.tree.map(
        lambda x: x.astype(dtype)
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)
