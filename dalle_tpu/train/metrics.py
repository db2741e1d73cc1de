"""Throughput + MFU instrumentation.

The reference's two perf hooks (SURVEY.md §6): a samples/sec meter every 10
steps (legacy/train_dalle.py:601-602,651-654) and a FLOPS profile at step 200
(DeepSpeed flops profiler, :492-499). TPU equivalents: the same rolling
samples/sec meter, an analytic-FLOPs MFU estimate against the chip's peak, and
`jax.profiler` trace capture.
"""

from __future__ import annotations

import time
from typing import Optional

import jax

# peak bf16 matmul TFLOP/s per chip by device kind (public figures)
PEAK_TFLOPS = {
    "TPU v2": 45.0, "TPU v3": 123.0, "TPU v4": 275.0,
    "TPU v5 lite": 197.0, "TPU v5e": 197.0, "TPU v5": 459.0, "TPU v5p": 459.0,
    "TPU v6 lite": 918.0, "TPU v6e": 918.0, "cpu": 0.1,
}


def device_peak_tflops(device: Optional[jax.Device] = None) -> float:
    """Peak bf16 TFLOP/s of ``device`` (default: the first one). A device
    kind with no row in PEAK_TFLOPS is an error: an MFU over a guessed
    denominator is not a measurement."""
    d = device or jax.devices()[0]
    kind = getattr(d, "device_kind", "cpu")
    for k, v in PEAK_TFLOPS.items():
        if kind.lower().startswith(k.lower()):
            return v
    raise ValueError(
        f"unknown accelerator {kind!r}: no peak FLOP/s to compute MFU "
        "against — add the chip's published peak to "
        "train/metrics.PEAK_TFLOPS")


class ThroughputMeter:
    """samples/sec + tokens/sec + MFU, reported every ``interval`` steps
    (reference computes batch*10/Δt every 10 steps)."""

    def __init__(self, batch_size: int, interval: int = 10,
                 tokens_per_sample: int = 0, flops_per_step: float = 0.0,
                 num_chips: int = 1):
        self.batch = batch_size
        self.interval = interval
        self.tokens_per_sample = tokens_per_sample
        self.flops_per_step = flops_per_step
        self.num_chips = max(num_chips, 1)
        self._t0 = time.perf_counter()
        self._last_step = 0
        self._last_report = None

    def step(self, step_num: int):
        """Call at any (possibly irregular) step numbers — e.g. only at
        ``metrics_every`` boundaries; rates use the ACTUAL steps elapsed."""
        if step_num - self._last_step < self.interval or step_num == 0:
            return None
        now = time.perf_counter()
        dt = now - self._t0
        n_steps = step_num - self._last_step
        self._t0 = now
        self._last_step = step_num
        sps = self.batch * n_steps / dt
        rep = {"sample_per_sec": sps, "step_time_s": dt / n_steps}
        if self.tokens_per_sample:
            rep["tokens_per_sec"] = sps * self.tokens_per_sample
            rep["tokens_per_sec_per_chip"] = sps * self.tokens_per_sample / self.num_chips
        if self.flops_per_step:
            achieved = self.flops_per_step * n_steps / dt
            rep["mfu"] = achieved / (
                device_peak_tflops() * 1e12 * self.num_chips)
        self._last_report = rep
        return rep


def transformer_train_flops(n_params: int, tokens_per_batch: int) -> float:
    """6·N·D analytic training FLOPs per step (fwd+bwd) — the standard MFU
    denominator's numerator."""
    return 6.0 * n_params * tokens_per_batch


def count_params(params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def profile_trace(logdir: str, fn, *args):
    """Capture a jax.profiler trace around one call of ``fn`` — the stand-in for
    the reference's flops-profiler-at-step-200 report."""
    with jax.profiler.trace(logdir):
        out = fn(*args)
        jax.block_until_ready(out)
    return out


class MetricsLogger:
    """Experiment-metrics sink: JSONL on disk, mirrored to wandb when the
    package+login are available — the reference's L6 observability layer
    (wandb.init/log at legacy/train_dalle.py:463-476,659-660) without a hard
    dependency on the external service."""

    def __init__(self, path: Optional[str] = None, use_wandb: bool = False,
                 project: str = "dalle-tpu", config: Optional[dict] = None,
                 run_name: Optional[str] = None):
        self._fh = open(path, "a") if path else None
        self._wandb = None
        if use_wandb:
            try:
                import wandb
                self._wandb = wandb.init(project=project, name=run_name,
                                         config=config or {}, resume="allow")
            except Exception as e:   # noqa: BLE001 - wandb offline / not
                # installed / auth failure: all degrade to jsonl-only logging
                print(f"[metrics] wandb unavailable ({e!r}); jsonl only")

    @staticmethod
    def _coerce_scalar(v):
        """Numeric scalars of ANY stripe → float: np.float32 is not a
        ``float`` and a 0-d device array is not an ``int``, so the plain
        isinstance filter used to drop them from the JSONL silently.
        Returns None for non-scalars (arrays, objects)."""
        if isinstance(v, (bool, int, float, str)):
            return v
        if getattr(v, "ndim", None) == 0:   # 0-d numpy/jax array, np scalar
            try:
                return float(v)
            except (TypeError, ValueError):  # non-numeric dtype
                return None
        return None

    def log(self, step: int, metrics: dict):
        import json
        import time as _time
        from ..obs import metrics_snapshot
        merged = {**metrics, **metrics_snapshot()}   # obs counters/gauges
        coerced = ((k, self._coerce_scalar(v)) for k, v in merged.items())
        rec = {"step": step, "time": _time.time(),
               **{k: v for k, v in coerced if v is not None}}
        if self._fh is not None:
            self._fh.write(json.dumps(rec) + "\n")
            self._fh.flush()
        if self._wandb is not None:
            self._wandb.log({k: v for k, v in rec.items() if k != "step"},
                            step=step)

    def log_images(self, step: int, images, key: str = "samples",
                   captions=None):
        """Periodic generated/reconstruction image logging (reference
        legacy/train_dalle.py:639-649, train_vae.py:245-255). ``images`` is
        (b, H, W, C) float [0,1]; no-op without a live wandb run (disk grids
        are the script's responsibility)."""
        if self._wandb is None:
            return
        import numpy as np
        import wandb
        arr = np.asarray(images)
        caps = captions or [None] * len(arr)
        self._wandb.log(
            {key: [wandb.Image((a * 255).clip(0, 255).astype("uint8"),
                               caption=c) for a, c in zip(arr, caps)]},
            step=step)

    def log_artifact(self, path: str, name: str, type: str = "model",
                     metadata: Optional[dict] = None):
        """Checkpoint artifact upload (reference legacy/train_dalle.py:584-587,
        667-669: per-epoch trained-dalle wandb.Artifact). No-op without wandb."""
        if self._wandb is None:
            return
        import os
        import wandb
        art = wandb.Artifact(name, type=type, metadata=metadata or {})
        if os.path.isdir(path):
            art.add_dir(path)
        else:
            art.add_file(path)
        self._wandb.log_artifact(art)

    def close(self):
        if self._fh is not None:
            self._fh.close()
        if self._wandb is not None:
            self._wandb.finish()
