"""Device telemetry: HBM gauges + a live XLA compile counter.

Two questions a slow pod run always raises — *is HBM filling up?* and *is it
recompiling?* — both answerable in-process without a profiler attach:

  * ``device_memory_stats`` reads ``device.memory_stats()`` (PJRT allocator
    stats: bytes_in_use / peak_bytes_in_use on TPU/GPU). Backends without
    allocator stats (CPU) fall back to summing ``jax.live_arrays()`` buffer
    sizes, so the gauge is always present and always means "device bytes
    held by this process".
  * ``CompileCounter`` listens on ``jax.monitoring``'s backend-compile
    duration event and counts every XLA compile in the process. This is the
    runtime home of the counter the recompile guard
    (analysis/recompile_guard.py) introduced for tests — lifted here so
    recompiles-per-100-steps is a *training metric*, not just a test
    ceiling. The guard re-exports from this module.

``DeviceTelemetry`` bundles both into a poller the trainers call at metrics
boundaries: HBM used/peak plus a sliding-window recompile rate.

This is the one jax-importing module of ``obs``, so importing it is also what
bridges ``obs.span`` to the jax profiler: every span becomes a
``jax.profiler.TraceAnnotation`` of the same name while a profiler session
is live (obs/trace.py stays importable without jax).
"""

from __future__ import annotations

from collections import deque
from typing import Optional

import jax

# private import; CompileCounter's self-test fails loudly if the event moves
from jax._src.dispatch import BACKEND_COMPILE_EVENT

from .trace import set_profiler_annotation

set_profiler_annotation(jax.profiler.TraceAnnotation)


class CompileCounter:
    """Monotonic count of XLA backend compiles in this process."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **kwargs):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1


_counter: Optional[CompileCounter] = None


def _self_test(counter: CompileCounter) -> None:
    """A guard that fails open is worse than no guard: if jax renames the
    monitoring event, the count would stay 0 and every budget would pass
    forever. One tiny throwaway jit at install time proves the listener
    actually fires (a fresh lambda is never cache-hit)."""
    import jax.numpy as jnp
    before = counter.count
    jax.jit(lambda x: x + 1)(jnp.zeros((3,), jnp.float32))
    if counter.count == before:
        raise RuntimeError(
            "compile counter self-test failed: no backend-compile event "
            "observed for a fresh jit — jax likely renamed "
            f"{BACKEND_COMPILE_EVENT!r}; update obs/device.py")


def install_compile_counter() -> CompileCounter:
    """Idempotent: jax.monitoring has no unregister, so one listener is
    installed for the life of the process and shared by every caller."""
    global _counter
    if _counter is None:
        _counter = CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(_counter._on_event)
        _self_test(_counter)
    return _counter


def device_memory_stats(device: Optional[jax.Device] = None) -> dict:
    """HBM gauges for one device: ``{"hbm_bytes_in_use", "hbm_peak_bytes"}``.
    Uses the PJRT allocator stats when the backend exposes them; otherwise
    (CPU) sums live device buffers, with the peak tracked host-side by
    ``DeviceTelemetry``. Values are plain ints, never None."""
    d = device if device is not None else jax.devices()[0]
    stats = None
    try:
        stats = d.memory_stats()
    except Exception:  # noqa: BLE001 - backends without the PJRT stats API
        pass           # raise NotImplementedError/AttributeError; fall back
    if stats:
        out = {"hbm_bytes_in_use": int(stats.get("bytes_in_use", 0))}
        peak = stats.get("peak_bytes_in_use")
        if peak is not None:
            out["hbm_peak_bytes"] = int(peak)
        limit = stats.get("bytes_limit")
        if limit:
            out["hbm_bytes_limit"] = int(limit)
        # what loaded programs reserve for their temporaries sits outside
        # bytes_in_use on the TPU allocator
        reserved = stats.get("peak_bytes_reserved")
        if reserved is not None:
            out["hbm_peak_reserved_bytes"] = int(reserved)
        return out
    live = sum(int(x.nbytes) for x in jax.live_arrays())
    return {"hbm_bytes_in_use": live}


class DeviceTelemetry:
    """Polled device gauges for the fit loop: HBM used/peak plus the compile
    rate over a sliding step window (``recompiles_per_100_steps``). A rate
    that stays >0 after warmup is the recompile-storm signature the static
    lint can't see (data-dependent shape churn, fresh statics)."""

    def __init__(self, device: Optional[jax.Device] = None, window: int = 200):
        self.device = device if device is not None else jax.devices()[0]
        self.counter = install_compile_counter()
        self.window = window
        self._hist: deque = deque()      # (step, cumulative compile count)
        self._peak = 0

    def poll(self, step: int) -> dict:
        out = device_memory_stats(self.device)
        self._peak = max(self._peak, out["hbm_bytes_in_use"])
        # host-tracked peak for backends whose stats lack one
        out.setdefault("hbm_peak_bytes", self._peak)
        compiles = self.counter.count
        self._hist.append((step, compiles))
        while len(self._hist) > 1 and step - self._hist[0][0] > self.window:
            self._hist.popleft()
        out["compiles_total"] = compiles
        step0, count0 = self._hist[0]
        if step > step0:
            out["recompiles_per_100_steps"] = (
                100.0 * (compiles - count0) / (step - step0))
        return out
