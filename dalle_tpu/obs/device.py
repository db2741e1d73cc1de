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

The third question, *which of the program's scopes does the device's time go
to?*, is answered from the program's side too: ``capture_program`` keeps the
optimized HLO text of a step that ran, ``program_scopes`` and ``scope_layer``
turn it into the table a device trace is joined to (second half of this
file; docs/OBSERVABILITY.md "Device time by scope").

This is the one jax-importing module of ``obs``, so importing it is also what
bridges ``obs.span`` to the jax profiler: every span becomes a
``jax.profiler.TraceAnnotation`` of the same name while a profiler session
is live (obs/trace.py stays importable without jax).
"""

from __future__ import annotations

import functools
import json
import re
from collections import deque
from typing import Optional

import jax

# private import; CompileCounter's self-test fails loudly if the event moves
from jax._src.dispatch import BACKEND_COMPILE_EVENT
# private import: HloPrintOptions, which Compiled.as_text() gives no way to
# pass (tests/test_obs_scopes.py reads a real step's table through it)
from jax._src.lib import xla_client

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


# ---------------------------------------------------------------------------
# device time by the program's own scopes (docs/OBSERVABILITY.md)
# ---------------------------------------------------------------------------
# A device trace names an operation by its HLO instruction ("%fusion.162 =
# ..."), the program names its work with ``jax.named_scope``. The optimized
# HLO of a jitted program holds both: every instruction's name and, as
# ``metadata={op_name="jit(step)/transpose(jvp(forward))/.../attn/kda_chunk/
# while/body/dot_general"}``, the scopes it was issued under, whether by the
# backward pass (``transpose(``) and whether by a recompute
# (``rematted_computation``). ``capture_program`` keeps that text for a
# program the process runs, ``program_scopes`` turns it into the table a
# trace's reader joins by instruction name, ``scope_layer`` is the one place
# that knows the program's vocabulary.

class ScopeTable(dict):
    """``{instruction name: op_name path}`` of one optimized program.
    ``inherited``: the instructions that carry no metadata of their own (the
    copies, bitcasts and transposes layout assignment inserts) and took the
    path of their first operand's producer. ``unseen``: the layers that the
    traced program scopes and no instruction of the executable carries: the
    executable was compiled from another version of the source (jax's
    persistent compile cache keys a program without its locations, so a
    change of scopes alone loads the older entry, with the older names)."""

    def __init__(self, paths=(), inherited=(), unseen=()):
        super().__init__(paths)
        self.inherited = frozenset(inherited)
        self.unseen = frozenset(unseen)


# name -> (the program's HLO text, the paths its jaxpr scopes) until the table
# is first asked for, then its ScopeTable; None where a capture failed
_programs: dict = {}
_capture_failures_said: set = set()


def capture_program(name: str, jitted, *args, log=print) -> bool:
    """Keep, under ``name`` (an earlier one is replaced), the optimized HLO
    text (``_print_options``) of the executable that ``jitted(*args)`` has
    just run. The text comes from the lowering that call made and compiled,
    found again on ``jitted`` by the same arguments (donated ones included:
    only their shapes and shardings are read): nothing is traced, compiled
    or loaded a second time, and where that lowering is not found compiled
    the capture is skipped, never compiled for. No ``Lowered``, ``Compiled``
    or executable outlives this call (a held executable keeps its
    reservation of the device's memory). Never raises: on any failure
    ``name`` holds None, and ``log`` hears of it once per name."""
    _programs[name] = None
    try:
        traced = jitted.trace(*args)
        lowered = traced.lower()
        # jax keeps a call's lowering and, on it, the executable; a lowering
        # without one is not the call's (other shardings), and compile()
        # would build a second program
        if getattr(lowered._lowering, "_executable", None) is None:
            raise RuntimeError("the lowering the call compiled was not found "
                               "again by these arguments")
        modules = lowered.compile().runtime_executable().hlo_modules()
        _programs[name] = ("\n".join(m.to_string(_print_options())
                                     for m in modules),
                           _source_paths(traced.jaxpr.jaxpr))
        return True
    except Exception as exc:  # noqa: BLE001 - telemetry never stops a fit()
        if name not in _capture_failures_said:
            _capture_failures_said.add(name)
            log(f"[obs] no scope table for {name!r} (device time by scope "
                f"will not be read): {exc!r}")
        return False


def _print_options():
    """``Compiled.as_text()``'s print of a module, less what no reader of
    the table needs and a TPU step's text is mostly made of: each Mosaic
    call's serialized kernel (``backend_config``), the operands' shapes and
    every shape's tiled layout. Names, operands and metadata stay."""
    options = xla_client._xla.HloPrintOptions()
    options.print_backend_config = False
    options.print_operand_shape = False
    options.print_large_constants = False
    options.include_layout_in_shapes = False
    return options


def program_scopes(name: str) -> Optional[ScopeTable]:
    """The ``{instruction name: op_name path}`` table of the program kept
    under ``name``: parsed at the first request, after which the text is
    dropped. None where nothing was captured."""
    held = _programs.get(name)
    if isinstance(held, tuple):
        text, source_paths = held
        table = parse_scopes(text)
        table.unseen = frozenset(
            {scope_layer(p)[0] for p in source_paths} - {"unscoped"}
            - {scope_layer(p)[0] for p in table.values()})
        held = _programs[name] = table
    return held


def write_program_scopes(path: str, name: str = "train/step") -> bool:
    """``{instruction: [layer, phase, op_name]}`` of the program kept under
    ``name`` as JSON at ``path``, so that a profile captured on this machine
    can be split by scope on another. False, and no file, without a table."""
    table = program_scopes(name)
    if not table:
        return False
    with open(path, "w") as f:
        json.dump({k: [*scope_layer(v), v] for k, v in table.items()}, f)
    return True


def _source_paths(jaxpr) -> set:
    """The name stacks in a jaxpr as ``op_name``-like paths, through its
    nested jaxprs (an inner equation's stack continues its caller's): what
    the SOURCE scopes, to hold the executable's names against."""
    paths, todo = set(), [(jaxpr, "")]
    while todo:
        jaxpr, prefix = todo.pop()
        own = {}             # one string per name stack and jaxpr
        for eqn in jaxpr.eqns:
            stack = eqn.source_info.name_stack
            path = own.get(id(stack))
            if path is None:
                path = own[id(stack)] = "/".join(
                    filter(None, (prefix, str(stack))))
            for value in eqn.params.values():
                for inner in (value if isinstance(value, (list, tuple))
                              else (value,)):
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        todo.append((inner, path))
        paths.update(own.values())
    return paths


_INSTRUCTION = re.compile(
    r"\s*(?:ROOT )?%?(?P<name>[\w.\-]+) = (?:\(.*?\)|\S+) [\w\-]+\(")
_OP_NAME = re.compile(r'op_name="([^";]*)')
# an operand, not a computation an attribute names (calls=%..., body=%...)
_OPERAND = re.compile(r"(?<![=\w.\-])%([\w.\-]+)")


def parse_scopes(hlo_text: str) -> ScopeTable:
    """The table of an optimized HLO module's text: every instruction that
    carries ``op_name`` metadata (the first where several were merged), and
    every one without that inherits its first operand's, in the text's
    order, so a chain of copies inherits along its length."""
    paths, inherited = {}, []
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        if found is None:
            continue
        name, rest = found["name"], line[found.end():]
        path = _OP_NAME.search(rest)
        if path is not None:
            paths[name] = path[1]
            continue
        operand = _OPERAND.search(rest)
        if operand is not None and operand[1] in paths:
            paths[name] = paths[operand[1]]
            inherited.append(name)
    return ScopeTable(paths, inherited)


def _rows(layer: str, *scopes: str) -> dict:
    return {tuple(scope.split("/")): layer for scope in scopes}


# the program's scopes as runs of whole path components, and each one's layer
_SCOPE_LAYERS = {
    **_rows("optimizer", "optimizer", "clip"),
    **_rows("loss", "loss", "loss/mtp"),
    **_rows("attn_core", "attn_core"),
    **_rows("attn_proj", "attn/qkv", "attn/out", "attn/gate", "attn/mla_q",
            "attn/mla_kv", "attn/mla_norm", "attn/gqa_qkv", "attn/kda_proj",
            "attn/kda_conv", "attn/kda_gates", "attn/gdn_proj",
            "attn/gdn_conv", "attn/gdn_gates"),
    **_rows("kda_chunk", "attn/kda_chunk"),
    **_rows("kda_state", "attn/kda_state"),
    **_rows("gdn_chunk", "attn/gdn_chunk"),
    **_rows("gdn_state", "attn/gdn_state"),
    **_rows("moe", "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
            "moe/shared"),
    **_rows("ff", "ff"),
    **_rows("embed", "embed/grad"),
    # norms, residuals, LayerScale, token shift, the tables' forward
    **_rows("other", "forward", "mtp/merge", "mtp/block"),
}
# jax writes a scope that a transform was applied under as e.g.
# "transpose(jvp(forward))"
_TRANSFORM = re.compile(r"\b(?:jvp|transpose|vmap)\(")


@functools.lru_cache(maxsize=1 << 17)     # a table's paths are read twice
def scope_layer(op_name: Optional[str]) -> tuple:
    """``(layer, phase)`` of an ``op_name`` path. The layer is that of the
    INNERMOST program scope on the path (``_SCOPE_LAYERS``; a scope matches
    whole components, so the flax module ``attn_2`` is not ``attn``),
    ``unscoped`` without one or without a path. The phase is ``update`` under
    ``optimizer`` / ``clip``, else ``remat`` where the path holds
    ``rematted_computation``, else ``bwd`` where it holds ``transpose(``,
    else ``fwd``."""
    if not op_name:
        return "unscoped", "fwd"
    parts = [p.rstrip(")") for p in _TRANSFORM.sub("", op_name).split("/")]
    layer = "unscoped"
    for i, part in enumerate(parts):
        layer = (_SCOPE_LAYERS.get((part, *parts[i + 1:i + 2]))
                 or _SCOPE_LAYERS.get((part,)) or layer)
    if layer == "optimizer":
        return layer, "update"
    if "rematted_computation" in parts:
        return layer, "remat"
    return layer, "bwd" if "transpose(" in op_name else "fwd"
