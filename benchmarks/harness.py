"""What every kind of cell shares: finding a cell's files by name, the look
for the chip, the compile cache and its ledger, peak memory, the profiler
window, per-layer metric readers, the comparison's verdict and the result's
last line. It holds no cell's name and no configuration's numbers."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
WORK = os.path.join(REPO, ".bench_work")     # git-ignored scratch, in the checkout


def say(msg: str) -> None:
    """An earlier line of standard output."""
    print(msg, flush=True)


# --------------------------------------------------------------------------
# files found by name
# --------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark() -> dict:
    return load_json(os.path.join(REPO, "BENCHMARK.json"))


def load_cell(name: str, bench: dict | None = None) -> tuple:
    """(cell file, configuration file) of the workload ``name``, found
    through ``BENCHMARK.json``: the cell's file is
    ``benchmarks/workloads/<name>.json``, its ``traffic`` names a mix under
    ``benchmarks/traffic_mixes/`` (loaded into ``cell["traffic"]``) and its
    configuration's ``file`` is given."""
    bench = bench or load_benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"({[w['name'] for w in bench['workloads']]})")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    cell = load_json(os.path.join(HERE, "workloads", f"{name}.json"))
    cfg = load_json(os.path.join(REPO, config["file"]))
    if cell["traffic"] != entry["traffic"]:
        raise SystemExit(f"workload file {name}.json disagrees with "
                         f"BENCHMARK.json on its traffic mix")
    cell["traffic"] = load_json(os.path.join(HERE, "traffic_mixes",
                                             f"{entry['traffic']}.json"))
    if cell["config"] != entry["config"] or cell["chips"] != entry["chips"]:
        raise SystemExit(f"workload file {name}.json disagrees with "
                         f"BENCHMARK.json on config or chips")
    return cell, cfg


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_kind(kind: str):
    """The runner of a kind of cell: ``benchmarks/kinds/<kind>.py``."""
    path = os.path.join(HERE, "kinds", f"{kind}.py")
    if not os.path.exists(path):
        raise SystemExit(f"no runner for cells of kind {kind!r} ({path})")
    return load_module(path, f"benchmarks.kinds.{kind}")


def read_metrics(names, run: dict) -> dict:
    """Each per-layer metric's own reader, ``benchmarks/metrics/<name>.py``
    with ``UNIT`` and ``read(run) -> number or None``. A reader that finds
    nothing to read returns None and the metric is left out of the line."""
    out = {}
    for name in names:
        module = load_module(os.path.join(HERE, "metrics", f"{name}.py"),
                             "benchmarks.metrics." + name.replace(".", "_"))
        value = module.read(run)
        if value is not None:
            out[name] = {"value": float(value), "unit": module.UNIT}
    return out


def reported(bench: dict, cell: dict, trace: bool, run: dict,
             end_to_end: dict) -> dict:
    """The ``metrics`` of the last line: with ``--trace 1`` the per-layer
    metrics that BENCHMARK.json lists for this cell, each from its reader;
    else the kind's end-to-end metrics, ``{name: (value, unit)}``."""
    if not trace:
        return {k: {"value": float(v), "unit": unit}
                for k, (v, unit) in end_to_end.items()}
    return read_metrics([m["name"] for m in bench["per_layer"]
                         if cell["name"] in m.get("workloads", [cell["name"]])],
                        run)


# --------------------------------------------------------------------------
# the device
# --------------------------------------------------------------------------

def require_tpu(chips: int) -> dict:
    """The device as jax reports it. Exits non-zero, with no result, unless
    jax finds a TPU with at least the chips the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"benchmarks/run.py measures the TPU and jax found "
                         f"platform {devices[0].platform!r}: nothing was "
                         f"measured and no metric is printed.")
    if len(devices) < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s) and jax found "
                         f"{len(devices)}: nothing was measured.")
    return describe_device(chips)


def describe_device(chips: int) -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def peak_bytes(chips: int = 1) -> int:
    """Peak on the fullest chip: buffers plus what loaded programs reserved
    for their temporaries (the TPU reports the two apart; PR 21)."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)
                             + stats.get("peak_bytes_reserved", 0)))
    return peak


def mosaic_calls(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


class CompileLedger:
    """Backend compiles (count, seconds) and persistent-cache hits/misses in
    this process, from jax.monitoring (copied from chip_smoke.py, PR 21)."""

    def __init__(self):
        import jax
        from jax._src.dispatch import BACKEND_COMPILE_EVENT
        self._event = BACKEND_COMPILE_EVENT
        self.compiles = 0
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_dur)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_dur(self, event, duration, **kw):
        if event == self._event:
            self.compiles += 1
            self.compile_s += duration

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def line(self) -> str:
        import jax
        return (f"compile: {self.compiles} programs, {self.compile_s:.1f} s in "
                f"the backend (cold where missed); cache {self.hits} hits / "
                f"{self.misses} misses in "
                f"{jax.config.jax_compilation_cache_dir}")


def enable_compile_cache() -> str:
    """The program's own switch: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else the fixed ``.xla_cache/`` inside the checkout."""
    from dalle_tpu.utils.misc import enable_compilation_cache
    return enable_compilation_cache()


# --------------------------------------------------------------------------
# the traced part of a window
# --------------------------------------------------------------------------

class TraceWindow:
    """A jax profiler session with the benchmark's marks at both ends, read
    back and removed. ``open`` and ``close`` are called at points where the
    device is idle (a step boundary after a sync)."""

    def __init__(self, name: str):
        self.dir = os.path.join(WORK, f"trace_{name}")
        self.opened_at = None
        self.closed_at = None

    def open(self) -> None:
        import jax
        from benchmarks import xplane
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0     # host spans, no Python frames
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=options)
        with jax.profiler.TraceAnnotation(xplane.MARK_OPEN):
            self.opened_at = time.perf_counter()

    def close(self) -> None:
        import jax
        from benchmarks import xplane
        with jax.profiler.TraceAnnotation(xplane.MARK_CLOSE):
            self.closed_at = time.perf_counter()
        jax.profiler.stop_trace()

    def read(self) -> dict:
        from benchmarks import xplane
        try:
            return xplane.reduce(xplane.load(xplane.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


# --------------------------------------------------------------------------
# the verdict and the last line
# --------------------------------------------------------------------------

def judge(compared: dict, limits: dict) -> tuple:
    """``compared``: {short name: number}, everything the kind can compare;
    ``limits``: the cell's file, which names the numbers this cell is held
    to, each with its limit. Returns (correct, {name: [number, limit]})."""
    if not limits:
        raise SystemExit("the cell's file sets no limit: nothing would "
                         "decide `correct`")
    shown, ok = {}, True
    for name, limit in limits.items():
        if name not in compared:
            raise SystemExit(f"the cell's file sets a limit for {name!r}, "
                             f"which this kind of cell does not compare "
                             f"({sorted(compared)})")
        shown[name] = [float(compared[name]), float(limit)]
        # a NaN compares false and so fails
        ok = ok and (float(compared[name]) <= float(limit))
    return ok, shown


def finish(*, correct: bool, attempted: int, failed: int, metrics: dict,
           device: dict, compared: dict,
           breakdown: dict | None = None) -> None:
    """The compared numbers beside their limits as the last lines of standard
    error, and the contract's one JSON object as the last line of standard
    output, with ``compared`` as its last key."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["compared"] = compared
    sys.stdout.flush()
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value:.6g} (limit {limit:.6g})"
              f"{'' if value <= limit else '  <-- over'}", file=sys.stderr)
    print(f"correct: {bool(correct)}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
