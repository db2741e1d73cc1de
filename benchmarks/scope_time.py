"""Device time of a traced run by the program's own scopes: the join of the
trace's operations (``run["trace"]["ops"]``: event name -> self seconds,
every operation, not the ten largest) with the table the program kept of its
step (``dalle_tpu.obs.device.program_scopes("train/step")``: instruction name
-> ``op_name`` path) through ``scope_layer``, the one place that knows which
scope is which layer: of the vocabulary this file holds only ``unscoped``.

On a program that lacks the table (the commit before the PR that brought it)
``by_layer`` finds nothing and returns None, and every reader under
``benchmarks/metrics/`` that asks it returns None with it.
"""

from __future__ import annotations

from benchmarks.harness import say

PROGRAM = "train/step"
MATCHED_AT_LEAST = 0.9      # of busy_s; under it the names are not this program's
KEPT = "scope_time"         # where a run keeps its split, once made


def instruction(event_name: str) -> str:
    """The HLO instruction's name in a device event's: the trace names an
    operation by its whole instruction, "%fusion.7 = bf16[...] fusion(...)"
    (``program_names.kernel_seconds`` reads it the same way)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def by_layer(run: dict):
    """``{"layers": {layer: seconds}, "phases": {phase: seconds}, "cells":
    {(layer, phase): seconds}, "matched_s", "inherited_s", "busy_s",
    "unscoped": [[event name, seconds], ...], "unseen": [layer, ...]}`` of
    the run's traced window (``unseen``: ``ScopeTable.unseen``),
    or None: without a trace, without a table, or when the events found in
    the table hold under 90 % of ``busy_s`` (the names are another
    program's). Time of an event the table lacks, or whose path holds no
    scope of the program, is ``unscoped``. The first call of a run prints
    the split as one log line and keeps it in ``run``."""
    if KEPT in run:
        return run[KEPT]
    run[KEPT] = split = _split(run)
    if split is not None:
        say(f"[{run['cell']['name']}] " + line(run, split))
    return split


def _split(run: dict):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    from dalle_tpu.obs import device
    program_scopes = getattr(device, "program_scopes", None)
    scope_layer = getattr(device, "scope_layer", None)
    table = program_scopes(PROGRAM) if program_scopes and scope_layer else None
    if not table:
        return None
    cells, unscoped = {}, {}
    matched_s = inherited_s = 0.0
    for event, seconds in trace["ops"].items():
        name = instruction(event)
        path = table.get(name)
        if path is not None:
            matched_s += seconds
            if name in table.inherited:
                inherited_s += seconds
        key = scope_layer(path)
        cells[key] = cells.get(key, 0.0) + seconds
        if key[0] == "unscoped":
            unscoped[event] = seconds
    if matched_s < MATCHED_AT_LEAST * trace["busy_s"]:
        say(f"[{run['cell']['name']}] no device time by scope: the events "
            f"found in the program's table hold {matched_s:.3f} s of "
            f"{trace['busy_s']:.3f} s busy, under "
            f"{100 * MATCHED_AT_LEAST:.0f} % (the table is another "
            f"program's)")
        return None
    layers, phases = {}, {}
    for (layer, phase), seconds in cells.items():
        layers[layer] = layers.get(layer, 0.0) + seconds
        phases[phase] = phases.get(phase, 0.0) + seconds
    return {"layers": layers, "phases": phases, "cells": cells,
            "matched_s": matched_s, "inherited_s": inherited_s,
            "busy_s": trace["busy_s"],
            "unseen": sorted(table.unseen),
            "unscoped": sorted(unscoped.items(), key=lambda kv: -kv[1])[:5]}


def line(run: dict, split: dict) -> str:
    """The whole table for the log: layers by phases in ms a traced step and
    the layer's share of busy time, then the five largest unscoped
    operations."""
    from benchmarks import program_names, xplane
    steps = (run.get("traced") or {}).get("steps") or 1
    capture_s = program_names.phase_total_s("warmup/scopes")
    busy = split["busy_s"]
    phases = sorted(split["phases"], key=lambda p: -split["phases"][p])
    rows = []
    for layer, seconds in sorted(split["layers"].items(),
                                 key=lambda kv: -kv[1]):
        parts = " ".join(
            f"{p} {1e3 * split['cells'][layer, p] / steps:.2f}"
            for p in phases if (layer, p) in split["cells"])
        rows.append(f"{layer} {1e3 * seconds / steps:.2f} ms "
                    f"{100 * seconds / busy:.2f} % ({parts})")
    return (
        f"device time by scope, ms a traced step ({steps} steps) and % of "
        f"busy {busy:.3f} s: " + "; ".join(rows) + ". By phase: "
        + ", ".join(f"{p} {100 * split['phases'][p] / busy:.2f} %"
                    for p in phases)
        + f". Events found in the program's table {100 * split['matched_s'] / busy:.2f} % "
          f"of busy, through an inherited path "
          f"{100 * split['inherited_s'] / busy:.2f} %; the layers add to "
          f"{100 * sum(split['layers'].values()) / busy:.2f} %; keeping the "
          f"program's text cost "
          f"{'?' if capture_s is None else '%.3f' % capture_s} s of set-up "
          f"(span warmup/scopes). Largest unscoped: "
        + ("; ".join(f"{xplane.short_name(name, 100)} "
                     f"{1e3 * s / steps:.3f} ms" for name, s in
                     split["unscoped"]) or "none")
        + (f". THE EXECUTABLE IS ANOTHER VERSION'S: the program's source "
           f"scopes {', '.join(split['unseen'])} and no operation of the "
           f"step carries them (a compile cache's entry, keyed without "
           f"locations, written before the scopes changed); the shares read "
           f"by that version's names" if split["unseen"] else ""))


def share_pct(run: dict, kind: str, name: str):
    """The share of the traced busy time, in %, of the layer (``kind``
    "layers") or phase ("phases") ``name``: 0.0 where the split stands and
    nothing ran under it; None without a split."""
    split = by_layer(run)
    if split is None:
        return None
    return 100.0 * split[kind].get(name, 0.0) / split["busy_s"]
