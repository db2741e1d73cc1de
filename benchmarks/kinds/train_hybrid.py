"""Cells of kind ``train_hybrid``: ``kinds/train_moe.py``'s run, unchanged,
for a stack of two attention kinds in a cyclic pattern (Kimi-delta linear
attention and gated grouped-query attention) with routed experts in every
layer (``reference/solar_open2.py``): the same ``DalleTrainer.fit`` call
spanning the checked steps, the warm-up and the window, the same ``run``
dictionary for the metric readers, the same four kinds of compared number,
the same wait for the queued step before the trace opens, the same control
(fp8) and fault (half of the batch) in ``calibrate``.

Nothing of that run is copied: this module loads ``kinds/train_moe.py`` a
second time under a name of its own and gives that copy this stack's
reference, leaf names, weights and arithmetic (``reference/solar_open2``,
``adapter_solar_open2``, ``arith_hybrid``) in the places of DeepSeek-V2's.
What differs besides: the run refuses at once, by name, a program that
lacks the stack's layer kinds (the commit before the PR that brought them),
and ``fit()``'s records carry one more counter, ``kda_logdecay_min`` (the
most negative cumulative log-decay over a chunk, worst layer), printed with
the records.
"""

from __future__ import annotations

import statistics

from benchmarks import arith_hybrid, harness
from benchmarks.adapter_solar_open2 import make_weights, named_leaves
from benchmarks.harness import say
from benchmarks.kinds import train_moe
from benchmarks.reference import solar_open2 as ref

_run = harness.load_module(train_moe.__file__, __name__ + "._train_moe")
_run.ref = ref
_run.make_weights, _run.named_leaves = make_weights, named_leaves
_run.arith_moe = arith_hybrid      # held_param_count, train_flops_per_token
_run.COUNTERS = train_moe.COUNTERS + ("kda_logdecay_min",)


class _Records(train_moe._Records):
    """``fit()``'s records, the newest writer kept for the counter's line."""
    newest = None

    def __init__(self):
        super().__init__()
        _Records.newest = self


_run._Records = _Records


def refuse_unknown_kinds(cfg: dict) -> None:
    """Exit non-zero, at once, where the program does not have the block
    kinds the configuration names: the parent of the PR that brought them
    would otherwise fail later and less plainly."""
    from dalle_tpu.config import BlockConfig
    block = cfg["model"]["block"]
    known = BlockConfig.KINDS
    unknown = sorted(set(block["attention_layers"]) - set(known["attention"]))
    if block["positions"] not in known["positions"]:
        unknown.append(f"positions: {block['positions']}")
    if unknown or "attention_layers" not in BlockConfig.__dataclass_fields__:
        raise SystemExit(
            f"the program has no block kind {unknown or block['attention_layers']} "
            f"(config.BlockConfig knows attention {known['attention']} and no "
            f"per-layer pattern): {cfg['name']} cannot be built on this "
            f"commit; nothing was measured")


def calibrate(cell: dict, cfg: dict, *, seeds, control_seeds) -> dict:
    refuse_unknown_kinds(cfg)
    return _run.calibrate(cell, cfg, seeds=seeds, control_seeds=control_seeds)


def run_cell(cell: dict, cfg: dict, **kw) -> dict:
    """One run of a train_hybrid cell. Returns the keyword arguments of
    ``harness.finish``."""
    refuse_unknown_kinds(cfg)
    result = _run.run_cell(cell, cfg, **kw)
    lows = [m["kda_logdecay_min"] for _, _, m in _Records.newest.rows
            if "kda_logdecay_min" in m]
    if lows:
        say(f"[{cell['name']}] kda_logdecay_min over the run's records (the "
            f"most negative cumulative log-decay over a chunk, worst layer; "
            f"exp of it is the least decay the chunked form multiplies by): "
            f"median {statistics.median(lows):.2f}, least {min(lows):.2f}")
    return result
