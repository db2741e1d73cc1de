"""Cells of kind ``train_hybrid_mtp``: ``kinds/train_moe.py``'s run,
unchanged, for a stack of Kimi-delta linear attention and latent attention
in a cyclic pattern with a leading dense layer, a biased group-limited
router and a multi-token-prediction block (``reference/ling3.py``): the same
``DalleTrainer.fit`` call spanning the checked steps, the warm-up and the
window, the same ``run`` dictionary for the metric readers, the same four
kinds of compared number, the same control (fp8) and fault (half of the
batch) in ``calibrate``.

Nothing of that run is copied: as ``kinds/train_hybrid.py`` does, this
module loads ``kinds/train_moe.py`` once more under a name of its own and
gives that copy this stack's reference, leaf names, weights and arithmetic
(``reference/ling3``, ``adapter_ling3``, ``arith_ling3``). What differs
besides: the run refuses at once, by name, a program that lacks the forms
the configuration names (the commit before the PR that brought them);
``fit()``'s records carry two more numbers, ``kda_logdecay_min`` and
``loss_mtp``, printed with the records, the second beside the reference's.
"""

from __future__ import annotations

import dataclasses
import statistics

from benchmarks import arith_ling3, harness
from benchmarks.adapter_ling3 import make_weights, named_leaves
from benchmarks.harness import say
from benchmarks.kinds import train_moe
from benchmarks.reference import ling3 as ref

_run = harness.load_module(train_moe.__file__, __name__ + "._train_moe")
_run.ref = ref
_run.make_weights, _run.named_leaves = make_weights, named_leaves
_run.arith_moe = arith_ling3      # held_param_count, train_flops_per_token
_run.COUNTERS = train_moe.COUNTERS + ("kda_logdecay_min", "loss_mtp")


class _Records(train_moe._Records):
    """``fit()``'s records, the newest writer kept for the counters' lines."""
    newest = None

    def __init__(self):
        super().__init__()
        _Records.newest = self


_run._Records = _Records
_reference_numbers = _run.reference_numbers


def _kept_reference(cell, cfg, seed, **kw):
    """``reference_numbers``, its last plain result kept for
    ``loss_mtp``'s line."""
    out = _reference_numbers(cell, cfg, seed, **kw)
    if not kw:
        _kept_reference.newest = out
    return out


_kept_reference.newest = None
_run.reference_numbers = _kept_reference


def refuse_unknown_kinds(cfg: dict) -> None:
    """Exit non-zero, at once, where the program does not have the fields
    and kinds the configuration names: the parent of the PR that brought
    them would otherwise fail later and less plainly."""
    from dalle_tpu.config import BlockConfig, DalleConfig
    model = cfg["model"]
    fields = {f.name for f in dataclasses.fields(BlockConfig)}
    missing = sorted(set(model["block"]) - fields)
    missing += sorted(f"model.{k}" for k in set(model)
                      - {f.name for f in dataclasses.fields(DalleConfig)})
    known = BlockConfig.KINDS
    missing += sorted(
        f"{key}: {model['block'][key]}" for key in known
        if key in model["block"] and model["block"][key] not in known[key])
    if missing:
        raise SystemExit(
            f"the program has no {missing} (config.BlockConfig knows "
            f"{sorted(fields)} and the kinds {known}): {cfg['name']} cannot "
            f"be built on this commit; nothing was measured")


def calibrate(cell: dict, cfg: dict, *, seeds, control_seeds) -> dict:
    refuse_unknown_kinds(cfg)
    return _run.calibrate(cell, cfg, seeds=seeds, control_seeds=control_seeds)


def run_cell(cell: dict, cfg: dict, **kw) -> dict:
    """One run of a train_hybrid_mtp cell. Returns the keyword arguments of
    ``harness.finish``."""
    refuse_unknown_kinds(cfg)
    result = _run.run_cell(cell, cfg, **kw)
    name, rows = cell["name"], _Records.newest.rows
    lows = [m["kda_logdecay_min"] for _, _, m in rows
            if "kda_logdecay_min" in m]
    if lows:
        say(f"[{name}] kda_logdecay_min over the run's records (the most "
            f"negative cumulative log-decay over a chunk, worst layer; at "
            f"least 64 x kda_lower_bound by construction): median "
            f"{statistics.median(lows):.2f}, least {min(lows):.2f}")
    mtp = [m["loss_mtp"] for _, _, m in rows if "loss_mtp" in m]
    if mtp and _kept_reference.newest:
        theirs = _kept_reference.newest["loss_mtp"]
        say(f"[{name}] loss_mtp of the first steps: program "
            f"{', '.join('%.5f' % v for v in mtp[:len(theirs)])}; reference "
            f"{', '.join('%.5f' % v for v in theirs)}; last {mtp[-1]:.5f}")
    return result
