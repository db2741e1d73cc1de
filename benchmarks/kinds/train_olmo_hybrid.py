"""Cells of kind ``train_olmo_hybrid``: ``kinds/train.py``'s run for a dense
stack of Gated DeltaNet linear attention and QK-normed softmax attention in a
cyclic pattern, post-norm, a SwiGLU in every layer
(``reference/olmo_hybrid.py``): the same ``DalleTrainer.fit`` call spanning
the checked steps, the warm-up and the window, the same ``run`` dictionary
for the metric readers, the same four kinds of compared number, the same
control (fp8) and fault (half of the batch) in ``calibrate``.

Nothing of that run is copied: as ``kinds/train_hybrid.py`` does with
``kinds/train_moe.py``, this module loads ``kinds/train.py`` once more under
a name of its own and gives that copy this stack's reference, leaf names,
weights and arithmetic (``reference/olmo_hybrid``, ``adapter_olmo_hybrid``,
``arith_olmo_hybrid``). What differs besides:

- the run refuses at once, by name, a program that lacks the block's kinds
  or fields (the commit before the PR that brought them), by
  ``kinds/train_hybrid_mtp.py``'s own check;
- ``fit()`` waits for the queued step before the trace opens, as
  ``kinds/train_moe.py`` does, so that the traced steps are whole and
  ``gdn_core_roofline`` divides by the steps its seconds cover;
- ``fit()``'s records carry two counters, ``gdn_logdecay_min`` and
  ``gdn_beta_over_one_pct``, printed with the records.
"""

from __future__ import annotations

import statistics
import time

from benchmarks import arith_olmo_hybrid, harness
from benchmarks.adapter_olmo_hybrid import make_weights, named_leaves
from benchmarks.harness import say
from benchmarks.kinds import train
from benchmarks.kinds.train_hybrid_mtp import refuse_unknown_kinds
from benchmarks.reference import olmo_hybrid as ref

COUNTERS = ("gdn_logdecay_min", "gdn_beta_over_one_pct")

_run = harness.load_module(train.__file__, __name__ + "._train")
_run.ref = ref
_run.make_weights, _run.named_leaves = make_weights, named_leaves
_run.arith = arith_olmo_hybrid    # dalle_param_count, rate, percentile


class _Records(train._Records):
    """``fit()``'s records, the newest writer kept for the counters' line."""
    newest = None

    def __init__(self):
        super().__init__()
        _Records.newest = self


_run._Records = _Records

# what the run in progress traces: whether, and how many seconds before the
# window's end the trace opens (``kinds/train.py``'s own rule)
_tracing = {"on": False, "seconds": 0.0}
_make_trainer = _run.make_trainer


def make_trainer(cell: dict, cfg: dict):
    """``kinds/train.py``'s trainer, whose ``fit()`` waits for the queued
    step at the ``on_step`` call that opens the trace: ``fit()`` calls
    ``on_step(N)`` with step N queued and N - 1 running, and a trace opened
    there would hold a step it does not count."""
    import jax
    model_cfg, trainer = _make_trainer(cell, cfg)
    fit = trainer.fit

    def fit_whole_steps_traced(feed, *, on_step, **kw):
        waited = []

        def wait_then(step):
            deadline = getattr(feed, "deadline", None)
            if (_tracing["on"] and deadline is not None and not waited
                    and time.perf_counter() >= deadline - _tracing["seconds"]):
                jax.block_until_ready(trainer.state.params)
                waited.append(step)
            on_step(step)
        return fit(feed, on_step=wait_then, **kw)

    trainer.fit = fit_whole_steps_traced
    return model_cfg, trainer


_run.make_trainer = make_trainer


def calibrate(cell: dict, cfg: dict, *, seeds, control_seeds) -> dict:
    refuse_unknown_kinds(cfg)
    return _run.calibrate(cell, cfg, seeds=seeds, control_seeds=control_seeds)


def run_cell(cell: dict, cfg: dict, **kw) -> dict:
    """One run of a train_olmo_hybrid cell. Returns the keyword arguments of
    ``harness.finish``."""
    refuse_unknown_kinds(cfg)
    _tracing.update(on=kw["trace"], seconds=min(
        float(cell.get("trace_seconds", 5.0)), kw["seconds"] / 2))
    result = _run.run_cell(cell, cfg, **kw)
    rows = [m for _, _, m in _Records.newest.rows if COUNTERS[0] in m]
    if rows:
        lows = [m["gdn_logdecay_min"] for m in rows]
        over = [m["gdn_beta_over_one_pct"] for m in rows]
        say(f"[{cell['name']}] over the run's records: gdn_logdecay_min (the "
            f"most negative cumulative log-decay over a chunk, worst layer) "
            f"median {statistics.median(lows):.2f}, least {min(lows):.2f}; "
            f"gdn_beta_over_one_pct (the (position, head) pairs whose beta "
            f"passes 1, a negative eigenvalue, mean over layers) median "
            f"{statistics.median(over):.2f}, least {min(over):.2f}, most "
            f"{max(over):.2f}")
    return result
