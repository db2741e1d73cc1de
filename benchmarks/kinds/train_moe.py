"""Cells of kind ``train_moe``: ``kinds/train.py``'s run for a stack of
latent attention and routed experts (``reference/deepseek_v2.py``): the same
``DalleTrainer.fit`` call spanning the checked steps, the warm-up and the
window, the same ``run`` dictionary for the metric readers, the same four
kinds of compared number, the same control (fp8) and fault (half of the
batch) in ``calibrate``.

What differs: the weights, the leaf names and the reference are this
block's (``adapter_deepseek_v2``, ``arith_moe``); ``fit()``'s records carry
the step's counters (``moe_rows_held``, ``moe_load_max_over_mean``,
``moe_rows_dropped``: the trainer raises on a dropped row, so a run that
ends has none), and the first step's rows per held expert are printed
beside the reference's.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

from benchmarks import arith, arith_moe, harness, traffic
from benchmarks.adapter_deepseek_v2 import make_weights, named_leaves
from benchmarks.harness import say
from benchmarks.kinds.train import (CHECK_STEPS, _Feed, _Records, _find_state,
                                    check_batches, compare, make_trainer,
                                    worst_leaves)
from benchmarks.reference import deepseek_v2 as ref
from benchmarks.reference.dalle import _factored

COUNTERS = ("moe_rows_held", "moe_load_max_over_mean", "moe_rows_dropped")
# accepted readers of what every fit() cell has (its spans, records and
# trace). Their ``workloads`` lists name the dense cells, and adding a cell to
# one is a benchmark PR's edit: until then a traced run prints them in its log
FIT_READERS = ("device_idle_pct.train", "train_step_device_ms",
               "fit_batch_wait_pct", "fit_dispatch_ms", "fit_after_step_ms",
               "idle_in_fit_dispatch_pct", "idle_in_fit_sync_pct",
               "setup_trainer_init_s", "setup_fit_warmup_s")


# --------------------------------------------------------------------------
# what is read off the program's state during the first steps
# --------------------------------------------------------------------------

def first_gradient_norms(shapes, state) -> dict:
    """Every leaf's gradient norm as Adafactor got it at step 1: at step 1
    the decay is 0, so the moments are means of g^2 + 1e-30 (a stacked
    expert leaf's over its largest axis)."""
    import jax
    import jax.numpy as jnp
    fs = _find_state(state.opt_state, "FactoredState")

    def leaf_sq(p, row, full):
        if _factored(p.shape) is not None:
            return jnp.sum(row) * (p.size / row.size)
        return jnp.sum(full)
    sq = jax.device_get(jax.jit(lambda p, r, v: jax.tree.map(leaf_sq, p, r, v))(
        state.params, fs.v_row, fs.v))
    return {k: math.sqrt(max(float(v), 0.0))
            for k, v in named_leaves(shapes, sq).items()}


def change_norms(shapes: ref.Shapes, seed: int, params) -> dict:
    """||leaf - its value at the start|| for every leaf, on the device, in
    one program whose chains (a leaf made again from the seed, subtracted,
    squared, summed) are independent: the compiler runs them in turn, so
    nothing the size of the model is held beside the state."""
    import jax
    import jax.numpy as jnp
    specs = ref.leaf_specs(shapes)

    # the key is an argument: one compiled program for every seed
    def sq(key, leaves):
        return {name: jnp.sum(jnp.square(
            x - ref.init_leaf(key, name, specs[name])))
            for name, x in leaves.items()}
    out = jax.device_get(jax.jit(sq)(ref.seed_key(seed),
                                     named_leaves(shapes, params)))
    return {k: math.sqrt(max(float(v), 0.0)) for k, v in out.items()}


def reference_numbers(cell: dict, cfg: dict, seed: int, **kw) -> dict:
    shapes = ref.Shapes.from_model(cfg["model"])
    return ref.first_steps(shapes, cell["recipe"], seed,
                           check_batches(cell, cfg, seed), **kw)


def set_weights(trainer, shapes, seed: int) -> None:
    """``kinds/train.py``'s ``set_weights`` with this block's weights."""
    import jax
    import jax.numpy as jnp
    state = trainer.state
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=x.sharding), state.params)
    used = trainer._host_step != 0
    state = state.replace(params=None)
    trainer.state = None
    params = make_weights(shapes, seed, like)
    if used:
        shardings = jax.tree.map(lambda x: x.sharding, state.opt_state)
        state = state.replace(
            step=jnp.zeros_like(state.step),
            opt_state=jax.jit(state.tx.init, out_shardings=shardings)(params))
        trainer._host_step = 0
    trainer.state = state.replace(params=params)


class FirstSteps:
    """``on_step`` hook of the first, checked steps."""

    def __init__(self, trainer, shapes, recipe: dict, seed: int):
        if recipe["optimizer"] != "adafactor":
            raise SystemExit("a train_moe cell reads the first gradient off "
                             "Adafactor's moments; its recipe is adafactor")
        self.trainer, self.shapes, self.seed = trainer, shapes, seed
        self.program = {}

    def on_step(self, step: int) -> None:
        if step == 1:
            self.program["leaf_grad_norms"] = first_gradient_norms(
                self.shapes, self.trainer.state)
        if step == CHECK_STEPS:
            self.program["leaf_change_norms"] = change_norms(
                self.shapes, self.seed, self.trainer.state.params)

    def finish(self, records) -> dict:
        by_step = {s: m for s, _, m in records.rows}
        for name in ("loss", "grad_norm") + COUNTERS:
            self.program[name] = [by_step[i + 1][name]
                                  for i in range(CHECK_STEPS)]
        return self.program


def program_first_steps(trainer, cell: dict, cfg: dict, seed: int) -> dict:
    shapes = ref.Shapes.from_model(cfg["model"])
    set_weights(trainer, shapes, seed)
    first = FirstSteps(trainer, shapes, cell["recipe"], seed)
    records = _Records()
    trainer.fit(iter(check_batches(cell, cfg, seed)), log=lambda _msg: None,
                metrics_writer=records, on_step=first.on_step)
    return first.finish(records)


def free(trainer) -> None:
    import jax
    trainer.state = None
    trainer._last_good = trainer._last_good_device = None
    del trainer
    gc.collect()
    jax.clear_caches()


def rows_line(program: dict, reference: dict) -> str:
    """The first step's routed rows here, program beside reference."""
    ours = sum(sum(layer) for layer in reference["rows_per_expert"])
    return (f"rows routed to the held experts at step 1: program "
            f"{program['moe_rows_held'][0]:.0f}, reference {ours} "
            f"(per expert layer {[sum(l) for l in reference['rows_per_expert']]}"
            f"); program's largest group over the mean "
            f"{program['moe_load_max_over_mean'][0]:.3f}, rows dropped "
            f"{sum(program['moe_rows_dropped']):.0f}")


def calibrate(cell: dict, cfg: dict, *, seeds, control_seeds) -> dict:
    """``kinds/train.py``'s ``calibrate``: the program's first steps on
    every seed, then, with the trainer freed, the reference on every seed; on
    ``control_seeds`` also the control (the reference computed in fp8) and
    the fault "half of the batch left out", planted in the reference."""
    out = {"program": [], "control": [], "faults": {"half_batch": []}}
    programs = {}
    _, trainer = make_trainer(cell, cfg)
    for seed in seeds:
        t0 = time.perf_counter()
        programs[seed] = program_first_steps(trainer, cell, cfg, seed)
        say(f"[calibrate {cell['name']}] program, seed {seed}: "
            f"{time.perf_counter() - t0:.1f} s")
    free(trainer)
    del trainer
    half = slice(0, cell["traffic"]["batch"] // 2)
    for seed in seeds:
        t0 = time.perf_counter()
        reference = reference_numbers(cell, cfg, seed)
        row = {"seed": seed, "compared": compare(programs[seed], reference),
               "loss": reference["loss"], "program_loss": programs[seed]["loss"],
               "grad_norm": reference["grad_norm"],
               "program_grad_norm": programs[seed]["grad_norm"],
               "rows_per_expert": reference["rows_per_expert"],
               "program_counters": {k: programs[seed][k] for k in COUNTERS},
               "worst_leaves": worst_leaves(programs[seed], reference)}
        out["program"].append(row)
        say(f"[calibrate {cell['name']}] reference, seed {seed}: "
            f"{time.perf_counter() - t0:.1f} s; {row['compared']}; "
            + rows_line(programs[seed], reference))
        if seed in control_seeds:
            control = reference_numbers(cell, cfg, seed, precision="fp8")
            out["control"].append(
                {"seed": seed, "compared": compare(control, reference),
                 "worst_leaves": worst_leaves(control, reference)})
            fault = reference_numbers(cell, cfg, seed, rows=half)
            out["faults"]["half_batch"].append(
                {"seed": seed, "compared": compare(fault, reference)})
            say(f"[calibrate {cell['name']}] seed {seed}: control (fp8) "
                f"{out['control'][-1]['compared']}; half of the batch "
                f"{out['faults']['half_batch'][-1]['compared']}")
    return out


def run_cell(cell: dict, cfg: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, ledger, bench: dict) -> dict:
    """One run of a train_moe cell. Returns the keyword arguments of
    ``harness.finish``."""
    import jax
    shapes = ref.Shapes.from_model(cfg["model"])
    recipe, chips = cell["recipe"], cell["chips"]
    batch = cell["traffic"]["batch"]
    warm_steps = max(int(cell.get("warm_steps", 5)), CHECK_STEPS)
    trace_seconds = min(float(cell.get("trace_seconds", 5.0)), seconds / 2)
    name = cell["name"]

    t0 = time.perf_counter()
    model_cfg, trainer = make_trainer(cell, cfg)
    t1 = time.perf_counter()
    set_weights(trainer, shapes, seed)
    jax.block_until_ready(trainer.state.params)
    counted = arith_moe.held_param_count(cfg["model"])
    say(f"[{name}] set-up: {t0 - t_start:.1f} s to reach the chip, trainer "
        f"built in {t1 - t0:.1f} s (the program's eager init), weights made "
        f"from seed {seed} in {time.perf_counter() - t1:.1f} s: "
        f"{trainer.num_params / 1e9:.3f}B parameters held (benchmark's count "
        f"{counted}), block {model_cfg.block.name}, batch {batch}, "
        f"{recipe['optimizer']}")
    if trainer.num_params != counted:
        raise SystemExit("the benchmark's parameter count is not the "
                         "program's: the MFU arithmetic would be wrong")

    t0 = time.perf_counter()
    feed = _Feed(cell, cfg, seed, int(cell.get("batches_drawn_ahead", 256)))
    say(f"[{name}] set-up: {len(feed.ready)} batches drawn from the seed in "
        f"{time.perf_counter() - t0:.1f} s")
    records = _Records()
    tracer = harness.TraceWindow(name) if trace else None
    first = FirstSteps(trainer, shapes, recipe, seed)
    marks = {"fit": time.perf_counter()}

    def on_step(step: int) -> None:
        now = time.perf_counter()
        if step == 1:
            marks["step1"] = now
        first.on_step(step)
        if step == warm_steps:
            jax.block_until_ready(trainer.state.params)
            marks["compiles_open"] = ledger.compiles
            marks["open_step"] = step
            marks["open"] = time.perf_counter()
            feed.deadline = marks["open"] + seconds
        elif (tracer is not None and "open" in marks
              and "trace_step" not in marks
              and now >= feed.deadline - trace_seconds):
            # fit() calls on_step(N) with step N queued and N - 1 running:
            # wait for N, so that the trace holds the whole steps after it
            # and the readers' steps and rows are those its seconds cover
            jax.block_until_ready(trainer.state.params)
            marks["untraced_end"] = time.perf_counter()
            marks["trace_step"] = step
            tracer.open()

    trainer.fit(feed, log=lambda _msg: None, metrics_writer=records,
                on_step=on_step)
    jax.block_until_ready(trainer.state.params)
    if tracer is not None and "trace_step" in marks:
        tracer.close()
    marks["close"] = time.perf_counter()
    last_step = trainer._host_step
    compiles_in_window = ledger.compiles - marks["compiles_open"]
    window_s = marks["close"] - marks["open"]
    steps = last_step - marks["open_step"]
    tokens = steps * batch * shapes.seq_len
    tokens_per_s_per_chip = arith.rate(tokens, window_s) / chips
    memory_peak = harness.peak_bytes(chips)
    setup_s = marks["open"] - t_start

    program = first.finish(records)
    window_rows = [(s, t, m) for s, t, m in records.rows
                   if s > marks["open_step"]]
    dropped = sum(m["moe_rows_dropped"] for _, _, m in records.rows)
    failed = sum(1 for _, _, m in window_rows
                 if not math.isfinite(m["loss"]))
    failed += steps - len(window_rows)       # a step without a record

    say(f"[{name}] device: {device['platform']} {device['kind']!r} x "
        f"{device['count']}")
    say(f"[{name}] set-up: fit() to the end of its first step "
        f"{marks['step1'] - marks['fit']:.1f} s (the rollback snapshot, the "
        f"step's program loaded or compiled), to the window's opening "
        f"{marks['open'] - marks['step1']:.1f} s more")
    say(f"[{name}] set-up {setup_s:.2f} s; window {window_s:.3f} s, {steps} "
        f"steps ({warm_steps} before it), {tokens} tokens, "
        f"{tokens_per_s_per_chip:.1f} tokens/s/chip; losses of the first "
        f"steps {', '.join('%.5f' % v for v in program['loss'])}; last "
        f"{window_rows[-1][2]['loss'] if window_rows else float('nan'):.5f}")
    if window_rows:
        held = [m["moe_rows_held"] for _, _, m in window_rows]
        load = [m["moe_load_max_over_mean"] for _, _, m in window_rows]
        pairs = statistics.median(held) / (batch * shapes.seq_len)
        say(f"[{name}] routed rows computed here a step: median "
            f"{statistics.median(held):.0f} (least {min(held):.0f}, most "
            f"{max(held):.0f}) of {batch * shapes.seq_len} tokens x "
            f"{shapes.num_experts_per_tok}; largest held group over the "
            f"mean, worst layer: median {statistics.median(load):.3f}, most "
            f"{max(load):.3f}; moe_rows_dropped over the whole run: "
            f"{dropped:.0f} (must be 0); "
            f"{arith_moe.train_flops_per_token(cfg['model'], pairs) / 1e9:.3f} "
            f"GFLOP a token")
    if len(window_rows) > 2:
        gaps = [b[1] - a[1] for a, b in zip(window_rows, window_rows[1:])]
        parts = {k: arith.percentile([m[k] for _, _, m in window_rows
                                      if k in m] or [float("nan")], 50)
                 for k in ("t_batch_wait_s", "t_dispatch_s", "t_sync_s",
                           "t_h2d_s")}
        say(f"[{name}] a step on the host's clock: p50 "
            f"{1e3 * arith.percentile(gaps, 50):.2f} ms, p95 "
            f"{1e3 * arith.percentile(gaps, 95):.2f} ms, longest "
            f"{1e3 * max(gaps):.2f} ms; fit()'s own split, p50: " + ", ".join(
                f"{k} {1e3 * v:.2f} ms" for k, v in parts.items())
            + f"; batches drawn inside the window: "
              f"{max(0, feed.i - len(feed.ready))}")
        # a stall shows as one long step: say which, and fit()'s split of it
        worst = max(range(len(gaps)), key=gaps.__getitem__)
        step, _, m = window_rows[worst + 1]
        say(f"[{name}] the longest step was step {step} (the window opened "
            f"at {marks['open_step']}): " + ", ".join(
                f"{k} {1e3 * v:.2f} ms" for k, v in sorted(m.items())
                if k.startswith("t_")))
    say(f"[{name}] compiles inside the window: {compiles_in_window} "
        f"(must be 0)")
    say(f"[{name}] " + ledger.line())
    say(f"[{name}] peak bytes (buffers + reservations): {memory_peak} = "
        f"{memory_peak / 2**30:.2f} GiB")

    run = {"cell": cell, "config": cfg, "device": device,
           "window": {"seconds": window_s, "steps": steps, "tokens": tokens,
                      "tokens_per_s_per_chip": tokens_per_s_per_chip,
                      "open": marks["open"], "close": marks["close"]},
           "records": window_rows, "trace": None, "traced": None}
    device_out = dict(device, memory_peak_bytes=memory_peak)
    breakdown = None
    if tracer is not None and "trace_step" in marks:
        reduced = tracer.read()
        untraced_s = marks["untraced_end"] - marks["open"]
        untraced_steps = marks["trace_step"] - marks["open_step"]
        run["trace"] = reduced
        run["traced"] = {
            "steps": last_step - marks["trace_step"],
            "from_step": marks["trace_step"],
            "untraced_tokens_per_s_per_chip": arith.rate(
                untraced_steps * batch * shapes.seq_len, untraced_s) / chips}
        device_out.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        say(f"[{name}] traced {run['traced']['steps']} steps: window "
            f"{reduced['window_s']:.3f} s, busy {reduced['busy_s']:.3f} s")

    # which kernels the step was built with: looked up once the window has
    # closed, so that lowering the step again costs no set-up
    if "mosaic_calls" in cell:
        text, ids = traffic.train_batch(seed, 0, batch, cfg["model"],
                                        cell["traffic"])
        t, i = trainer._put_batch((text, ids))
        key = jax.random.fold_in(trainer.base_key, 0)
        calls = harness.mosaic_calls(
            trainer.step_fn.lower(trainer.state, t, i, key).as_text())
        say(f"[{name}] Mosaic calls in the lowered step: {calls} (the "
            f"cell's file expects {cell['mosaic_calls']}: the grouped "
            f"product forward, recomputed, and its two gradients, three "
            f"products an expert layer)")
        if device["platform"] == "tpu" and calls != cell["mosaic_calls"]:
            raise SystemExit(f"the lowered step holds {calls} Mosaic calls, "
                             f"the cell's file says {cell['mosaic_calls']}")
    # free the program's state, then the reference, then the verdict
    free(trainer)
    del trainer
    t_ref = time.perf_counter()
    reference = reference_numbers(cell, cfg, seed)
    compared = compare(program, reference)
    say(f"[{name}] reference: {CHECK_STEPS} steps in float32 at 'highest' in "
        f"{time.perf_counter() - t_ref:.1f} s; losses "
        f"{', '.join('%.5f' % v for v in reference['loss'])}; gradient norms "
        f"{', '.join('%.4f' % v for v in reference['grad_norm'])} (program "
        f"{', '.join('%.4f' % v for v in program['grad_norm'])})")
    say(f"[{name}] " + rows_line(program, reference))
    correct, shown = harness.judge(compared, cell["limits"])
    correct = correct and failed == 0 and dropped == 0

    metrics = harness.reported(bench, cell, trace, run, {
        "train_tokens_per_s_per_chip": (tokens_per_s_per_chip,
                                        "tokens/s/chip"),
        "setup_s": (setup_s, "s")})
    if trace:
        listed = {m["name"] for m in bench["per_layer"]} - set(metrics)
        for k, v in harness.read_metrics(
                [n for n in FIT_READERS if n in listed], run).items():
            say(f"[{name}] {k} (not in the result line: the cell is not on "
                f"that metric's list): {v['value']:.6g} {v['unit']}")
    return dict(correct=correct, attempted=steps, failed=failed,
                metrics=metrics, device=device_out, compared=shown,
                breakdown=breakdown)
