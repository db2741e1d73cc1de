"""Cells of kind ``train``: ``DalleTrainer.fit`` driven by a host iterator of
batches drawn from the seed, one ``fit()`` call spanning the first (checked)
steps, the warm-up and the measured window.

What is taken from the program: ``DalleTrainer`` with its configs, ``fit``,
its metrics records and its state. Everything else (weights, batches, the
reference, the comparison, the arithmetic) is the benchmark's own.
"""

from __future__ import annotations

import gc
import math
import os
import statistics
import time

from benchmarks import arith, harness, traffic
from benchmarks.adapter import make_weights, named_leaves
from benchmarks.harness import say
from benchmarks.reference import dalle as ref

CHECK_STEPS = 3            # the reference follows the first three steps
UNMOVED_GRAD_SHARE = 1e-3  # leaves under this share of the median gradient
#                            move by round-off alone: left out of the change


# --------------------------------------------------------------------------
# what is read off the program's state during the first steps
# --------------------------------------------------------------------------

def _find_state(opt_state, type_name: str):
    import jax
    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: type(x).__name__ == type_name)
        if type(x).__name__ == type_name]
    if len(found) != 1:
        raise RuntimeError(f"{len(found)} {type_name} in the optimizer state")
    return found[0]


def first_gradient_norms(shapes, optimizer: str, state, recipe: dict) -> dict:
    """Every leaf's gradient norm as the optimizer got it at step 1, worked
    out from its state after that step. Adam: mu = (1 - b1) g. Adafactor: at
    step 1 the decay is 0, so the moments are means of g^2 + 1e-30."""
    import jax
    import jax.numpy as jnp
    if optimizer == "adam":
        b1 = float(recipe.get("beta1", 0.9))
        mu = _find_state(state.opt_state, "ScaleByAdamState").mu
        sq = jax.jit(lambda t: jax.tree.map(
            lambda m: jnp.sum(jnp.square(m)) / (1 - b1) ** 2, t))(mu)
    elif optimizer == "adafactor":
        fs = _find_state(state.opt_state, "FactoredState")

        def leaf_sq(p, row, full):
            if ref._factored(p.shape) is not None:
                return jnp.sum(row) * (p.size / row.size)
            return jnp.sum(full)
        sq = jax.jit(lambda p, r, v: jax.tree.map(leaf_sq, p, r, v))(
            state.params, fs.v_row, fs.v)
    else:
        raise SystemExit(f"no reading of the first gradient for {optimizer!r}")
    sq = jax.device_get(sq)
    return {k: math.sqrt(max(float(v), 0.0))
            for k, v in named_leaves(shapes, sq).items()}


def change_norms(shapes: ref.Shapes, seed: int, params) -> dict:
    """||leaf - its value at the start|| for every leaf, on the device: the
    start is made again from the seed, one leaf name at a time, so nothing
    the size of the model is held beside the state."""
    import jax
    import jax.numpy as jnp
    key = ref.seed_key(seed)
    specs = ref.leaf_specs(shapes)
    by_name = {}
    for full, leaf in named_leaves(shapes, params).items():
        name, _, layer = full.partition(".")
        by_name.setdefault(name, []).append((int(layer or 0), full, leaf))
    out = {}
    for name, rows in by_name.items():
        spec = specs[name]

        # the key is an argument: one compiled program for every seed
        def sq(key, leaves, layers, name=name, spec=spec):
            return jnp.stack([
                jnp.sum(jnp.square(
                    x.reshape(spec[0]) - ref.init_leaf(key, name, spec, l)))
                for x, l in zip(leaves, layers)])
        vals = jax.device_get(jax.jit(sq)(
            key, [leaf for _, _, leaf in rows],
            jnp.asarray([l for l, _, _ in rows], jnp.int32)))
        for (_, full, _), v in zip(rows, vals):
            out[full] = math.sqrt(max(float(v), 0.0))
    return out


# --------------------------------------------------------------------------
# the comparison
# --------------------------------------------------------------------------

def leaf_gaps(program: dict, reference: dict, keep=None) -> dict:
    """Per leaf, |program's norm - reference's norm| over max(the
    reference's norm of that leaf, the reference's median leaf): the gap
    between the two norms, not the norm of a difference."""
    names = [k for k in reference if keep is None or k in keep]
    median = statistics.median(reference[k] for k in names)
    return {k: abs(program[k] - reference[k]) / max(reference[k], median)
            for k in names}


def compare(program: dict, reference: dict) -> dict:
    """Every number a train cell may be judged on; the cell's file chooses
    among them by giving a limit. ``program`` and ``reference`` are shaped
    like ``reference.dalle.first_steps``'s result. ``*_gap``: the worst of
    the three steps or of the leaves; ``*1_gap``: the first step alone;
    ``*_median_gap``: the median leaf."""
    def rel(a, b):
        return [abs(x - y) / abs(y) for x, y in zip(a, b)]
    grads = reference["leaf_grad_norms"]
    floor = UNMOVED_GRAD_SHARE * statistics.median(grads.values())
    moved = {k for k, v in grads.items() if v >= floor}
    loss = rel(program["loss"], reference["loss"])
    norm = rel(program["grad_norm"], reference["grad_norm"])
    grad = leaf_gaps(program["leaf_grad_norms"], grads)
    change = leaf_gaps(program["leaf_change_norms"],
                       reference["leaf_change_norms"], keep=moved)
    return {
        "loss_gap": max(loss), "loss1_gap": loss[0],
        "grad_norm_gap": max(norm), "grad_norm1_gap": norm[0],
        "leaf_grad_gap": max(grad.values()),
        "leaf_grad_median_gap": statistics.median(grad.values()),
        "leaf_change_gap": max(change.values()),
        "leaf_change_median_gap": statistics.median(change.values()),
    }


def worst_leaves(program: dict, reference: dict, top: int = 5) -> dict:
    """For a look by hand: the leaves with the widest gaps."""
    out = {}
    for which in ("leaf_grad_norms", "leaf_change_norms"):
        gaps = leaf_gaps(program[which], reference[which])
        out[which] = [[k, gaps[k], program[which][k], reference[which][k]]
                      for k in sorted(gaps, key=gaps.get, reverse=True)[:top]]
    return out


def check_batches(cell: dict, cfg: dict, seed: int) -> list:
    return [traffic.train_batch(seed, i, cell["traffic"]["batch"],
                                cfg["model"], cell["traffic"])
            for i in range(CHECK_STEPS)]


def reference_numbers(cell: dict, cfg: dict, seed: int, **kw) -> dict:
    shapes = ref.Shapes.from_model(cfg["model"])
    return ref.first_steps(shapes, cell["recipe"], seed,
                           check_batches(cell, cfg, seed), **kw)


# --------------------------------------------------------------------------
# the run
# --------------------------------------------------------------------------

def make_trainer(cell: dict, cfg: dict):
    """``chip_smoke.make_trainer`` (PR 21), the recipe from the cell's file."""
    import jax
    from dalle_tpu.config import (DalleConfig, MeshConfig, OptimConfig,
                                  TrainConfig)
    from dalle_tpu.parallel.mesh import build_mesh
    from dalle_tpu.train.trainer_dalle import DalleTrainer
    recipe = cell["recipe"]
    model_cfg = DalleConfig(**cfg["model"])
    mesh_cfg = MeshConfig(**cell.get("mesh", {}))
    tc = TrainConfig(
        batch_size=cell["traffic"]["batch"],
        checkpoint_dir=os.path.join(harness.WORK, f"ckpt_{cell['name']}"),
        preflight_checkpoint=False, save_every_steps=0, log_every=10 ** 9,
        metrics_every=1, scan_steps=1, mesh=mesh_cfg,
        optim=OptimConfig(optimizer=recipe["optimizer"],
                          learning_rate=recipe.get("learning_rate", 3e-4),
                          grad_clip_norm=recipe["grad_clip_norm"]))
    mesh = build_mesh(mesh_cfg, devices=jax.devices()[:cell["chips"]])
    return model_cfg, DalleTrainer(model_cfg, tc, mesh=mesh)


def set_weights(trainer, shapes, seed: int) -> None:
    """The trainer at step 0 with the weights of ``seed``. The program's own
    initial parameters are dropped before the new ones are made, so the two
    are never resident together (the peak is the job's, not the set-up's).
    A trainer that has already stepped (calibrate.py drives one trainer
    through many seeds) gets a fresh optimizer state as well."""
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
    """``on_step`` hook of the first, checked steps: reads off the program's
    state what the comparison needs, at the steps it needs it."""

    def __init__(self, trainer, shapes, recipe: dict, seed: int):
        self.trainer, self.shapes = trainer, shapes
        self.recipe, self.seed = recipe, seed
        self.program = {}

    def on_step(self, step: int) -> None:
        if step == 1:
            self.program["leaf_grad_norms"] = first_gradient_norms(
                self.shapes, self.recipe["optimizer"], self.trainer.state,
                self.recipe)
        if step == CHECK_STEPS:
            self.program["leaf_change_norms"] = change_norms(
                self.shapes, self.seed, self.trainer.state.params)

    def finish(self, records) -> dict:
        by_step = {s: m for s, _, m in records.rows}
        for name in ("loss", "grad_norm"):
            self.program[name] = [by_step[i + 1][name]
                                  for i in range(CHECK_STEPS)]
        return self.program


def program_first_steps(trainer, cell: dict, cfg: dict, seed: int) -> dict:
    """The program's numbers of the first steps alone, with no window:
    what calibrate.py reads over many seeds. The same ``fit()`` entry, feed
    and hook as a run's."""
    shapes = ref.Shapes.from_model(cfg["model"])
    set_weights(trainer, shapes, seed)
    first = FirstSteps(trainer, shapes, cell["recipe"], seed)
    records = _Records()
    trainer.fit(iter(check_batches(cell, cfg, seed)), log=lambda _msg: None,
                metrics_writer=records, on_step=first.on_step)
    return first.finish(records)


def calibrate(cell: dict, cfg: dict, *, seeds, control_seeds) -> dict:
    """The readings a train cell's limits are set between (calibrate.py):
    the program's first steps on every seed, then, with the trainer freed,
    the reference on every seed; on ``control_seeds`` also the control (the
    reference with fp8 for its compute type, one step below the bfloat16
    the configurations state) and the fault "half of the batch left out",
    planted in the reference. A state left unchanged reads 1 on
    ``leaf_change_gap`` by that number's own measure and needs no run."""
    import jax
    out = {"program": [], "control": [], "faults": {"half_batch": []}}
    programs = {}
    _, trainer = make_trainer(cell, cfg)
    for seed in seeds:
        t0 = time.perf_counter()
        programs[seed] = program_first_steps(trainer, cell, cfg, seed)
        say(f"[calibrate {cell['name']}] program, seed {seed}: "
            f"{time.perf_counter() - t0:.1f} s")
    trainer.state = None
    trainer._last_good = trainer._last_good_device = None
    del trainer
    gc.collect()
    jax.clear_caches()
    half = slice(0, cell["traffic"]["batch"] // 2)
    for seed in seeds:
        t0 = time.perf_counter()
        reference = reference_numbers(cell, cfg, seed)
        row = {"seed": seed, "compared": compare(programs[seed], reference),
               "loss": reference["loss"], "program_loss": programs[seed]["loss"],
               "grad_norm": reference["grad_norm"],
               "program_grad_norm": programs[seed]["grad_norm"],
               "worst_leaves": worst_leaves(programs[seed], reference)}
        out["program"].append(row)
        say(f"[calibrate {cell['name']}] reference, seed {seed}: "
            f"{time.perf_counter() - t0:.1f} s; {row['compared']}")
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


class _Feed:
    """The host iterator ``fit()`` pulls batches from: batch ``i`` of the
    seed, a fresh one every step, until the window's time is up. The batches
    are drawn before ``fit()`` starts: the program's prefetcher pulls the
    next batch on a thread of its own at the moment the main thread
    dispatches a step, and drawing it there in Python would hold the
    interpreter lock against that dispatch, by an amount that differs from
    run to run (my chip runs, PR 24: 182k to 206k tokens/s/chip over six
    runs of one cell)."""

    def __init__(self, cell, cfg, seed, drawn: int):
        self.cell, self.cfg, self.seed = cell, cfg, seed
        self.ready = [self._draw(i) for i in range(drawn)]
        self.i = 0
        self.deadline = None      # set when the window opens

    def _draw(self, i: int):
        return traffic.train_batch(self.seed, i, self.cell["traffic"]["batch"],
                                   self.cfg["model"], self.cell["traffic"])

    def __iter__(self):
        return self

    def __next__(self):
        if self.deadline is not None and time.perf_counter() >= self.deadline:
            raise StopIteration
        batch = (self.ready[self.i] if self.i < len(self.ready)
                 else self._draw(self.i))
        self.i += 1
        return batch


class _Records:
    """metrics_writer for fit(): every record with the host's clock."""

    def __init__(self):
        self.rows = []

    def log(self, step, metrics):
        self.rows.append((int(step), time.perf_counter(), dict(metrics)))


def run_cell(cell: dict, cfg: dict, *, seed: int, seconds: float, trace: bool,
             t_start: float, device: dict, ledger, bench: dict) -> dict:
    """One run of a train cell. Returns the keyword arguments of
    ``harness.finish``."""
    import jax
    shapes = ref.Shapes.from_model(cfg["model"])
    recipe, chips = cell["recipe"], cell["chips"]
    batch = cell["traffic"]["batch"]
    warm_steps = max(int(cell.get("warm_steps", 5)), CHECK_STEPS)
    trace_seconds = min(float(cell.get("trace_seconds", 5.0)), seconds / 2)

    t0 = time.perf_counter()
    model_cfg, trainer = make_trainer(cell, cfg)
    t1 = time.perf_counter()
    set_weights(trainer, shapes, seed)
    jax.block_until_ready(trainer.state.params)
    say(f"[{cell['name']}] set-up: {t0 - t_start:.1f} s to reach the chip, "
        f"trainer built in {t1 - t0:.1f} s (the program's eager init), "
        f"weights made from seed {seed} in {time.perf_counter() - t1:.1f} s: "
        f"{trainer.num_params / 1e9:.3f}B parameters "
        f"(benchmark's count {arith.dalle_param_count(cfg['model'])}), batch "
        f"{batch}, {recipe['optimizer']}")
    if trainer.num_params != arith.dalle_param_count(cfg["model"]):
        raise SystemExit("the benchmark's parameter count is not the "
                         "program's: the MFU arithmetic would be wrong")

    t0 = time.perf_counter()
    feed = _Feed(cell, cfg, seed, int(cell.get("batches_drawn_ahead", 256)))
    say(f"[{cell['name']}] set-up: {len(feed.ready)} batches drawn from the "
        f"seed in {time.perf_counter() - t0:.1f} s")
    records = _Records()
    tracer = harness.TraceWindow(cell["name"]) if trace else None
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
            marks["untraced_end"] = now
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
    tokens = steps * cell["traffic"]["batch"] * shapes.seq_len
    tokens_per_s_per_chip = arith.rate(tokens, window_s) / chips
    memory_peak = harness.peak_bytes(chips)
    setup_s = marks["open"] - t_start

    program = first.finish(records)
    window_rows = [(s, t, m) for s, t, m in records.rows
                   if s > marks["open_step"]]
    failed = sum(1 for _, _, m in window_rows if not math.isfinite(m["loss"]))
    failed += steps - len(window_rows)       # a step without a record

    say(f"[{cell['name']}] device: {device['platform']} {device['kind']!r} x "
        f"{device['count']}")
    say(f"[{cell['name']}] set-up: fit() to the end of its first step "
        f"{marks['step1'] - marks['fit']:.1f} s (the rollback snapshot, the "
        f"step's program loaded or compiled), to the window's opening "
        f"{marks['open'] - marks['step1']:.1f} s more")
    say(f"[{cell['name']}] set-up {setup_s:.2f} s; window {window_s:.3f} s, "
        f"{steps} steps ({warm_steps} before it), {tokens} tokens, "
        f"{tokens_per_s_per_chip:.1f} tokens/s/chip; losses of the first "
        f"steps {', '.join('%.5f' % v for v in program['loss'])}; last "
        f"{window_rows[-1][2]['loss'] if window_rows else float('nan'):.5f}")
    if len(window_rows) > 2:
        gaps = [b[1] - a[1] for a, b in zip(window_rows, window_rows[1:])]
        parts = {k: arith.percentile([m[k] for _, _, m in window_rows
                                      if k in m] or [float("nan")], 50)
                 for k in ("t_batch_wait_s", "t_dispatch_s", "t_sync_s",
                           "t_h2d_s")}
        say(f"[{cell['name']}] a step on the host's clock: p50 "
            f"{1e3 * arith.percentile(gaps, 50):.2f} ms, p95 "
            f"{1e3 * arith.percentile(gaps, 95):.2f} ms, longest "
            f"{1e3 * max(gaps):.2f} ms; fit()'s own split, p50: " + ", ".join(
                f"{k} {1e3 * v:.2f} ms" for k, v in parts.items())
            + f"; batches drawn inside the window: "
              f"{max(0, feed.i - len(feed.ready))}")
        # a stall shows as one long step: say which, and fit()'s split of it
        worst = max(range(len(gaps)), key=gaps.__getitem__)
        step, _, m = window_rows[worst + 1]
        say(f"[{cell['name']}] the longest step was step {step} (the window "
            f"opened at {marks['open_step']}): " + ", ".join(
                f"{k} {1e3 * m[k]:.2f} ms" for k in parts if k in m))
    say(f"[{cell['name']}] compiles inside the window: {compiles_in_window} "
        f"(must be 0)")
    say(f"[{cell['name']}] " + ledger.line())
    say(f"[{cell['name']}] peak bytes (buffers + reservations): "
        f"{memory_peak} = {memory_peak / 2**30:.2f} GiB")

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
            "untraced_tokens_per_s_per_chip": arith.rate(
                untraced_steps * cell["traffic"]["batch"] * shapes.seq_len,
                untraced_s) / chips}
        device_out.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        say(f"[{cell['name']}] traced {run['traced']['steps']} steps: window "
            f"{reduced['window_s']:.3f} s, busy {reduced['busy_s']:.3f} s")

    # which attention tier the step was built with: looked up once the
    # window has closed, so that lowering the step again costs no set-up
    if "mosaic_calls" in cell:
        text, ids = traffic.train_batch(seed, 0, cell["traffic"]["batch"], cfg["model"],
                                        cell["traffic"])
        t, i = trainer._put_batch((text, ids))
        key = jax.random.fold_in(trainer.base_key, 0)
        calls = harness.mosaic_calls(
            trainer.step_fn.lower(trainer.state, t, i, key).as_text())
        say(f"[{cell['name']}] attention tier in the lowered step: "
            f"{'Mosaic kernel x%d' % calls if calls else 'dense XLA'} "
            f"(use_pallas={model_cfg.use_pallas!r}; the cell's file expects "
            f"{cell['mosaic_calls']})")
        if device["platform"] == "tpu" and calls != cell["mosaic_calls"]:
            raise SystemExit(f"the lowered step holds {calls} Mosaic calls, "
                             f"the cell's file says {cell['mosaic_calls']}")
    # free the program's state, then the reference, then the verdict
    trainer.state = None
    trainer._last_good = trainer._last_good_device = None
    del trainer
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    reference = reference_numbers(cell, cfg, seed)
    compared = compare(program, reference)
    say(f"[{cell['name']}] reference: {CHECK_STEPS} steps in float32 at "
        f"'highest' in {time.perf_counter() - t_ref:.1f} s; losses "
        f"{', '.join('%.5f' % v for v in reference['loss'])}; gradient norms "
        f"{', '.join('%.4f' % v for v in reference['grad_norm'])} (program "
        f"{', '.join('%.4f' % v for v in program['grad_norm'])})")
    correct, shown = harness.judge(compared, cell["limits"])
    correct = correct and failed == 0

    metrics = harness.reported(bench, cell, trace, run, {
        "train_tokens_per_s_per_chip": (tokens_per_s_per_chip,
                                        "tokens/s/chip"),
        "setup_s": (setup_s, "s")})
    return dict(correct=correct, attempted=steps, failed=failed,
                metrics=metrics, device=device_out, compared=shown,
                breakdown=breakdown)
