"""The ``train_olmo_hybrid`` and ``train_fsdp`` kinds (PR 38), their
configuration, arithmetic and metric readers on the CPU at a tiny width:
one run of each tiny cell through the kind's own ``run_cell`` (flash
attention off the TPU is the dense tier; the fsdp cell on four of the CPU's
virtual devices), the faults they have to catch, the readers on made-up
traced runs, and the files' own pins: the new lines of BENCHMARK.json held
to the driver's form, the configuration to the catalog's row and to the
program's own count."""

import contextlib
import io
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import arith, arith_olmo_hybrid, harness

from _drive import CPU, DATA

REPO = harness.REPO
BENCH = harness.load_benchmark()
CELL = "train_olmo_hybrid_pp8_fit"
FSDP = "train_malevich_adam_fsdp4"
CONFIG = "olmo_hybrid_pp8"
SEED = 2 ** 31 + 3838
NEW = ("train_olmo_hybrid_mfu_pct", "gdn_chunk_device_pct",
       "gdn_state_device_pct", "gdn_core_roofline")
# accepted readers of every traced fit() step: they would read the Olmo cell,
# but ``test_bench_scopes.py`` pins their lists, and this PR edits no file the
# benchmark had, so appending the cell is left to a ``benchmark`` PR
GENERIC = ("train_step_device_ms", "device_idle_pct.train",
           "step_optimizer_device_pct", "step_remat_device_pct",
           "step_loss_device_pct", "step_unscoped_device_pct",
           "attn_core_device_pct", "attn_proj_device_pct", "ff_device_pct")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def fsdp_entry() -> dict:
    """The BENCHMARK.json entry the held-out fsdp cell's file describes."""
    cell = harness.load_json(os.path.join(harness.HERE, "workloads",
                                          f"{FSDP}.json"))
    return {k: cell[k] for k in ("name", "config", "traffic", "chips", "why")}


def run(name: str, config: str, *, seed: int, seconds: float, fault=None,
        chips: int = 1) -> tuple:
    """One tiny cell of ``tests/benchmarks/data`` through its kind's own
    ``run_cell``: (the result's last line, what was printed before it)."""
    cell = harness.load_json(os.path.join(DATA, f"{name}.json"))
    cfg = harness.load_json(os.path.join(DATA, f"{config}.json"))
    os.makedirs(harness.WORK, exist_ok=True)
    kind = harness.load_kind(cell["kind"])
    if fault is not None:
        sound = kind._run.make_trainer

        def broken(cell, cfg):
            model_cfg, trainer = sound(cell, cfg)
            fault(trainer)
            return model_cfg, trainer
        kind._run.make_trainer = broken
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = kind.run_cell(
            cell, cfg, seed=seed, seconds=seconds, trace=False,
            t_start=time.perf_counter(), device=dict(CPU, count=chips),
            ledger=harness.CompileLedger(), bench=BENCH)
        harness.finish(**result)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


def state_left_unchanged(trainer):
    real = trainer.step_fn

    def step(state, text, ids, key):
        kept = jax.tree.map(jnp.copy, state)     # the real step donates
        _, metrics = real(state, text, ids, key)
        return kept, metrics
    trainer.step_fn = step


# -- one run of each tiny cell ----------------------------------------------------

def test_a_sound_olmo_run_is_correct_and_prints_its_counters():
    line, earlier = run("tiny_train_olmo_hybrid", "tiny_olmo_hybrid_config",
                        seed=SEED, seconds=1.0)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap",
                                     "leaf_grad_gap", "leaf_change_gap"}
    text = "\n".join(earlier)
    assert "compiles inside the window: 0" in text
    found = re.search(r"gdn_logdecay_min .* median (-[\d.]+), least "
                      r"(-[\d.]+); gdn_beta_over_one_pct .* median ([\d.]+)",
                      text)
    assert found, text
    assert float(found[2]) <= float(found[1]) < 0
    assert 0 < float(found[3]) < 100


def test_an_olmo_state_left_unchanged_is_not_correct():
    line, _ = run("tiny_train_olmo_hybrid", "tiny_olmo_hybrid_config",
                  seed=SEED + 1, seconds=0.5, fault=state_left_unchanged)
    assert line["correct"] is False
    value, limit = line["compared"]["leaf_change_gap"]
    assert value > limit


def test_a_sound_fsdp_run_over_four_devices_is_correct():
    line, earlier = run("tiny_train_fsdp", "tiny_config", seed=SEED,
                        seconds=1.0, chips=4)
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["count"] == 4
    for value, limit in line["compared"].values():
        assert 0 <= value <= limit


def test_the_fsdp_reference_shards_its_state_over_the_cells_mesh():
    """Parameters, gradients and Adam's moments along their largest axis
    that the fsdp axis divides (a layer's leaf never along the axis of
    layers); the batch's rows over the mesh. Made on four CPU devices."""
    from benchmarks.reference import dalle
    kind = harness.load_kind("train_fsdp")
    cell = harness.load_json(os.path.join(DATA, "tiny_train_fsdp.json"))
    cfg = harness.load_json(os.path.join(DATA, "tiny_config.json"))
    kind._on["mesh"] = kind._mesh(cell)
    s = dalle.Shapes.from_model(cfg["model"])
    init, _, _ = kind._programs(s, "adam", 3e-4, 0.5, "f32")
    params = init(dalle.seed_key(SEED))
    assert params["w_logits"].sharding.spec == jax.sharding.PartitionSpec(
        None, "fsdp")
    assert params["layers"]["w1"].sharding.spec[0] is None
    assert "fsdp" in params["layers"]["w1"].sharding.spec
    assert len(params["w_logits"].sharding.device_set) == 4
    moments = kind._ref.OPTIMIZERS["adam"][0](params)
    assert moments["mu"]["w_logits"].sharding == params["w_logits"].sharding


# -- the configuration against the catalog and the program --------------------------

def catalog_entry() -> dict:
    if not os.path.exists(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        return next(json.loads(l) for l in f
                    if json.loads(l)["name"] == "Olmo-Hybrid-7B")


def the_config() -> dict:
    return harness.load_cell(CELL, BENCH)[1]


def test_the_configuration_holds_every_published_width_unchanged():
    cfg, catalog = the_config(), catalog_entry()
    assert cfg["published"]["config_json"] == catalog["config"]
    assert cfg["source"] == catalog["source_url"]
    for key, value in catalog["config"].items():
        assert cfg[key] == value, key
    model, block, pub = cfg["model"], cfg["model"]["block"], catalog["config"]
    assert model["dim"] == pub["hidden_size"] == 3840
    assert model["heads"] == pub["num_attention_heads"] == 30
    assert block["num_key_value_heads"] == pub["num_key_value_heads"]
    assert block["linear_num_heads"] == pub["linear_num_key_heads"]
    assert block["linear_key_head_dim"] == pub["linear_key_head_dim"]
    assert block["linear_value_head_dim"] == pub["linear_value_head_dim"]
    assert block["short_conv_kernel_size"] == pub["linear_conv_kernel_dim"]
    assert block["intermediate_size"] == pub["intermediate_size"]
    assert block["rms_norm_eps"] == pub["rms_norm_eps"]
    assert block["kda_beta_max"] == 2.0 and pub["linear_allow_neg_eigval"]
    period = ["gdn" if t == "linear_attention" else "gqa"
              for t in pub["layer_types"][:4]]
    assert block["attention_layers"] == period == ["gdn", "gdn", "gdn", "gqa"]
    assert block["norm_position"] == "post" and block["qk_norm"]
    assert block["positions"] == "none"
    assert pub["rope_parameters"]["rope_theta"] is None


def test_the_cut_is_depth_and_the_tables_and_says_why():
    cfg = the_config()
    entry = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert cfg["reduced"] == entry["reduced"] == ["depth", "num_text_tokens"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    model = cfg["model"]
    assert model["depth"] == 4 and cfg["num_hidden_layers"] == 32
    rows = (model["num_text_tokens"] + model["text_seq_len"]
            + model["image_vocab_size"])
    assert rows * 8 == cfg["vocab_size"] == cfg["arithmetic"]["table_rows"] * 8


def test_the_held_count_is_the_files_and_the_programs():
    """928,874,740: ``arith_olmo_hybrid``, the file's arithmetic, and the
    tree the program builds (its shapes, at the real size, with no array
    made)."""
    from dalle_tpu.config import DalleConfig
    from dalle_tpu.models.dalle import DALLE
    cfg = the_config()
    count = arith_olmo_hybrid.held_param_count(cfg["model"])
    assert count == cfg["arithmetic"]["parameters_held"] == 928_874_740
    a = cfg["arithmetic"]
    assert count == (3 * a["gdn_layer"] + a["gqa_layer"]
                     + 2 * a["table_rows"] * 3840 + 3840 + a["table_rows"])
    model = DALLE(DalleConfig(**cfg["model"]))
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 256), jnp.int32),
        jnp.zeros((1, 4096), jnp.int32)))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == count


def test_the_arithmetic_by_hand():
    model = the_config()["model"]
    # 6 x the product parameters a token touches + 3 x the cores' own
    flops = arith_olmo_hybrid.train_flops_per_token(model)
    assert 5.2e9 < flops < 5.5e9
    cost = arith_olmo_hybrid.gdn_core_cost(model, 2)
    pairs = 2 * 4352 * 30
    assert cost["bytes"] == pairs * (3 * (2 * (2 * 96 + 192 + 1) + 4)
                                     + 2 * 2 * 192)
    least, bound = arith.least_seconds(cost, arith.peaks_for("TPU v5 lite"))
    assert bound == "bandwidth" and 0.9e-3 < least < 1.1e-3
    assert arith_olmo_hybrid.linear_layers(model) == 3


# -- the readers ------------------------------------------------------------

def a_traced_run(layers: dict, steps: int = 4, busy: float = 2.0) -> dict:
    """A run whose scope split is given (``scope_time``'s kept split)."""
    split = {"layers": layers, "phases": {}, "cells": {}, "matched_s": busy,
             "inherited_s": 0.0, "busy_s": busy, "unseen": [],
             "unscoped": []}
    return {"cell": {"name": CELL, "traffic": {"batch": 2}},
            "config": the_config(), "device": {"kind": "TPU v5 lite"},
            "trace": {"busy_s": busy, "ops": {}}, "records": [],
            "traced": {"steps": steps,
                       "untraced_tokens_per_s_per_chip": 10_000.0},
            "window": {"tokens_per_s_per_chip": 9_000.0},
            "scope_time": split}


def test_the_readers_read_their_scopes():
    run = a_traced_run({"gdn_chunk": 0.3, "gdn_state": 0.1, "ff": 1.6})
    got = harness.read_metrics(NEW, run)
    assert got["gdn_chunk_device_pct"]["value"] == pytest.approx(15.0)
    assert got["gdn_state_device_pct"]["value"] == pytest.approx(5.0)
    model = run["config"]["model"]
    least = arith.least_seconds(arith_olmo_hybrid.gdn_core_cost(model, 2),
                                arith.peaks_for("TPU v5 lite"))[0]
    assert got["gdn_core_roofline"]["value"] == pytest.approx(
        100 * least * 3 * 4 / 0.4)
    assert got["train_olmo_hybrid_mfu_pct"]["value"] == pytest.approx(
        arith.mfu_pct(arith_olmo_hybrid.train_flops_per_token(model),
                      10_000.0, 197e12))


def test_the_new_readers_return_none_never_zero_without_their_scopes():
    """A run without a trace, and a traced run of a program without the
    per-head form (the parent's): nothing to read, so nothing is printed."""
    bare = {"trace": None, "traced": None, "records": [], "cell": {
        "name": CELL, "traffic": {"batch": 2}}, "config": {"model": {
            "block": {}}}, "window": {"tokens_per_s_per_chip": 1.0},
        "device": {"kind": "TPU v5 lite"}}
    assert harness.read_metrics(NEW, bare) == {}
    other = a_traced_run({"kda_chunk": 0.5, "kda_state": 0.1, "ff": 1.4})
    assert harness.read_metrics(NEW[1:], other) == {}


# -- the files' own pins ------------------------------------------------------

def test_the_new_lines_of_the_benchmark_fit_the_drivers_form():
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for metric in NEW:
        m = by_name[metric]
        assert m["workloads"] == [CELL]
        assert m["moves"] == "train_tokens_per_s_per_chip"
        assert m["unit"] == "%" and name.fullmatch(metric)
        assert os.path.exists(os.path.join(REPO, "benchmarks", "metrics",
                                           f"{metric}.py"))
    assert by_name["gdn_core_roofline"]["better"] == "higher"
    assert {by_name[n]["layer"] for n in NEW[1:]} == {"linear attention"}
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0])
    assert tuple(names[at:at + len(NEW)]) == NEW
    cells = {w["name"]: w for w in BENCH["workloads"]}
    assert cells[CELL] == {"name": CELL, "config": CONFIG,
                           "traffic": "fit_b2_t4352", "chips": 1,
                           "why": cells[CELL]["why"]}
    assert [w["name"] for w in BENCH["workloads"]][-1] == CELL
    # the fsdp cell is held out until its limits have readings of their own
    # (PERF.md section 7): its files are there, its entry and lists are not
    assert FSDP not in cells and not any(
        FSDP in m.get("workloads", ()) for m in BENCH["per_layer"])
    entry = fsdp_entry()
    assert entry["chips"] == 4 and entry["traffic"] == "fit_b16"
    for why in (cells[CELL]["why"], entry["why"]):
        assert len(why) <= 200 and why.isprintable()
    # what the Olmo cell is reported by: its own four; every accepted metric
    # has a list of cells, and none of them names it (GENERIC's comment)
    assert all("workloads" in m for m in BENCH["per_layer"])
    listed = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
    assert listed == set(NEW) and not listed & set(GENERIC)


def test_the_flash_rooflines_cannot_read_the_olmo_stack():
    """Why ``flash_attn_fwd_roofline`` and ``flash_attn_bwd_roofline`` do not
    list the cell though it runs the flash kernels: their arithmetic
    (``arith_hybrid``) sizes a Kimi-delta stack's leaves and counts
    ``gqa_gated`` layers alone: on this stack it raises, and a reader that
    raises refuses the run. Repairing it is an edit to an accepted file."""
    from benchmarks import arith_hybrid
    _, cfg = harness.load_cell(CELL, BENCH)
    for reckon in (lambda m: arith_hybrid.flash_attention_cost(
            m, 2, backward=False), arith_hybrid.softmax_layers):
        with pytest.raises(KeyError, match="linear_head_dim"):
            reckon(cfg["model"])


def test_the_new_cells_files_parse_and_name_what_their_kinds_compare():
    from benchmarks.kinds import train
    held_out = dict(BENCH, workloads=BENCH["workloads"] + [fsdp_entry()])
    for cell_name in (CELL, FSDP):
        cell, cfg = harness.load_cell(cell_name, held_out)
        kind = harness.load_kind(cell["kind"])
        assert hasattr(kind, "run_cell") and hasattr(kind, "calibrate")
        compared = train.compare(
            {"loss": [1.0] * 3, "grad_norm": [1.0] * 3,
             "leaf_grad_norms": {"a": 1.0}, "leaf_change_norms": {"a": 1.0}},
            {"loss": [1.0] * 3, "grad_norm": [1.0] * 3,
             "leaf_grad_norms": {"a": 1.0}, "leaf_change_norms": {"a": 1.0}})
        assert set(cell["limits"]) <= set(compared)
    cell, _ = harness.load_cell(CELL, BENCH)
    assert cell["mosaic_calls"] == 4 and cell["traffic"]["batch"] == 2
    cell, cfg = harness.load_cell(FSDP, held_out)
    assert cell["mesh"] == {"fsdp": 4} and cell["recipe"]["optimizer"] == "adam"
    assert cell["traffic"]["batch"] == 16 and cfg["model"]["depth"] == 20
