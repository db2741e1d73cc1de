"""The ``train_hybrid`` kind, its configuration, arithmetic and metric
readers on the CPU at a tiny width: one run of a tiny cell through the
kind's own ``run_cell`` (flash attention off the TPU is the dense tier, the
grouped product ``ragged_dot``), the faults it has to catch, the readers on
a made-up traced run, and the files' own pins."""

import contextlib
import io
import json
import os
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import arith, arith_hybrid, harness

from _drive import CPU, DATA

REPO = harness.REPO
BENCH = harness.load_benchmark()
CELL = "train_solar2_ep32_fit"
SEED = 2 ** 31 + 3232
NEW = ("train_hybrid_mfu_pct", "flash_attn_fwd_roofline",
       "flash_attn_bwd_roofline", "kda_state_device_pct")
PR27 = ("train_active_mfu_pct", "moe_gmm_fwd_roofline",
        "moe_gmm_bwd_roofline", "moe_gmm_device_pct",
        "moe_load_max_over_mean")
PR25 = ("fit_dispatch_ms", "fit_after_step_ms",
        "idle_in_fit_dispatch_pct", "idle_in_fit_sync_pct",
        "setup_trainer_init_s", "setup_fit_warmup_s",
        "fused_attn_fwd_roofline", "fused_attn_bwd_roofline")
TARGET = 'custom_call_target="tpu_custom_call"'


def tiny() -> tuple:
    return (harness.load_json(os.path.join(DATA, "tiny_train_hybrid.json")),
            harness.load_json(os.path.join(DATA, "tiny_solar2_config.json")))


def run(*, seed: int, seconds: float, fault=None) -> tuple:
    """``_drive.run`` for the tiny train_hybrid cell and its configuration."""
    cell, cfg = tiny()
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
            t_start=time.perf_counter(), device=dict(CPU),
            ledger=harness.CompileLedger(), bench=BENCH)
        harness.finish(**result)
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


# -- one run of a tiny cell ----------------------------------------------------

def test_a_sound_run_is_correct_and_prints_its_counters():
    line, earlier = run(seed=SEED, seconds=1.0)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap",
                                     "leaf_change_gap"}
    text = "\n".join(earlier)
    assert "compiles inside the window: 0" in text
    assert "moe_rows_dropped over the whole run: 0 (must be 0)" in text
    assert "block gqa_gated/kda+moe" in text
    assert "rows routed to the held experts" in text
    assert "kda_logdecay_min over the run's records" in text
    # the copy of ``kinds/train_moe.py`` read this stack's arithmetic after
    # the kind put it in DeepSeek-V2's place: the count it printed
    held = arith_hybrid.held_param_count(tiny()[1]["model"])
    assert f"(benchmark's count {held})" in text


def test_the_kind_puts_its_parts_under_names_train_moe_reads():
    """``kinds/train_hybrid.py`` gives its own copy of ``kinds/train_moe.py``
    this stack's reference, leaves, weights, arithmetic, counters and
    records writer by overwriting module globals: each of those names has to
    exist in ``train_moe`` and be looked up when its functions run (a rename
    there would leave DeepSeek-V2's part in place without a word). The
    accepted kind's own module stays as it was."""
    import ast
    import inspect
    from benchmarks import (adapter_deepseek_v2, adapter_solar_open2,
                            arith_moe)
    from benchmarks.kinds import train_moe
    from benchmarks.reference import deepseek_v2, solar_open2
    kind = harness.load_kind("train_hybrid")
    put = {"ref": solar_open2, "make_weights": adapter_solar_open2.make_weights,
           "named_leaves": adapter_solar_open2.named_leaves,
           "arith_moe": arith_hybrid, "_Records": kind._Records,
           "COUNTERS": train_moe.COUNTERS + ("kda_logdecay_min",)}
    was = {"ref": deepseek_v2, "make_weights": adapter_deepseek_v2.make_weights,
           "named_leaves": adapter_deepseek_v2.named_leaves,
           "arith_moe": arith_moe}
    assert kind._run is not train_moe
    tree = ast.parse(inspect.getsource(train_moe))
    inside = {n.id for f in ast.walk(tree)
              if isinstance(f, (ast.FunctionDef, ast.Lambda))
              for n in ast.walk(f) if isinstance(n, ast.Name)}
    bound_early = {n.id for f in ast.walk(tree)
                   if isinstance(f, ast.FunctionDef)
                   for d in f.args.defaults + f.args.kw_defaults if d
                   for n in ast.walk(d) if isinstance(n, ast.Name)}
    for name, part in put.items():
        assert hasattr(train_moe, name), name
        assert getattr(kind._run, name) == part or \
            getattr(kind._run, name) is part, name
        assert name in inside and name not in bound_early, name
    for name, part in was.items():
        assert getattr(train_moe, name) is part, name
    assert train_moe._Records is not kind._Records
    assert issubclass(kind._Records, train_moe._Records)


def state_left_unchanged(trainer):
    real = trainer.step_fn

    def step(state, text, ids, key):
        kept = jax.tree.map(jnp.copy, state)     # the real step donates
        _, metrics = real(state, text, ids, key)
        return kept, metrics
    trainer.step_fn = step


def test_a_state_left_unchanged_under_the_timed_path_is_not_correct():
    line, _ = run(seed=SEED + 1, seconds=0.5, fault=state_left_unchanged)
    assert line["correct"] is False
    value, limit = line["compared"]["leaf_change_gap"]
    assert value > limit


def test_a_program_without_the_kinds_is_refused_at_once_by_name(monkeypatch):
    """The new files on the commit before this PR: ``config.BlockConfig``
    there knows ``mha | mla``, no pattern and no ``positions: none``."""
    from dalle_tpu.config import BlockConfig
    kind = harness.load_kind("train_hybrid")
    cell, cfg = harness.load_cell(CELL, BENCH)
    kind.refuse_unknown_kinds(cfg)               # this program: nothing
    monkeypatch.setattr(BlockConfig, "KINDS", {
        **BlockConfig.KINDS, "attention": ("mha", "mla"),
        "positions": ("dalle_axial", "seq_yarn")})
    with pytest.raises(SystemExit, match=r"no block kind \['gqa_gated', "
                                         r"'kda', 'positions: none'\]"):
        kind.run_cell(cell, cfg, seed=1, seconds=1.0, trace=False,
                      t_start=time.perf_counter(), device=dict(CPU),
                      ledger=None, bench=BENCH)
    with pytest.raises(SystemExit, match="no block kind"):
        kind.calibrate(cell, cfg, seeds=[1], control_seeds=[])


# -- the control -----------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 5, 900000007])
def test_the_control_and_the_fault_fail_the_cells_limits(seed):
    """At a test size: the reference put in the program's place and computed
    in fp8 comes out not correct under the cell's own limits, and so does
    the reference with half of the batch left out; in the stated bfloat16 it
    passes."""
    from benchmarks.kinds import train
    kind = harness.load_kind("train_hybrid")
    cfg = tiny()[1]
    cell = {"recipe": {"optimizer": "adafactor", "learning_rate": 3e-4,
                       "grad_clip_norm": 0.5},
            "traffic": {"batch": 4, "text_tokens": [2, 8]}}
    limits = harness.load_cell(CELL, BENCH)[0]["limits"]
    sound = kind._run.reference_numbers(cell, cfg, seed)

    def verdict(only=None, **kw):
        held_to = {k: v for k, v in limits.items() if only in (None, k)}
        return harness.judge(train.compare(
            kind._run.reference_numbers(cell, cfg, seed, **kw), sound),
            held_to)[0]
    assert set(limits) == {"loss_gap", "grad_norm_gap", "leaf_grad_gap",
                           "leaf_change_gap"}
    assert verdict(precision="bf16") is True
    assert verdict(precision="fp8") is False
    assert verdict(rows=slice(0, 2)) is False


# -- the arithmetic --------------------------------------------------------------

def test_the_benchmarks_count_is_the_programs_and_the_files():
    cfg = harness.load_cell(CELL, BENCH)[1]
    model = cfg["model"]
    assert arith_hybrid.held_param_count(model) == 1420939840     # 1.421B
    assert cfg["arithmetic"]["parameters_held"] == 1420939840
    a = cfg["arithmetic"]
    assert (3 * a["kda_layer"] + a["gqa_gated_layer"]
            + 4 * a["router_shared_expert_norms_a_layer"]
            + 40 * a["routed_expert"] + 2 * a["table_rows"] * 4096
            + 4096 + a["table_rows"]) == a["parameters_held"]
    from dalle_tpu.config import DalleConfig
    from dalle_tpu.models.dalle import DALLE
    for m in (model, tiny()[1]["model"]):
        c = DalleConfig(**m)
        shapes = jax.eval_shape(
            lambda k, c=c: DALLE(c).init(
                {"params": k, "cfg": k},
                jnp.zeros((1, c.text_seq_len), jnp.int32),
                jnp.zeros((1, c.image_seq_len), jnp.int32), return_loss=True),
            jax.random.PRNGKey(0))
        assert sum(x.size for x in jax.tree.leaves(shapes)) == \
            arith_hybrid.held_param_count(m)


def test_flops_a_token_and_the_flash_kernels_cost_by_hand():
    model = harness.load_cell(CELL, BENCH)[1]["model"]
    d, inner = 4096, 8192
    kda = 3 * d * inner + 2 * (d * 128 + 128 * inner) + d * 64 + inner * d
    gqa = d * (inner + 2 * 1024 + inner) + inner * d
    columns = (256 * 16384 + 4096 * 8192) / 4352
    assert arith_hybrid.head_columns_per_token(model) == columns
    products = (3 * kda + gqa + 4 * (d * 320 + 3 * d * 1280)
                + 1.0 * 3 * d * 1280 + d * columns)
    assert arith_hybrid.product_params_per_token(model, 1.0) == products
    rule = (2 * 128 * 64 + 64 * 64 / 3 + 64 * 256 + 6 * 128 * 128 + 64 * 128)
    assert arith_hybrid.delta_rule_flops_per_token(128, 128) == rule
    assert arith_hybrid.train_flops_per_token(model, 1.0) == (
        6.0 * products + 3.0 * (2.0 * 64 * 128 * 4352 + 3 * 64 * rule))
    # about 4.1 GFLOP a token without the recompute
    assert 4.0e9 < arith_hybrid.train_flops_per_token(model, 1.0) < 4.3e9
    fwd = arith_hybrid.flash_attention_cost(model, 2, backward=False)
    whole = arith.causal_attention_cost(2, 64, 4352, 128, backward=False)
    assert fwd["flops"] == whole["flops"]
    assert fwd["bytes"] == 2.0 * 2 * 4352 * 128 * (2 * 64 + 2 * 8)
    bwd = arith_hybrid.flash_attention_cost(model, 2, backward=True)
    assert bwd["flops"] == 2.5 * fwd["flops"] and bwd["bytes"] == 2 * fwd["bytes"]
    peaks = arith.peaks_for("TPU v5 lite")
    assert arith.least_seconds(fwd, peaks)[1] == "compute"     # by 3.15 ms
    assert arith.least_seconds(fwd, peaks)[0] == pytest.approx(3.15e-3,
                                                               rel=0.01)
    assert arith_hybrid.softmax_layers(model) == 1


# -- the readers ------------------------------------------------------------------

def a_run(**kw):
    cell, cfg = harness.load_cell(CELL, BENCH)
    run = {"cell": cell, "config": cfg, "device": {"kind": "TPU v5 lite"},
           "records": [], "window": {"seconds": 30.0, "steps": 0,
                                     "tokens_per_s_per_chip": 15000.0},
           "trace": None, "traced": None}
    run.update(kw)
    return run


def kernel(name, out="bf16[2,64,4352,128]"):
    return f"%{name} = {out} custom-call(bf16[2,64,4352,128] %a), {TARGET}"


STATE_LOOP = ("%while.{n} = (s32[], f32[2,64,128,128]{{3,2,1,0}}, "
              "bf16[68,2,64,64,128]{{4,3,2,1,0}}) while(%tuple.{n}), "
              "condition=%cond, body=%body")
OTHER_LOOP = ("%while.9 = (s32[], bf16[17,4,2,64,64,128]{5,4,3,2,1,0}) "
              "while(%tuple.9), condition=%cond, body=%body")


def test_the_new_readers_with_nothing_to_read_return_nothing():
    """The parent commit has neither the layers nor the counters, and the
    other configurations no ``attention_layers``."""
    for name in NEW:
        assert harness.read_metrics([name], a_run()) == {}, name
    traced = a_run(trace={"window_s": 5.0, "busy_s": 4.5, "ops": {
        kernel("fused_attn_fwd.4"): 1.0,
        "%fusion.1 = bf16[8] fusion(%flash_attn_fwd.1)": 2.0},
        "events": [(OTHER_LOOP, 0, 10 ** 9)], "idle_gaps": []},
        traced={"steps": 10, "from_step": 5,
                "untraced_tokens_per_s_per_chip": 1.0},
        records=[(6, 0.0, {"loss": 1.0, "t_batch_wait_s": 0.001})])
    for name in NEW:
        assert harness.read_metrics([name], traced) == {}, name
    # a dense cell's run, whose configuration has no pattern of kinds
    dense = harness.load_cell("train_small_b64", BENCH)
    other = a_run(cell=dense[0], config=dense[1], trace=traced["trace"],
                  traced=traced["traced"],
                  records=[(6, 0.0, {"loss": 1.0, "moe_rows_held": 5.0})])
    for name in NEW:
        assert harness.read_metrics([name], other) == {}, name


def test_the_new_readers_on_a_made_up_traced_run():
    records = [(s, float(s), {"loss": 1.0, "moe_rows_held": 8704.0,
                              "kda_logdecay_min": -100.0})
               for s in range(40, 51)]
    model = harness.load_cell(CELL, BENCH)[1]["model"]
    peaks = arith.peaks_for("TPU v5 lite")
    least = {b: arith.least_seconds(arith_hybrid.flash_attention_cost(
        model, 2, backward=b), peaks)[0] for b in (False, True)}
    # 10 traced steps x 1 softmax layer; the forward runs twice (remat)
    ops = {kernel("flash_attn_fwd.3"): 2 * 2 * 10 * least[False],
           kernel("flash_attn_dq.7"): 2 * 10 * least[True],
           kernel("flash_attn_dkv"): 2 * 10 * least[True],
           "%fusion.2 = bf16[8] fusion(%x)": 1.0}
    busy = sum(ops.values()) + 0.5
    events = [(STATE_LOOP.format(n=1), 0, 2 * 10 ** 8),
              (STATE_LOOP.format(n=2), 10 ** 8, 3 * 10 ** 8),    # overlapping
              ("%fusion.5 = f32[2,64,128,128] fusion(%y)", 0, 10 ** 8),
              (OTHER_LOOP, 4 * 10 ** 8, 9 * 10 ** 8)]
    run = a_run(records=records,
                trace={"window_s": 1.1 * busy, "busy_s": busy, "ops": ops,
                       "events": events, "idle_gaps": []},
                traced={"steps": 10, "from_step": 40,
                        "untraced_tokens_per_s_per_chip": 15000.0})
    got = harness.read_metrics(NEW, run)
    assert got["flash_attn_fwd_roofline"]["value"] == pytest.approx(25.0)
    assert got["flash_attn_bwd_roofline"]["value"] == pytest.approx(25.0)
    assert got["kda_state_device_pct"]["value"] == pytest.approx(
        100.0 * 0.3 / busy)
    assert got["train_hybrid_mfu_pct"]["value"] == pytest.approx(
        100.0 * arith_hybrid.train_flops_per_token(model, 1.0) * 15000.0
        / 197e12)
    # Pallas kernels for the recurrence, where a later PR writes them, are
    # read by their names before any loop is looked for
    run["trace"]["ops"][kernel("kda_state_fwd.1")] = 0.25
    assert harness.read_metrics(["kda_state_device_pct"], run)[
        "kda_state_device_pct"]["value"] == pytest.approx(100.0 * 0.25 / busy)
    # the accepted readers that a traced run of this kind prints in its log
    kind = harness.load_kind("train_hybrid")
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert set(kind._run.FIT_READERS) <= listed - set(NEW)


# -- the files --------------------------------------------------------------------

def test_the_configuration_holds_every_published_width_unchanged():
    cfg = harness.load_cell(CELL, BENCH)[1]
    published = cfg["published"]["config_json"]
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        catalog = next(json.loads(l) for l in f
                       if json.loads(l)["name"] == "Solar-Open2-250B")
    assert published == catalog["config"]
    assert cfg["source"] == catalog["source_url"]
    assert {k: cfg[k] for k in published} == published   # top level, verbatim
    model, block = cfg["model"], cfg["model"]["block"]
    for key in ("moe_intermediate_size", "n_routed_experts",
                "n_shared_experts", "num_experts_per_tok", "rms_norm_eps",
                "num_key_value_heads", "norm_topk_prob",
                "routed_scaling_factor"):
        assert block[key] == published[key], key
    linear = published["linear_attn_config"]
    assert block["linear_num_heads"] == linear["num_heads"]
    assert block["linear_head_dim"] == linear["head_dim"]
    assert block["short_conv_kernel_size"] == linear["short_conv_kernel_size"]
    assert model["dim"] == published["hidden_size"]
    assert model["heads"] == published["num_attention_heads"]
    assert model["dim_head"] == published["head_dim"]
    assert block["first_dense_layers"] == published["first_k_dense_replace"]
    assert published["use_rope"] is False and block["positions"] == "none"
    # the pattern is the source's: gqa_layers 0, 4, 8, ... of 48
    period = block["attention_layers"]
    assert [i for i in range(published["num_hidden_layers"])
            if period[i % len(period)] == "gqa_gated"] == \
        published["gqa_layers"]
    assert len(period) == published["gqa_interval"] + 1
    # the cut: one whole period, a thirty-second of the experts, an eighth
    # of the rows; every assumed size is written down
    assert cfg["reduced"] == ["depth", "experts_held", "num_text_tokens"]
    assert cfg["chips_sharing_a_layer"] == 32
    assert model["depth"] == len(period) >= 4
    assert model["experts_held"] * 32 == published["n_routed_experts"]
    assert model["experts_held"] >= 8
    rows = (model["num_text_tokens"] + model["text_seq_len"]
            + model["image_vocab_size"])
    assert rows * 8 == published["vocab_size"]
    assert {"gqa_gate", "linear_gate_rank", "decay", "router",
            "framing"} <= set(cfg["assumed"])
    assert model["text_seq_len"] + model["image_fmap_size"] ** 2 == 4352


def test_every_line_of_text_in_the_benchmark_fits_the_drivers_form():
    """The driver refuses BENCHMARK.json before any run where a ``why``, a
    ``layer`` or a ``source`` is not 1 to 200 printable characters on one
    line (PR 32's first ``why`` of its configuration had 223), where an entry
    has another key than its kind's, or where a name has a character outside
    the form's."""
    import re
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, allowed in keys.items():
        for entry in BENCH[section]:
            assert set(entry) - {"workloads"} == allowed, entry["name"]
            assert name.fullmatch(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                text = entry.get(key)
                if text is not None:
                    assert 1 <= len(text) <= 200 and text.isprintable(), (
                        entry["name"], key, len(text))
    for config in BENCH["configs"]:
        assert len(config["reduced"]) <= 16
        assert all(name.fullmatch(k) for k in config["reduced"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10


def test_the_entries_are_new_and_sit_at_the_end_of_their_lists():
    """The driver takes an entry only at the end of its list. PR 27's pins
    of its own entries as the last (``test_bench_moe.py``) cannot hold any
    more and are expected failures (``tests/conftest.py``); what else
    ``test_bench_moe.py::test_the_entries_are_new_and_sit_at_the_end_of_their_lists``
    asserts is asserted here by name (the other of the two: the next test)."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert tuple(names[-len(NEW):]) == NEW
    at = names.index(PR27[0])
    assert tuple(names[at:at + len(PR27)]) == PR27
    assert at + len(PR27) == len(names) - len(NEW)
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for name in NEW + PR27:
        cell = CELL if name in NEW else "train_dsv2_share16_fit"
        assert by_name[name]["workloads"] == [cell]
        assert by_name[name]["moves"] == "train_tokens_per_s_per_chip"
    assert by_name["kda_state_device_pct"]["better"] == "lower"
    assert by_name["kda_state_device_pct"]["layer"] == "linear attention"
    assert {by_name[n]["layer"] for n in NEW[:3]} == {"model, whole step",
                                                      "attention tiers"}
    assert [w["name"] for w in BENCH["workloads"]][-2:] == [
        "train_dsv2_share16_fit", CELL]
    assert BENCH["workloads"][-1] == {
        "name": CELL, "config": "solar_open2_ep32", "traffic": "fit_b2_t4352",
        "chips": 1, "why": BENCH["workloads"][-1]["why"]}
    assert [c["name"] for c in BENCH["configs"]][-2:] == [
        "deepseek_v2_share16", "solar_open2_ep32"]
    assert all(CELL not in m["workloads"] for m in BENCH["per_layer"]
               if m["name"] not in NEW)
    # PR 27's cell, as its own test pins it but for its place in the list
    dsv2 = {w["name"]: w for w in BENCH["workloads"]}["train_dsv2_share16_fit"]
    assert dsv2["chips"] == 1
    assert all("train_dsv2_share16_fit" not in m["workloads"]
               for m in BENCH["per_layer"] if m["name"] not in PR27)
    cell = harness.load_cell(CELL, BENCH)[0]
    assert cell["traffic"]["batch"] == 2
    assert cell["traffic"]["text_tokens"] == [8, 64]
    assert cell["recipe"] == harness.load_cell(
        "train_dsv2_share16_fit", BENCH)[0]["recipe"]


def test_pr25s_entries_are_listed_as_their_test_pins_them():
    """What ``test_bench_program_names.py`` asserts of PR 25's eight entries
    and ``test_bench_moe.py``'s test of this name re-homed (both are expected
    failures by now, for their asserts of position alone): the eight by name
    and in their order, PR 27's five behind them, this PR's four behind
    those, and each of the eight's cells, source, moved metric, better side
    and layer."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(PR25[0])
    assert tuple(names[at:at + len(PR25)]) == PR25
    assert tuple(names[at + len(PR25):]) == PR27 + NEW
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    both = ["train_malevich_b4", "train_small_b64"]
    for name in PR25:
        m = by_name[name]
        fused = name.startswith("fused_attn")
        assert m["workloads"] == (["train_small_b64"] if fused else both)
        assert m["source"] == ("device_trace" if fused else "program_span")
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "train_tokens_per_s_per_chip")
        assert m["better"] == ("higher" if fused else "lower")
    assert {by_name[n]["layer"] for n in PR25} == {
        "trainer loop", "trainer construction", "attention tiers"}


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "reference",
                           "solar_open2.py")) as f:
        text = f.read()
    assert "dalle_tpu" not in text
    imports = [l for l in text.splitlines()
               if l.startswith(("import ", "from "))]
    assert [l for l in imports if "benchmarks" in l] == [
        "from benchmarks.reference.dalle import (LOSS_IMG_WEIGHT, _quantize,",
        "from benchmarks.reference.deepseek_v2 import (FLAT_OPTIMIZERS, "
        "by_batch_row,"]
    # linear attention one position at a time: a scan over positions whose
    # body is the definition, and no chunked algebra
    assert "def position(state, x):" in text
    assert "tril" not in text and "cumsum" not in text
