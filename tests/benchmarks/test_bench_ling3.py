"""The ``train_hybrid_mtp`` kind, its configuration, arithmetic and metric
readers on the CPU at a tiny width: one run of a tiny cell through the
kind's own ``run_cell`` (flash attention off the TPU is the dense tier, the
grouped product ``ragged_dot``), the faults it has to catch, the readers on
a made-up traced run, and the files' own pins: every new line of
BENCHMARK.json held to the driver's form, and the entries of every PR so far
pinned by name and in order (never as "the last")."""

import contextlib
import io
import json
import os
import re
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import arith, arith_ling3, harness

from _drive import CPU, DATA

REPO = harness.REPO
BENCH = harness.load_benchmark()
CELL = "train_ling3_ep32_fit"
CONFIG = "ling3_flash_ep32"
SEED = 2 ** 31 + 3434
NEW = ("train_ling3_mfu_pct", "mla_flash_fwd_roofline",
       "mla_flash_bwd_roofline")
PR32 = ("train_hybrid_mfu_pct", "flash_attn_fwd_roofline",
        "flash_attn_bwd_roofline", "kda_state_device_pct")
PR27 = ("train_active_mfu_pct", "moe_gmm_fwd_roofline",
        "moe_gmm_bwd_roofline", "moe_gmm_device_pct",
        "moe_load_max_over_mean")
PR25 = ("fit_dispatch_ms", "fit_after_step_ms",
        "idle_in_fit_dispatch_pct", "idle_in_fit_sync_pct",
        "setup_trainer_init_s", "setup_fit_warmup_s",
        "fused_attn_fwd_roofline", "fused_attn_bwd_roofline")
TARGET = 'custom_call_target="tpu_custom_call"'
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def tiny() -> tuple:
    return (harness.load_json(os.path.join(DATA, "tiny_train_ling3.json")),
            harness.load_json(os.path.join(DATA, "tiny_ling3_config.json")))


def run(*, seed: int, seconds: float, fault=None) -> tuple:
    """``_drive.run`` for the tiny train_hybrid_mtp cell and its
    configuration."""
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
    assert "block kda/mla+moe" in text
    assert "rows routed to the held experts" in text
    assert "kda_logdecay_min over the run's records" in text
    assert "loss_mtp of the first steps: program" in text
    # three routed layers counted: two of the stack's and the MTP block's
    assert re.search(r"per expert layer \[\d+, \d+, \d+\]\)", text)
    held = arith_ling3.held_param_count(tiny()[1]["model"])
    assert f"(benchmark's count {held})" in text


def test_the_kind_puts_its_parts_under_names_train_moe_reads():
    """``kinds/train_hybrid_mtp.py`` gives its own copy of
    ``kinds/train_moe.py`` this stack's reference, leaves, weights,
    arithmetic, counters, records writer and a reference call that keeps its
    result, by overwriting module globals: each of those names has to exist
    in ``train_moe`` and be looked up when its functions run. The accepted
    kinds' own modules stay as they were."""
    import ast
    import inspect
    from benchmarks import adapter_deepseek_v2, adapter_ling3, arith_moe
    from benchmarks.kinds import train_moe
    from benchmarks.reference import deepseek_v2, ling3
    kind = harness.load_kind("train_hybrid_mtp")
    hybrid = harness.load_kind("train_hybrid")
    put = {"ref": ling3, "make_weights": adapter_ling3.make_weights,
           "named_leaves": adapter_ling3.named_leaves,
           "arith_moe": arith_ling3, "_Records": kind._Records,
           "reference_numbers": kind._kept_reference,
           "COUNTERS": train_moe.COUNTERS + ("kda_logdecay_min", "loss_mtp")}
    was = {"ref": deepseek_v2, "make_weights": adapter_deepseek_v2.make_weights,
           "named_leaves": adapter_deepseek_v2.named_leaves,
           "arith_moe": arith_moe}
    assert kind._run is not train_moe and kind._run is not hybrid._run
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
    assert hybrid._run.COUNTERS == train_moe.COUNTERS + ("kda_logdecay_min",)
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


def test_a_program_without_the_forms_is_refused_at_once_by_name(monkeypatch):
    """The new files on the commit before this PR: ``config.BlockConfig``
    there has no ``topk_method``, ``qk_norm``, ``attention_gate``,
    ``kda_lower_bound`` or ``kda_beta_max`` and ``DalleConfig`` no
    ``mtp_depth``; the run exits non-zero before it builds anything."""
    import dataclasses
    from dalle_tpu import config
    kind = harness.load_kind("train_hybrid_mtp")
    cell, cfg = harness.load_cell(CELL, BENCH)
    kind.refuse_unknown_kinds(cfg)               # this program: nothing

    @dataclasses.dataclass(frozen=True)
    class ParentBlock:
        attention: str = "mha"
        KINDS = {"attention": ("mha", "mla", "gqa_gated", "kda"),
                 "positions": ("dalle_axial", "seq_yarn", "none")}

    @dataclasses.dataclass(frozen=True)
    class ParentDalle:
        dim: int = 512
    monkeypatch.setattr(config, "BlockConfig", ParentBlock)
    monkeypatch.setattr(config, "DalleConfig", ParentDalle)
    with pytest.raises(SystemExit, match=r"the program has no \[.*"
                                         r"'kda_lower_bound'.*'topk_method'.*"
                                         r"'model\.mtp_depth'") as refused:
        kind.run_cell(cell, cfg, seed=1, seconds=1.0, trace=False,
                      t_start=time.perf_counter(), device=dict(CPU),
                      ledger=None, bench=BENCH)
    assert "nothing was measured" in str(refused.value)
    with pytest.raises(SystemExit, match="the program has no"):
        kind.calibrate(cell, cfg, seeds=[1], control_seeds=[])


# -- the control -----------------------------------------------------------------

@pytest.mark.parametrize("seed", [2 ** 31 + 11, 5, 900000007])
def test_the_control_and_the_fault_fail_the_cells_limits(seed):
    """At a test size: the reference put in the program's place and computed
    in fp8 comes out not correct under the cell's own limits, by each of
    them alone, and the reference with half of the batch left out by
    ``grad_norm_gap`` at least. The stated bfloat16 lies several times
    nearer than the control on every compared number; at this width (a
    routing choice that flips moves a sixteenth of an expert's rows) it
    reads several times the cell's limits, which are set from the chip's
    readings at the real width (PERF.md section 4)."""
    from benchmarks.kinds import train
    kind = harness.load_kind("train_hybrid_mtp")
    cfg = tiny()[1]
    cell = {"recipe": {"optimizer": "adafactor", "learning_rate": 3e-4,
                       "grad_clip_norm": 0.5},
            "traffic": {"batch": 4, "text_tokens": [2, 8]}}
    limits = harness.load_cell(CELL, BENCH)[0]["limits"]
    sound = kind._run.reference_numbers(cell, cfg, seed)

    def compared(**kw):
        return train.compare(
            kind._run.reference_numbers(cell, cfg, seed, **kw), sound)
    assert set(limits) == {"loss_gap", "grad_norm_gap", "leaf_grad_gap",
                           "leaf_change_gap"}
    stated, control = compared(precision="bf16"), compared(precision="fp8")
    for name, limit in limits.items():
        assert harness.judge(control, {name: limit})[0] is False, name
        assert stated[name] < control[name] / 4, name
        assert stated[name] <= 8 * limit, name
    fault = compared(rows=slice(0, 2))
    assert harness.judge(fault, limits)[0] is False
    assert fault["grad_norm_gap"] > limits["grad_norm_gap"]


# -- the arithmetic --------------------------------------------------------------

def test_the_benchmarks_count_is_the_programs_and_the_files():
    cfg = harness.load_cell(CELL, BENCH)[1]
    model = cfg["model"]
    assert arith_ling3.held_param_count(model) == 1149626080      # 1.150B
    a = cfg["arithmetic"]
    assert a["parameters_held"] == 1149626080
    routed = a["router_bias_shared_expert_a_layer"] + 16 * a["routed_expert"]
    assert a["mtp_block"] == (5120 * 2560 + 5120 + a["mla_layer"] + routed
                              + 5120 + 2560)
    assert (5 * a["kda_layer"] + a["mla_layer"] + a["dense_mlp"] + 5 * routed
            + 12 * 2560 + 2 * a["table_rows"] * 2560 + 2560 + a["table_rows"]
            + a["mtp_block"]) == a["parameters_held"]
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
            arith_ling3.held_param_count(m)
        # the adapter names every leaf of the program's tree
        from benchmarks import adapter_ling3
        from benchmarks.reference import ling3
        named = adapter_ling3.named_leaves(ling3.Shapes.from_model(m), shapes)
        assert set(named) == set(ling3.leaf_specs(ling3.Shapes.from_model(m)))


def test_flops_a_token_and_the_flash_kernels_cost_by_hand():
    model = harness.load_cell(CELL, BENCH)[1]["model"]
    d, inner = 2560, 4096
    kda = 3 * d * inner + 2 * d * inner + d * 32 + inner * d
    mla = (d * 32 * 192 + d * 576 + 512 * 32 * 256 + d * 32 + inner * d)
    assert kda + 3 * 4 * inner + 32 + inner + 128 == 63049888
    assert mla + 512 + 2 * 192 == 31966080
    main = 256 * 11456 + 4096 * 8192
    ahead = 255 * 11456 + 4096 * 8192
    assert arith_ling3.head_columns_per_token(model) == (main + ahead) / 4352
    shared, expert, router = 3 * d * 768, 3 * d * 768, d * 512
    products = (5 * kda + mla + 3 * d * 6144 + 5 * (router + shared)
                + (2 * d * d + mla + router + shared) * 4351 / 4352
                + 1.5 * expert + d * (main + ahead) / 4352)
    assert arith_ling3.product_params_per_token(model, 1.5) == \
        pytest.approx(products, rel=1e-12)
    rule = (2 * 128 * 64 + 64 * 64 / 3 + 64 * 256 + 6 * 128 * 128 + 64 * 128)
    assert arith_ling3.train_flops_per_token(model, 1.5) == pytest.approx(
        6.0 * products + 3.0 * (2 * 32 * 320 * 4352 + 5 * 32 * rule),
        rel=1e-12)
    # about 3.5 GFLOP a token without the recompute
    assert 3.4e9 < arith_ling3.train_flops_per_token(model, 1.5) < 3.7e9
    fwd = arith_ling3.flash_attention_cost(model, 2, backward=False)
    half = 4352 * 4353 / 2
    assert fwd["flops"] == 2.0 * half * (192 + 128) * 2 * 32
    assert fwd["bytes"] == 2.0 * 2 * 32 * 4352 * (2 * 192 + 2 * 128)
    bwd = arith_ling3.flash_attention_cost(model, 2, backward=True)
    assert bwd["flops"] == 2.0 * half * (3 * 192 + 2 * 128) * 2 * 32
    assert bwd["bytes"] == 2 * fwd["bytes"]
    # at one head width it is the accepted count
    same = {**model, "block": {**model["block"], "qk_nope_head_dim": 64}}
    whole = arith.causal_attention_cost(2, 32, 4352, 128, backward=True)
    assert arith_ling3.flash_attention_cost(same, 2, backward=True) == whole
    peaks = arith.peaks_for("TPU v5 lite")
    assert arith.least_seconds(fwd, peaks)[1] == "compute"
    assert arith.least_seconds(fwd, peaks)[0] == pytest.approx(1.97e-3,
                                                               rel=0.01)
    assert arith_ling3.latent_layers(model) == 2


# -- the readers ------------------------------------------------------------------

def a_run(**kw):
    cell, cfg = harness.load_cell(CELL, BENCH)
    run = {"cell": cell, "config": cfg, "device": {"kind": "TPU v5 lite"},
           "records": [], "window": {"seconds": 30.0, "steps": 0,
                                     "tokens_per_s_per_chip": 9000.0},
           "trace": None, "traced": None}
    run.update(kw)
    return run


def kernel(name, out="bf16[2,32,4352,128]"):
    return f"%{name} = {out} custom-call(bf16[2,32,4352,192] %a), {TARGET}"


def test_the_new_readers_with_nothing_to_read_return_nothing():
    """The parent commit has neither the layers nor the counters, and the
    other configurations neither a latent layer on the flash tier nor a
    multi-token-prediction block: nothing is returned, nothing raised."""
    for name in NEW:
        assert harness.read_metrics([name], a_run()) == {}, name
    traced = a_run(trace={"window_s": 5.0, "busy_s": 4.5, "ops": {
        kernel("fused_attn_fwd.4"): 1.0,
        "%fusion.1 = bf16[8] fusion(%flash_attn_fwd.1)": 2.0},
        "events": [], "idle_gaps": []},
        traced={"steps": 10, "from_step": 5,
                "untraced_tokens_per_s_per_chip": 1.0},
        records=[(6, 0.0, {"loss": 1.0, "t_batch_wait_s": 0.001})])
    for name in NEW:
        assert harness.read_metrics([name], traced) == {}, name
    # the other cells' runs, flash kernels and routed rows and all
    ops = {kernel("flash_attn_fwd.3"): 1.0, kernel("flash_attn_dq.7"): 1.0,
           kernel("flash_attn_dkv"): 1.0}
    for other in ("train_small_b64", "train_dsv2_share16_fit",
                  "train_solar2_ep32_fit"):
        cell, cfg = harness.load_cell(other, BENCH)
        theirs = a_run(cell=cell, config=cfg,
                       trace={**traced["trace"], "ops": ops},
                       traced=traced["traced"],
                       records=[(6, 0.0, {"loss": 1.0,
                                          "moe_rows_held": 5.0})])
        for name in NEW:
            assert harness.read_metrics([name], theirs) == {}, (other, name)


def test_the_new_readers_on_a_made_up_traced_run():
    records = [(s, float(s), {"loss": 1.0, "loss_mtp": 1.0,
                              "moe_rows_held": 13056.0,
                              "kda_logdecay_min": -100.0})
               for s in range(40, 51)]
    model = harness.load_cell(CELL, BENCH)[1]["model"]
    peaks = arith.peaks_for("TPU v5 lite")
    least = {b: arith.least_seconds(arith_ling3.flash_attention_cost(
        model, 2, backward=b), peaks)[0] for b in (False, True)}
    # 10 traced steps x 2 latent layers; the forward runs twice (remat)
    ops = {kernel("flash_attn_fwd.3"): 2 * 2 * 2 * 10 * least[False],
           kernel("flash_attn_dq.7", "bf16[2,32,4352,192]"):
               2 * 2 * 10 * least[True],
           kernel("flash_attn_dkv"): 2 * 2 * 10 * least[True],
           "%fusion.2 = bf16[8] fusion(%x)": 1.0}
    busy = sum(ops.values()) + 0.5
    run = a_run(records=records,
                trace={"window_s": 1.1 * busy, "busy_s": busy, "ops": ops,
                       "events": [], "idle_gaps": []},
                traced={"steps": 10, "from_step": 40,
                        "untraced_tokens_per_s_per_chip": 9000.0})
    got = harness.read_metrics(NEW, run)
    assert got["mla_flash_fwd_roofline"]["value"] == pytest.approx(25.0)
    assert got["mla_flash_bwd_roofline"]["value"] == pytest.approx(25.0)
    # 13,056 pairs of 8,704 tokens: 1.5 a token
    assert got["train_ling3_mfu_pct"]["value"] == pytest.approx(
        100.0 * arith_ling3.train_flops_per_token(model, 1.5) * 9000.0
        / 197e12)
    assert {v["unit"] for v in got.values()} == {"%"}
    # untraced, the rate is the window's
    untraced = a_run(records=records)
    assert harness.read_metrics(["train_ling3_mfu_pct"], untraced)[
        "train_ling3_mfu_pct"]["value"] == got["train_ling3_mfu_pct"]["value"]
    # the accepted readers that a traced run of this kind prints in its log
    kind = harness.load_kind("train_hybrid_mtp")
    listed = {m["name"] for m in BENCH["per_layer"]}
    assert set(kind._run.FIT_READERS) <= listed - set(NEW)


# -- the files --------------------------------------------------------------------

def catalog_entry() -> dict:
    with open(CATALOG) as f:
        return next(json.loads(l) for l in f
                    if json.loads(l)["name"] == "Ling-3.0-flash")


def test_the_configuration_holds_every_published_width_unchanged():
    cfg = harness.load_cell(CELL, BENCH)[1]
    published, catalog = cfg["published"]["config_json"], catalog_entry()
    assert published == catalog["config"]
    assert cfg["source"] == catalog["source_url"]
    assert {k: cfg[k] for k in published} == published   # top level, verbatim
    model, block = cfg["model"], cfg["model"]["block"]
    for key in ("moe_intermediate_size", "num_experts_per_tok", "n_group",
                "topk_group", "rms_norm_eps", "norm_topk_prob",
                "routed_scaling_factor", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "intermediate_size",
                "short_conv_kernel_size", "rope_theta", "scoring_func",
                "topk_method", "kda_lower_bound"):
        assert block[key] == published[key], key
    assert block["n_routed_experts"] == published["num_experts"]
    assert block["n_shared_experts"] == published["num_shared_experts"]
    assert block["linear_num_heads"] == published["num_attention_heads"]
    assert block["linear_head_dim"] == published["head_dim"]
    assert model["dim"] == published["hidden_size"]
    assert model["heads"] == published["num_attention_heads"]
    assert model["dim_head"] == published["v_head_dim"]
    assert (block["qk_nope_head_dim"] + block["qk_rope_head_dim"]
            == published["qk_head_dim"])
    assert published["q_lora_rank"] is None and block["q_lora_rank"] == 0
    assert published["use_qk_norm"] is True and block["qk_norm"] is True
    assert published["no_kda_lora"] and block["linear_gate_rank"] == 0
    assert block["attention_gate"] == \
        published["gated_attention_proj_granularity_type"]
    assert published["rope_scaling"] is None and block["yarn_factor"] == 1.0
    assert model["mtp_depth"] == published["num_nextn_predict_layers"]
    # the pattern is the source's: one latent layer closes a group of six
    period = block["attention_layers"]
    assert len(period) == published["layer_group_size"] == model["depth"]
    assert period == ["kda"] * 5 + ["mla"]
    # the cut: one whole period, its leading dense layer counted once (told
    # under ``depth``: ``test_bench_units.py`` holds every name in
    # ``reduced`` to be a key of ``model``), a thirty-second of the experts,
    # an eighth of the rows
    assert cfg["reduced"] == ["depth", "experts_held", "num_text_tokens"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert "first_k_dense_replace 2" in cfg["reduced_why"]["depth"]
    assert block["first_dense_layers"] == 1
    assert model["depth"] - block["first_dense_layers"] >= 4
    assert cfg["chips_sharing_a_layer"] == 32
    assert model["experts_held"] * 32 == published["num_experts"]
    assert model["experts_held"] >= 8
    rows = (model["num_text_tokens"] + model["text_seq_len"]
            + model["image_vocab_size"])
    assert rows * 8 == published["vocab_size"]
    # no layer kept has a clamp: the lists are 0 over the first 34
    assert not any(published["expert_swiglu_limit_list"][:model["depth"]])
    assert not any(published["share_expert_swiglu_limit_list"][:34])
    assert {"kda_decay", "kda_beta", "kda_gates", "mla_qk_norm", "mla_gate",
            "mla_rotary", "router", "router_bias_update_and_balance_loss",
            "mtp", "mtp_loss_weight", "framing"} <= set(cfg["assumed"])
    assert model["mtp_loss_weight"] == 0.1
    assert model["text_seq_len"] + model["image_fmap_size"] ** 2 == 4352
    for key in ("deployment", "experts_load", "arithmetic", "precision"):
        assert cfg[key], key
    # never a width
    widths = re.compile(r"(_dim|_rank|hidden|intermediate|head)")
    assert not any(widths.search(k) for k in cfg["reduced"])


def test_every_new_line_of_the_benchmark_fits_the_drivers_form():
    """Names of at most 64 of ``[A-Za-z0-9_.-]``, ``why`` / ``layer`` /
    ``source`` 1 to 200 printable characters on one line, each entry its
    kind's keys and no other, units of the form's characters, the catalog's
    keys at the configuration file's top level, ``reduced`` of at most 16
    names each a key of the file's ``model``, the whole file within 64 KiB, and
    the check's time with five cells inside the driver's."""
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for section, allowed in keys.items():
        names = [e["name"] for e in BENCH[section]]
        assert len(set(names)) == len(names), section
        for entry in BENCH[section]:
            assert set(entry) - {"workloads"} == allowed, entry["name"]
            assert name.fullmatch(entry["name"]), entry["name"]
            for key in ("why", "layer", "source"):
                text = entry.get(key)
                if text is not None:
                    assert 1 <= len(text) <= 200, (entry["name"], key)
                    assert text.isprintable() and "\t" not in text
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for metric in NEW:
        m = by_name[metric]
        assert unit.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert os.path.exists(os.path.join(REPO, "benchmarks", "metrics",
                                           f"{metric}.py"))
    config = {c["name"]: c for c in BENCH["configs"]}[CONFIG]
    assert len(config["reduced"]) <= 16
    assert all(name.fullmatch(k) for k in config["reduced"])
    assert config["file"].startswith("benchmarks/") and re.fullmatch(
        r"[A-Za-z0-9_.\-/]+", config["file"])
    cfg = harness.load_json(os.path.join(REPO, config["file"]))
    catalog = catalog_entry()
    assert config["source"] == catalog["source_url"]
    assert cfg["reduced"] == config["reduced"]
    for key, value in catalog["config"].items():      # equal, all of them
        assert cfg[key] == value, key
    assert set(config["reduced"]) <= set(cfg["model"])
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert name.fullmatch(cell["traffic"]) and cell["chips"] in (1, 4)
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    cells = len(BENCH["workloads"])
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(
        1, cells // 4)
    assert ((2 + 14 * cells) * (BENCH["run_seconds"] + 60) + 2 * 90 * cells
            + 1200) <= 43200


def test_the_entries_of_every_pr_are_pinned_by_name_and_in_order():
    """What ``test_bench_hybrid.py``'s two pins of position hold but "last"
    (they are expected failures since this PR, ``tests/conftest.py``): PR
    25's eight entries, then PR 27's five, then PR 32's four, then this PR's
    three, in that order and next to each other; each PR's cell and
    configuration in order; each new metric's cell, moved metric, side and
    layer. The next appended entry breaks nothing here."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(PR25[0])
    run_of = PR25 + PR27 + PR32 + NEW
    assert tuple(names[at:at + len(run_of)]) == run_of
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for metric in NEW + PR32 + PR27:
        cell = (CELL if metric in NEW else "train_solar2_ep32_fit"
                if metric in PR32 else "train_dsv2_share16_fit")
        assert by_name[metric]["workloads"] == [cell], metric
        assert by_name[metric]["moves"] == "train_tokens_per_s_per_chip"
    assert by_name["kda_state_device_pct"]["better"] == "lower"
    assert by_name["kda_state_device_pct"]["layer"] == "linear attention"
    assert {by_name[n]["layer"] for n in PR32[:3]} == {"model, whole step",
                                                       "attention tiers"}
    assert [by_name[n]["layer"] for n in NEW] == [
        "model, whole step", "attention tiers", "attention tiers"]
    assert [by_name[n]["source"] for n in NEW] == [
        "host_clock", "device_trace", "device_trace"]
    assert all(by_name[n]["better"] == "higher" and by_name[n]["unit"] == "%"
               for n in NEW)
    # no accepted metric took the new cell, and no new one an accepted cell
    assert all(CELL not in m.get("workloads", ()) for m in BENCH["per_layer"]
               if m["name"] not in NEW)
    cells = [w["name"] for w in BENCH["workloads"]]
    order = ["train_dsv2_share16_fit", "train_solar2_ep32_fit", CELL]
    at = cells.index(order[0])
    assert cells[at:at + 3] == order
    by_cell = {w["name"]: w for w in BENCH["workloads"]}
    assert by_cell[CELL] == {
        "name": CELL, "config": CONFIG, "traffic": "fit_b2_t4352",
        "chips": 1, "why": by_cell[CELL]["why"]}
    assert by_cell["train_solar2_ep32_fit"] == {
        "name": "train_solar2_ep32_fit", "config": "solar_open2_ep32",
        "traffic": "fit_b2_t4352", "chips": 1,
        "why": by_cell["train_solar2_ep32_fit"]["why"]}
    assert by_cell["train_dsv2_share16_fit"]["chips"] == 1
    configs = [c["name"] for c in BENCH["configs"]]
    at = configs.index("deepseek_v2_share16")
    assert configs[at:at + 3] == ["deepseek_v2_share16", "solar_open2_ep32",
                                  CONFIG]
    assert all("train_dsv2_share16_fit" not in m["workloads"]
               for m in BENCH["per_layer"] if m["name"] not in PR27)
    assert all("train_solar2_ep32_fit" not in m["workloads"]
               for m in BENCH["per_layer"] if m["name"] not in PR32)
    cell = harness.load_cell(CELL, BENCH)[0]
    assert cell["kind"] == "train_hybrid_mtp"
    assert cell["traffic"]["batch"] == 2
    assert cell["traffic"]["text_tokens"] == [8, 64]
    assert cell["warm_steps"] == 5 and cell["trace_seconds"] == 5
    for other in ("train_dsv2_share16_fit", "train_solar2_ep32_fit"):
        assert cell["recipe"] == harness.load_cell(other, BENCH)[0]["recipe"]


def test_pr25s_entries_keep_their_cells_sources_sides_and_layers():
    """What ``test_bench_program_names.py`` asserts of PR 25's eight entries
    and the tests of ``test_bench_moe.py`` and ``test_bench_hybrid.py`` named
    ``test_pr25s_entries_are_listed_as_their_test_pins_them`` re-homed, but
    for every assert of position."""
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    both = ["train_malevich_b4", "train_small_b64"]
    for metric in PR25:
        m = by_name[metric]
        fused = metric.startswith("fused_attn")
        assert m["workloads"] == (["train_small_b64"] if fused else both)
        assert m["source"] == ("device_trace" if fused else "program_span")
        assert m["moves"] == ("setup_s" if metric.startswith("setup_")
                              else "train_tokens_per_s_per_chip")
        assert m["better"] == ("higher" if fused else "lower")
    assert {by_name[n]["layer"] for n in PR25} == {
        "trainer loop", "trainer construction", "attention tiers"}
    assert [m["name"] for m in BENCH["end_to_end"]] == [
        "train_tokens_per_s_per_chip", "setup_s"]
    assert [m["bound"] for m in BENCH["end_to_end"]] == [0.02, 0.1]
    assert BENCH["run_seconds"] == 30


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "reference",
                           "ling3.py")) as f:
        text = f.read()
    assert "dalle_tpu" not in text
    imports = [l for l in text.splitlines()
               if l.startswith(("import ", "from "))]
    assert all(".reference." in l for l in imports if "benchmarks" in l)
    assert len([l for l in imports if "benchmarks" in l]) == 3
    # linear attention one position at a time (the reference it takes
    # ``delta_rule`` from scans positions and has no chunked algebra), and
    # latent attention with a whole score matrix a head
    with open(os.path.join(REPO, "benchmarks", "reference",
                           "solar_open2.py")) as f:
        assert "def position(state, x):" in f.read()
    assert "tril" not in text and "cumsum" not in text
    assert 'product("id,jd->ij"' in text and "jnp.inf" in text
    assert "flash" not in text.replace("Ling-3.0-flash", "")
