"""Device time by the program's own scopes, benchmark side:
``benchmarks/scope_time.py`` and the nine readers over hand-built runs (event
names as the trace writes them) against a stub table, and the files' own
pins: the nine entries at the end of ``per_layer`` with their cells, sides
and layers, and the entries of every PR before them by name and in order
(what ``test_bench_ling3.py``'s pin holds but "no other metric lists the
newer cells", which cannot hold once these do: that test is an expected
failure by ``tests/conftest.py``)."""

import os

import pytest

from benchmarks import harness, scope_time
from dalle_tpu.obs import device as obs_device

BENCH = harness.load_benchmark()
ALL = ["train_malevich_b4", "train_small_b64", "train_dsv2_share16_fit",
       "train_solar2_ep32_fit", "train_ling3_ep32_fit"]
# (name, what it reads, layer in BENCHMARK.json, cells), in the list's order
NEW = (
    ("step_optimizer_device_pct", "optimizer", "train step", ALL),
    ("step_remat_device_pct", "remat", "train step", ALL),
    ("step_loss_device_pct", "loss", "model, whole step", ALL),
    ("step_unscoped_device_pct", "unscoped", "device", ALL),
    ("attn_core_device_pct", "attn_core", "attention tiers", ALL),
    ("attn_proj_device_pct", "attn_proj", "attention tiers", ALL),
    ("ff_device_pct", "ff", "model, whole step",
     [ALL[0], ALL[1], ALL[2], ALL[4]]),
    ("moe_device_pct", "moe", "routed experts", ALL[2:]),
    ("kda_chunk_device_pct", "kda_chunk", "linear attention", ALL[3:]),
)
PR34 = ("train_ling3_mfu_pct", "mla_flash_fwd_roofline",
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

STEP = "jit(step)/"
FWD = STEP + "jvp(forward)/DALLE/transformer/transformer._block_body/"
BWD = (STEP + "transpose(jvp(forward))/DALLE/transformer/jvp(forward)/DALLE/"
       "transformer/checkpoint/transformer._block_body/")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")
# instruction -> path, as the program's table holds them
TABLE = {
    "fusion.7": FWD + "layer_ff_0/ff/ff_0/w1/dot_general",
    "fusion.8": REMAT + "layer_ff_0/ff/ff_0/w1/dot_general",
    "multiply_reduce_fusion.74": BWD + "layer_attn_0/attn_0/attn/out/to_out/"
                                       "dot_general",
    "flash_attn_dkv.1": BWD + "layer_attn_0/attn_0/attn_core/flash_attn_dkv",
    "while.3": BWD + "layer_attn_1/attn_1/attn/kda_chunk/while",
    "fusion.162": BWD + "layer_attn_1/attn_1/attn/kda_chunk/while/body/"
                        "closed_call/checkpoint/rematted_computation/mul",
    "copy.55": BWD + "layer_attn_1/attn_1/attn/kda_chunk/while/body/"
                     "closed_call/checkpoint/rematted_computation/mul",
    "moe_gmm_fwd.2": FWD + "layer_ff_1/ff_1/moe/experts/moe_gmm_fwd",
    "fusion.20": STEP + "jvp(forward)/DALLE/loss/DALLE._ce_segment/"
                        "dot_general",
    "fusion.30": STEP + "optimizer/mul",
    "fusion.31": STEP + "clip/mul",
    "fusion.40": FWD + "layer_ff_0/norm/mul",
    "convert.5": STEP + "convert_element_type",
}
INHERITED = {"copy.55"}
# device events as the trace names them, with their SELF seconds: the while's
# own 0.01 is what is left of it once its body's operations are taken out
OPS = {
    "%fusion.7 = bf16[4,1152,16384]{2,1,0:T(8,128)(2,1)} fusion(bf16[4,1152,"
    "2048]{2,1,0:T(8,128)(2,1)} %copy.1, bf16[2048,16384]{1,0} %p.3), "
    "kind=kOutput, calls=%fused_computation.7": 0.20,
    "%fusion.8 = bf16[4,1152,16384]{2,1,0} fusion(%copy.1), kind=kOutput, "
    "calls=%fused_computation.8": 0.10,
    "%multiply_reduce_fusion.74 = bf16[2048]{0} fusion(%p.9)": 0.05,
    "%flash_attn_dkv.1 = (bf16[2,8,4352,128]{3,2,1,0}, bf16[2,8,4352,128]"
    "{3,2,1,0}) custom-call(%a, %b), "
    "custom_call_target=\"tpu_custom_call\"": 0.08,
    "%while.3 = (s32[], f32[2,64,128,128]{3,2,1,0}) while(%tuple.9), "
    "condition=%cond.3, body=%body.3": 0.01,
    "%fusion.162 = f32[2,2,64,8,8,1024]{5,4,3,2,1,0} fusion(%gte.4)": 0.30,
    "%copy.55 = f32[2,64,128,128]{3,2,1,0} copy(%fusion.162)": 0.02,
    "%moe_gmm_fwd.2 = bf16[10240,1280]{1,0} custom-call(%x, %w), "
    "custom_call_target=\"tpu_custom_call\"": 0.04,
    "%fusion.20 = f32[128,8192]{1,0} fusion(%h)": 0.06,
    "%fusion.30 = f32[8192,2048]{1,0} fusion(%g)": 0.05,
    "%fusion.31 = f32[]{:T(128)} fusion(%g)": 0.02,
    "%fusion.40 = bf16[4,1152,2048]{2,1,0} fusion(%x)": 0.03,
    "%convert.5 = f32[8]{0} convert(%y)": 0.01,
    "%copy.900 = s32[]{:T(128)} copy(%get-tuple-element.77)": 0.03,
}
BUSY = sum(OPS.values())          # 1.00
EXPECTED = {          # % of busy
    "ff": 30.0, "attn_proj": 5.0, "attn_core": 8.0, "kda_chunk": 33.0,
    "moe": 4.0, "loss": 6.0, "optimizer": 7.0, "other": 3.0,
    "unscoped": 4.0, "remat": 42.0}


def made_up_run(ops=OPS, busy=BUSY) -> dict:
    return {"cell": {"name": "made_up"}, "traced": {"steps": 2},
            "trace": {"busy_s": busy, "window_s": busy * 1.01,
                      "ops": dict(ops)}}


@pytest.fixture
def table(monkeypatch):
    """The stub table under the name the readers ask for."""
    held = obs_device.ScopeTable(TABLE, INHERITED)
    monkeypatch.setitem(obs_device._programs, scope_time.PROGRAM, held)
    return held


def read(name: str, run: dict):
    return harness.read_metrics([name], run).get(name, {}).get("value")


@pytest.mark.parametrize("name, reads", [(n, r) for n, r, _, _ in NEW])
def test_each_reader_gives_its_share_of_the_busy_time(name, reads, table):
    assert read(name, made_up_run()) == pytest.approx(EXPECTED[reads])


def test_the_split_adds_up_and_says_what_it_could_not_place(table, capsys):
    run = made_up_run()
    split = scope_time.by_layer(run)
    assert sum(split["layers"].values()) == pytest.approx(BUSY)
    assert sum(split["phases"].values()) == pytest.approx(BUSY)
    assert split["matched_s"] == pytest.approx(BUSY - 0.03)
    assert split["inherited_s"] == pytest.approx(0.02)
    assert split["busy_s"] == BUSY
    # a loop's own time and its body's go to the loop's scope; recompute
    # inside the backward is `remat`
    assert split["cells"]["kda_chunk", "bwd"] == pytest.approx(0.01)
    assert split["cells"]["kda_chunk", "remat"] == pytest.approx(0.32)
    assert [e.split(" = ")[0] for e, _ in split["unscoped"]] == [
        "%copy.900", "%convert.5"]
    # one log line a run, however many readers ask
    assert scope_time.by_layer(run) is split
    lines = [l for l in capsys.readouterr().out.splitlines()
             if "device time by scope" in l]
    assert len(lines) == 1
    for piece in ("kda_chunk 165.00 ms 33.00 % (remat 160.00 bwd 5.00)",
                  "ff 150.00 ms 30.00 % (remat 50.00 fwd 100.00)",
                  "optimizer 35.00 ms 7.00 % (update 35.00)",
                  "By phase: remat 42.00 %", "the layers add to 100.00 %",
                  "found in the program's table 97.00 %",
                  "inherited path 2.00 %", "Largest unscoped: %copy.900"):
        assert piece in lines[0], piece
    assert split["unseen"] == [] and "ANOTHER VERSION" not in lines[0]


def test_a_layer_with_no_time_reads_zero_where_the_split_stands(table):
    ops = {k: v for k, v in OPS.items() if "moe_gmm" not in k}
    assert read("moe_device_pct", made_up_run(ops, sum(ops.values()))) == 0.0


def test_an_executable_of_another_versions_scopes_is_said_in_the_line(
        monkeypatch, capsys):
    """The table says which layers the source scopes and the executable
    lacks (a step loaded from a cache entry an older version wrote): the
    readers still read, and the log line says by whose names."""
    monkeypatch.setitem(
        obs_device._programs, scope_time.PROGRAM,
        obs_device.ScopeTable(TABLE, INHERITED, unseen={"embed"}))
    run = made_up_run()
    assert read("ff_device_pct", run) == pytest.approx(30.0)
    assert scope_time.by_layer(run)["unseen"] == ["embed"]
    out = capsys.readouterr().out
    assert "ANOTHER VERSION'S: the program's source scopes embed and" in out


def test_nothing_without_a_trace_a_table_or_enough_of_the_names(
        monkeypatch, capsys):
    names = [n for n, _, _, _ in NEW]
    # no table: a program that never captured one, or captured None
    monkeypatch.delitem(obs_device._programs, scope_time.PROGRAM,
                        raising=False)
    assert harness.read_metrics(names, made_up_run()) == {}
    monkeypatch.setitem(obs_device._programs, scope_time.PROGRAM, None)
    assert harness.read_metrics(names, made_up_run()) == {}
    # a program that lacks the functions (the parent commit): the same
    monkeypatch.setitem(obs_device._programs, scope_time.PROGRAM,
                        obs_device.ScopeTable(TABLE))
    with monkeypatch.context() as m:
        m.delattr(obs_device, "program_scopes")
        assert harness.read_metrics(names, made_up_run()) == {}
    with monkeypatch.context() as m:
        m.delattr(obs_device, "scope_layer")
        assert harness.read_metrics(names, made_up_run()) == {}
    # no trace
    run = made_up_run()
    run["trace"] = None
    assert harness.read_metrics(names, run) == {}
    # another program's names: under 90 % of busy found in the table
    assert "device time by scope" not in capsys.readouterr().out
    foreign = dict(OPS, **{"%fusion.999 = f32[] fusion(%q)": 0.2})
    assert harness.read_metrics(
        names, made_up_run(foreign, BUSY + 0.2)) == {}
    assert "no device time by scope" in capsys.readouterr().out
    # and with all of it there, every reader reads
    assert set(harness.read_metrics(names, made_up_run())) == set(names)


def test_the_nine_entries_follow_the_accepted_ones_with_their_cells_and_layers():
    """Next to each other and in the issue's order, right after PR 34's
    three: at this PR the last nine of the list, pinned as an order so that
    the next appended entry breaks nothing."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(NEW[0][0])
    assert names[at - 1] == PR34[-1]
    entries = BENCH["per_layer"][at:at + len(NEW)]
    assert [m["name"] for m in entries] == [n for n, _, _, _ in NEW]
    for entry, (name, _, layer, cells) in zip(entries, NEW):
        assert entry == {"name": name, "unit": "%", "better": "lower",
                         "source": "device_trace", "layer": layer,
                         "moves": "train_tokens_per_s_per_chip",
                         "workloads": cells}, name
        path = os.path.join(harness.HERE, "metrics", f"{name}.py")
        module = harness.load_module(path, "reader_" + name)
        assert module.UNIT == entry["unit"] and callable(module.read)
    # a layer's name is one the benchmark already had
    had = {m["layer"] for m in BENCH["per_layer"][:at]}
    assert {layer for _, _, layer, _ in NEW} <= had


def test_the_entries_of_every_pr_before_keep_their_order_and_cells():
    """``test_bench_ling3.py::test_the_entries_of_every_pr_are_pinned_by_
    name_and_in_order`` but for "no other metric lists the newer cells":
    PR 25's eight entries, then PR 27's five, PR 32's four, PR 34's three,
    then this PR's nine, next to each other; each earlier metric's cell,
    moved metric, side and layer; the cells and configurations in order."""
    names = [m["name"] for m in BENCH["per_layer"]]
    at = names.index(PR25[0])
    run_of = PR25 + PR27 + PR32 + PR34 + tuple(n for n, _, _, _ in NEW)
    assert tuple(names[at:at + len(run_of)]) == run_of
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    for metric in PR34 + PR32 + PR27:
        cell = ("train_ling3_ep32_fit" if metric in PR34
                else "train_solar2_ep32_fit" if metric in PR32
                else "train_dsv2_share16_fit")
        assert by_name[metric]["workloads"] == [cell], metric
        assert by_name[metric]["moves"] == "train_tokens_per_s_per_chip"
    assert by_name["kda_state_device_pct"]["better"] == "lower"
    assert by_name["kda_state_device_pct"]["layer"] == "linear attention"
    assert {by_name[n]["layer"] for n in PR32[:3]} == {"model, whole step",
                                                       "attention tiers"}
    assert [by_name[n]["layer"] for n in PR34] == [
        "model, whole step", "attention tiers", "attention tiers"]
    assert [by_name[n]["source"] for n in PR34] == [
        "host_clock", "device_trace", "device_trace"]
    assert all(by_name[n]["better"] == "higher" and by_name[n]["unit"] == "%"
               for n in PR34)
    # the three newer cells are listed by their own PR's metrics and by this
    # PR's nine, and by nothing else
    ours = {n for n, _, _, _ in NEW}
    for cell, own in (("train_ling3_ep32_fit", PR34),
                      ("train_solar2_ep32_fit", PR32),
                      ("train_dsv2_share16_fit", PR27)):
        listing = {m["name"] for m in BENCH["per_layer"]
                   if cell in m.get("workloads", ())}
        assert listing - ours == set(own), cell
    cells = [w["name"] for w in BENCH["workloads"]]
    at = cells.index(ALL[2])
    assert cells[at:at + 3] == ALL[2:]
    by_cell = {w["name"]: w for w in BENCH["workloads"]}
    for cell, config in (("train_ling3_ep32_fit", "ling3_flash_ep32"),
                         ("train_solar2_ep32_fit", "solar_open2_ep32")):
        assert by_cell[cell] == {
            "name": cell, "config": config, "traffic": "fit_b2_t4352",
            "chips": 1, "why": by_cell[cell]["why"]}
    assert by_cell["train_dsv2_share16_fit"]["chips"] == 1
    configs = [c["name"] for c in BENCH["configs"]]
    at = configs.index("deepseek_v2_share16")
    assert configs[at:at + 3] == ["deepseek_v2_share16", "solar_open2_ep32",
                                  "ling3_flash_ep32"]
    cell = harness.load_cell("train_ling3_ep32_fit", BENCH)[0]
    assert cell["kind"] == "train_hybrid_mtp"
    assert cell["traffic"]["batch"] == 2
    assert cell["traffic"]["text_tokens"] == [8, 64]
    assert cell["warm_steps"] == 5 and cell["trace_seconds"] == 5
    for other in ("train_dsv2_share16_fit", "train_solar2_ep32_fit"):
        assert cell["recipe"] == harness.load_cell(other, BENCH)[0]["recipe"]
