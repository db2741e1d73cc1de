"""The benchmark's own arithmetic, traffic, trace reduction and files."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import arith, harness, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = harness.REPO
BENCH = harness.load_benchmark()
MODEL = {"num_text_tokens": 100, "text_seq_len": 16, "image_vocab_size": 64,
         "image_fmap_size": 4}


# -- arithmetic ------------------------------------------------------------

@pytest.mark.parametrize("values,q,expected", [
    ([3, 1, 2], 50, 2), ([3, 1, 2], 100, 3), ([3, 1, 2], 1, 1),
    (list(range(1, 101)), 95, 95), (list(range(1, 21)), 95, 19),
    ([7.5], 95, 7.5)])
def test_percentile_is_nearest_rank(values, q, expected):
    assert arith.percentile(values, q) == expected


def test_percentile_and_rate_refuse_nothing_to_measure():
    with pytest.raises(ValueError):
        arith.percentile([], 95)
    with pytest.raises(ValueError):
        arith.rate(10, 0.0)
    assert arith.rate(4096 * 10, 2.0) == 20480.0


def test_param_count_and_train_flops_by_hand():
    m = {"dim": 8, "heads": 2, "dim_head": 4, "depth": 3, "ff_mult": 4,
         "num_text_tokens": 10, "text_seq_len": 6, "image_vocab_size": 5,
         "image_fmap_size": 2}
    layer = (8 * 24 + 8 * 8 + 8) + (8 * 64 + 64 + 32 * 8 + 8) + 6 * 8
    vocab = 10 + 6 + 5
    n = vocab * 8 + 3 * layer + 16 + 8 * vocab + vocab
    assert arith.dalle_param_count(m) == n
    assert arith.train_flops_per_token(m) == 6.0 * n + 12.0 * 3 * 2 * 4 * 10


def test_flagship_is_1p4b_by_the_benchmarks_own_count():
    cfg = harness.load_json(os.path.join(REPO, "benchmarks", "configs",
                                         "rudalle_malevich.json"))
    # as the TPU's compiler counted the program's own tree (PR 24)
    assert arith.dalle_param_count(cfg["model"]) == 1444049024


def test_mfu_and_least_seconds_by_hand():
    peaks = arith.peaks_for("TPU v5 lite")
    assert arith.mfu_pct(1e9, 98500.0, peaks["bf16_flops"]) == pytest.approx(50.0)
    # compute-bound: 197e12 flops take one second, its bytes far less
    assert arith.least_seconds({"flops": 197e12, "bytes": 1e9}, peaks) == (
        1.0, "compute")
    assert arith.least_seconds({"flops": 1e9, "bytes": 819e9}, peaks) == (
        1.0, "bandwidth")
    cost = arith.causal_attention_cost(2, 3, 8, 4, backward=False)
    assert cost["flops"] == 2 * 2.0 * (8 * 9 / 2) * 4 * 2 * 3
    assert cost["bytes"] == 4 * 2 * 3 * 8 * 4 * 2


def test_unknown_device_kind_is_an_error_not_a_default():
    with pytest.raises(KeyError, match="table of peaks"):
        arith.peaks_for("cpu")
    with pytest.raises(KeyError):
        arith.peaks_for("TPU v9")


def test_spread_is_the_contracts():
    import statistics
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert arith.spread(values) == (q3 - q1) / statistics.median(values)


# -- traffic ---------------------------------------------------------------

MIX = {"batch": 4, "text_tokens": [2, 8]}


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345, 2 ** 32 + 5])
def test_batches_repeat_for_a_seed_and_differ_for_another(seed):
    t0, i0 = traffic.train_batch(seed, 0, 4, MODEL, MIX)
    t1, i1 = traffic.train_batch(seed, 1, 4, MODEL, MIX)
    again = traffic.train_batch(seed, 0, 4, MODEL, MIX)
    assert np.array_equal(t0, again[0]) and np.array_equal(i0, again[1])
    assert not np.array_equal(i0, i1)                   # a fresh batch a step
    other = traffic.train_batch(seed + 1, 0, 4, MODEL, MIX)
    assert not np.array_equal(i0, other[1])
    assert i0.shape == (4, 16) and i0.dtype == np.int32
    assert i0.max() < 64 and t0.max() < 100


def test_captions_are_padded_as_the_tokenizer_pads_and_rows_differ():
    p = traffic.caption_rows(traffic._rng(11, 3), 32, 16, 100, [2, 8])
    assert p.shape == (32, 16) and p.dtype == np.int32
    lengths = (p != 0).sum(1)
    assert lengths.min() >= 2 and lengths.max() <= 8
    assert all((row[:n] != 0).all() and (row[n:] == 0).all()
               for row, n in zip(p, lengths))
    assert len({row.tobytes() for row in p}) == 32      # nothing shared


# -- the trace reduction ---------------------------------------------------

def ev(name, a, b):
    return (name, a, b)


def test_busy_is_the_union_and_gaps_are_the_rest():
    events = [ev("a", 0, 10), ev("b", 5, 20), ev("c", 30, 40), ev("d", 32, 35)]
    assert xplane.union_intervals(events) == [[0, 20], [30, 40]]
    assert xplane.busy_ns(events) == 30
    assert xplane.gaps(events, 0, 50) == [(20, 30), (40, 50)]
    assert xplane.clip(events, 8, 33) == [
        ("a", 8, 10), ("b", 8, 20), ("c", 30, 33), ("d", 32, 33)]


def test_self_time_takes_nested_events_out_and_adds_up_to_busy():
    events = [ev("while", 0, 100), ev("body", 10, 40), ev("body", 50, 90),
              ev("inner", 20, 30), ev("after", 120, 130)]
    self_time = xplane.self_time_by_name(events)
    assert self_time == {"while": 30, "body": 60, "inner": 10, "after": 10}
    assert sum(self_time.values()) == xplane.busy_ns(events)


def test_gaps_are_named_by_the_host_span_that_covers_them():
    host = [ev("fit/dispatch", 18, 31), ev("tiny", 24, 25),
            ev(xplane.MARK_OPEN, 0, 1), ev("np.asarray", 39, 52)]
    named = dict(xplane.name_gaps([(20, 30), (40, 50), (70, 80)], host))
    assert named == {"fit/dispatch": 10 / 1e9, "np.asarray": 10 / 1e9,
                     "no host span": 10 / 1e9}


def test_reduce_a_hand_made_trace():
    trace = {"devices": {"/device:TPU:0": [ev("k", 100, 200), ev("k", 300, 400),
                                           ev("early", 0, 50)],
                         "/device:TPU:1": [ev("k", 100, 300)]},
             "host": [ev(xplane.MARK_OPEN, 90, 95), ev(xplane.MARK_CLOSE, 495, 500),
                      ev("step", 190, 310)]}
    r = xplane.reduce(trace)
    assert r["window_s"] == 410 / 1e9
    assert r["busy_s"] == 200 / 1e9          # both devices busy 200 of 410
    assert r["ops"] == {"k": 200 / 1e9}      # "early" lies before the window
    assert dict(r["idle_gaps"]) == {"step": 100 / 1e9,
                                    "no host span": 110 / 1e9}
    with pytest.raises(ValueError, match="no device operation"):
        xplane.reduce({"devices": {"/device:TPU:0": []}, "host": trace["host"]})


def test_reduce_refuses_a_trace_without_both_marks():
    devices = {"/device:TPU:0": [ev("k", 100, 200)]}
    for host in ([], [ev(xplane.MARK_OPEN, 90, 95)],
                 [ev(xplane.MARK_CLOSE, 495, 500)]):
        with pytest.raises(ValueError, match="open or close mark"):
            xplane.reduce({"devices": devices, "host": host})


def test_reduce_the_recorded_trace():
    """A three-step trace of one small program, recorded on the v5e (PR 24)."""
    trace = xplane.load(os.path.join(HERE, "data", "tiny.xplane.pb"))
    assert list(trace["devices"]) == ["/device:TPU:0"]
    assert len(trace["devices"]["/device:TPU:0"]) == 9
    r = xplane.reduce(trace)
    assert r["window_s"] == pytest.approx(0.010609379, rel=1e-6)
    assert r["busy_s"] == pytest.approx(2.9974e-05, rel=1e-3)
    assert 0 < r["busy_s"] < r["window_s"]
    name, seconds = r["device_ops"][0]
    assert name.startswith("%fusion = bf16[] fusion(") and len(name) <= 120
    assert seconds == pytest.approx(2.9942e-05, rel=1e-3)
    assert r["idle_gaps"][0][0] == "PjitFunction(f)"
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)


def test_mosaic_calls_are_found_by_their_target_and_names_are_cut():
    name = ('%attn_4.4 = bf16[64,512,1536]{2,1,0:T(8,128)(2,1)} custom-call('
            'bf16[64,512,1536]{2,1,0} %copy.724), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={bf16[8]{0}}')
    assert xplane.is_mosaic(name) and not xplane.is_mosaic("%fusion.1 = f32[]")
    short = xplane.short_name(name)
    assert short.startswith("[mosaic] %attn_4.4 = bf16[64,512,1536] custom-call(")
    assert "{" not in short and len(short) <= 130


def test_fused_attention_kernels_are_found_by_their_qkv_shape():
    reader = harness.load_module(os.path.join(
        REPO, "benchmarks", "metrics", "fused_attn_roofline.py"), "reader")
    target = 'custom_call_target="tpu_custom_call"'
    ops = {f"%attn_1.4 = bf16[64,512,1536]{{2,1,0}} custom-call(bf16[64,512,1536]"
           f"{{2,1,0}} %a, bf16[64,512,512] %b), {target}": 3.0,       # backward
           f"%attn_1.2 = bf16[64,512,512]{{2,1,0}} custom-call(bf16[64,512,1536]"
           f"{{2,1,0}} %a), {target}": 1.0,                             # forward
           f"%other = bf16[64,512,512] custom-call(bf16[64,512,512] %x), "
           f"{target}": 7.0,                       # a Pallas kernel of another kind
           "%fusion.1 = bf16[64,512,1536] fusion(%y)": 9.0}          # not Mosaic
    assert reader.kernel_seconds(ops, 64, 512, 512) == 4.0
    assert reader.kernel_seconds(ops, 8, 512, 512) == 0.0


# -- the files -------------------------------------------------------------

def test_every_cell_finds_its_files_and_agrees_with_benchmark_json():
    for entry in BENCH["workloads"]:
        cell, cfg = harness.load_cell(entry["name"], BENCH)
        assert cell["name"] == entry["name"] and cell["chips"] == entry["chips"]
        assert cfg["name"] == entry["config"]
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "kinds", f"{cell['kind']}.py"))
        assert cell["limits"], "a cell with no limit decides nothing"
        assert isinstance(cell["traffic"], dict)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)


WIDTHS = ("dim", "heads", "dim_head", "ff_mult", "text_seq_len",
          "image_vocab_size")
# the source's own names for the same sizes
SAME = {"dim": "hidden_size", "heads": "num_attention_heads",
        "text_seq_len": "text_seq_length", "depth": "num_layers",
        "image_fmap_size": "image_tokens_per_dim"}


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_a_configuration_says_what_it_changed_from_its_source(config):
    cfg = harness.load_json(os.path.join(REPO, config["file"]))
    assert cfg["reduced"] == config["reduced"]
    assert cfg["source"].startswith("https://")
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])
    model, published = cfg["model"], cfg["published"]
    assert set(cfg["reduced"]) == set(cfg["reduced_why"])
    for key in cfg["reduced"]:                 # a cut is never a width
        assert key in model and key not in WIDTHS
        assert not key.endswith(("_dim", "_rank"))
    for key, value in model.items():
        theirs = published.get(key, published.get(SAME.get(key)))
        if isinstance(theirs, int) and key not in cfg["reduced"]:
            assert value == theirs, (key, value, theirs)
        if isinstance(theirs, int) and key in cfg["reduced"]:
            assert value != theirs, f"{key} is listed as cut and is not"
    if "hidden_size" in published:             # heads x head size = width
        assert model["heads"] * model["dim_head"] == published["hidden_size"]


def test_every_metric_has_a_reader_and_cells_that_report_what_it_moves():
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and all(m["bound"] <= 0.1 for m in e2e.values())
    for m in BENCH["per_layer"]:
        reader = harness.load_module(os.path.join(
            REPO, "benchmarks", "metrics", f"{m['name']}.py"), "reader")
        assert reader.UNIT == m["unit"], m["name"]
        moved = e2e[m["moves"]]
        reporting = set(moved.get("workloads", cells))
        assert m["workloads"] and set(m["workloads"]) <= cells
        assert set(m["workloads"]) <= reporting, (m["name"], m["moves"])
    for cell in cells:
        assert any(cell in m["workloads"] for m in BENCH["per_layer"])
        assert sum(cell in m.get("workloads", cells)
                   for m in BENCH["end_to_end"]) >= 2


def test_a_reader_with_nothing_to_read_returns_nothing():
    run = {"trace": None, "traced": None, "records": [], "lags": [], "ttft": [],
           "window": {"seconds": 1.0, "steps": 0}, "requests": []}
    for name in ("device_idle_pct.train", "train_step_device_ms",
                 "fused_attn_roofline", "fit_batch_wait_pct"):
        assert harness.read_metrics([name], run) == {}, name


def test_judge_holds_a_cell_to_the_limits_its_file_names():
    compared = {"a": 0.1, "b": 5.0, "c": float("nan")}
    assert harness.judge(compared, {"a": 0.2}) == (True, {"a": [0.1, 0.2]})
    assert harness.judge(compared, {"a": 0.2, "b": 1.0})[0] is False
    assert harness.judge(compared, {"c": 1.0})[0] is False   # NaN never passes
    with pytest.raises(SystemExit):
        harness.judge(compared, {})
    with pytest.raises(SystemExit):
        harness.judge(compared, {"zzz": 1.0})


# -- run.py off the chip ---------------------------------------------------

def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="7")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         BENCH["workloads"][0]["name"], "--seed", "3000000019", "--seconds",
         "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_py_refuses_to_measure_without_a_tpu():
    done = run_py(REPO)
    assert done.returncode != 0
    assert "nothing was measured" in done.stderr
    assert '"metrics"' not in done.stdout and "tokens/s" not in done.stdout


def test_run_py_fails_where_the_program_is_missing(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"), tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = run_py(tmp_path)
    assert done.returncode != 0 and '"metrics"' not in done.stdout


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmarks", "reference", "dalle.py")) as f:
        text = f.read()
    assert "dalle_tpu" not in text and "import benchmarks" not in text
    assert json.dumps(BENCH)  # the file is plain JSON


# -- the feed of a train cell ------------------------------------------------

def test_the_feed_draws_ahead_the_batches_it_would_draw_on_the_fly():
    import time

    from benchmarks.kinds import train
    cell = {"traffic": {"batch": 4, "text_tokens": [2, 8]}}
    cfg = {"model": MODEL}
    ahead, lazy = (train._Feed(cell, cfg, 2 ** 31 + 3, n) for n in (3, 0))
    assert len(ahead.ready) == 3 and not lazy.ready
    for _ in range(5):          # past what was drawn ahead: drawn on the fly
        (t0, i0), (t1, i1) = next(ahead), next(lazy)
        assert np.array_equal(t0, t1) and np.array_equal(i0, i1)
    assert ahead.i == 5
    ahead.deadline = time.perf_counter() - 1.0      # the window's time is up
    with pytest.raises(StopIteration):
        next(ahead)
