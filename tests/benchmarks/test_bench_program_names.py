"""The per-layer metrics that read the program's own names (PR 25): fit()'s
phase spans in its records and over the device's idle gaps, the set-up
spans' totals, the Pallas kernels' names; and the bridge they rest on, a
program span on a jax profiler session's host plane."""

import os

import pytest

from benchmarks import harness, program_names, xplane

BENCH = harness.load_benchmark()
NEW = ("fit_dispatch_ms", "fit_after_step_ms", "idle_in_fit_dispatch_pct",
       "idle_in_fit_sync_pct", "setup_trainer_init_s", "setup_fit_warmup_s",
       "fused_attn_fwd_roofline", "fused_attn_bwd_roofline")
TARGET = 'custom_call_target="tpu_custom_call"'
MODEL = {"text_seq_len": 256, "image_fmap_size": 16, "heads": 8,
         "dim_head": 64, "depth": 12}


def read(name, run):
    return harness.read_metrics([name], run).get(name, {}).get("value")


def a_run(**kw):
    run = {"cell": {"traffic": {"batch": 64}}, "config": {"model": MODEL},
           "device": {"kind": "TPU v5 lite"}, "records": [],
           "window": {"seconds": 30.0, "steps": 0}, "trace": None,
           "traced": None}
    run.update(kw)
    return run


# -- BENCHMARK.json ----------------------------------------------------------

def test_the_new_metrics_are_listed_with_their_cells_layers_and_sources():
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert [m["name"] for m in BENCH["per_layer"]][-len(NEW):] == list(NEW)
    both = ["train_malevich_b4", "train_small_b64"]
    for name in NEW:
        m = by_name[name]
        fused = name.startswith("fused_attn")
        assert m["workloads"] == (["train_small_b64"] if fused else both)
        assert m["source"] == ("device_trace" if fused else "program_span")
        assert m["moves"] == ("setup_s" if name.startswith("setup_")
                              else "train_tokens_per_s_per_chip")
        assert m["better"] == ("higher" if fused else "lower")
    assert {by_name[n]["layer"] for n in NEW} == {
        "trainer loop", "trainer construction", "attention tiers"}


def test_a_new_reader_with_nothing_to_read_returns_nothing():
    """The parent commit has none of the names: every new metric is left out
    of its line there, and no reader raises."""
    from dalle_tpu import obs
    obs.reset_phase_totals()
    for name in NEW:
        assert harness.read_metrics([name], a_run()) == {}, name
    traced = a_run(trace={"window_s": 5.0, "busy_s": 4.5, "ops": {
        f"%attn_3.4 = bf16[64,512,512] custom-call(%a), {TARGET}": 1.0},
        "idle_gaps": [["PjitFunction(step)", 0.3],
                      ["np.asarray(jax.Array)", 0.1]]},
        traced={"steps": 10},
        records=[(6, 0.0, {"loss": 1.0, "t_batch_wait_s": 0.001})])
    for name in NEW:
        assert harness.read_metrics([name], traced) == {}, name


# -- fit()'s records ---------------------------------------------------------

def test_dispatch_and_after_step_are_medians_of_the_records_columns():
    rows = [(s, float(s), {"loss": 1.0, "t_dispatch_s": d, "t_after_s": a})
            for s, d, a in ((6, 0.030, 0.0001), (7, 0.027, 0.0002),
                            (8, 0.500, 0.0500))]      # one stalled step
    rows.append((9, 9.0, {"loss": 1.0}))              # a record with no split
    run = a_run(records=rows)
    assert read("fit_dispatch_ms", run) == pytest.approx(30.0)
    assert read("fit_after_step_ms", run) == pytest.approx(0.2)
    assert harness.read_metrics(["fit_dispatch_ms"], run)[
        "fit_dispatch_ms"]["unit"] == "ms"


# -- the device's idle gaps, by the program's spans --------------------------

def test_idle_shares_read_the_gaps_by_the_programs_phase_names():
    trace = {"window_s": 5.0, "busy_s": 4.6, "idle_gaps": [
        ["fit/dispatch", 0.30], ["fit/sync", 0.05], ["fit/batch_wait", 0.001],
        ["H2D Dispatch", 0.000004]]}
    run = a_run(trace=trace, traced={"steps": 12})
    assert read("idle_in_fit_dispatch_pct", run) == pytest.approx(6.0)
    assert read("idle_in_fit_sync_pct", run) == pytest.approx(1.0)


def test_idle_share_is_zero_when_other_phases_name_gaps_and_none_when_none_do():
    named = {"window_s": 5.0, "busy_s": 4.7,
             "idle_gaps": [["fit/dispatch", 0.3], ["no host span", 0.01]]}
    run = a_run(trace=named, traced={"steps": 12})
    assert read("idle_in_fit_sync_pct", run) == 0.0
    # a broken bridge: jax's own names only. A missing metric, not a 0
    broken = {"window_s": 5.0, "busy_s": 4.7, "idle_gaps": [
        ["PjitFunction(step)", 0.3], ["np.asarray(jax.Array)", 0.1],
        ["tpu::System::Execute=>Done", 0.01], ["no host span", 0.01]]}
    run = a_run(trace=broken, traced={"steps": 12})
    assert harness.read_metrics(["idle_in_fit_sync_pct",
                                 "idle_in_fit_dispatch_pct"], run) == {}


@pytest.mark.parametrize("name,program", [
    ("fit/dispatch", True), ("dalle/step", True), ("data/h2d", True),
    ("ckpt/snapshot_good", True), ("PjitFunction(step)", False),
    ("np.asarray(jax.Array)", False), ("tpu::System::Execute=>Done", False),
    ("no host span", False), ("H2D Dispatch", False),
    ("bench/trace_open", True)])
def test_a_program_span_is_told_from_jaxs_own_host_events(name, program):
    assert bool(program_names.PROGRAM_SPAN.fullmatch(name)) is program


# -- set-up spans ------------------------------------------------------------

def test_setup_metrics_read_the_processes_span_totals(monkeypatch):
    from dalle_tpu import obs
    obs.reset_phase_totals()
    assert read("setup_trainer_init_s", a_run()) is None
    with obs.span("trainer/init") as built:
        with obs.span("init/model"):
            pass
    with obs.span("fit/warmup") as warm:
        pass
    assert read("setup_trainer_init_s", a_run()) == built.duration
    assert read("setup_fit_warmup_s", a_run()) == warm.duration
    with obs.span("trainer/init") as again:      # a second trainer: the sum
        pass
    assert read("setup_trainer_init_s", a_run()) == pytest.approx(
        built.duration + again.duration)
    obs.reset_phase_totals()
    # a program from before the totals existed
    monkeypatch.delattr(obs, "phase_totals")
    assert read("setup_fit_warmup_s", a_run()) is None


# -- kernels by name ---------------------------------------------------------

def ops_by_name():
    return {
        f"%fused_attn_fwd.24 = bf16[64,512,512]{{2,1,0}} custom-call("
        f"bf16[64,512,1536]{{2,1,0}} %copy.708, s8[512,512] %c), {TARGET}": 0.4,
        f"%fused_attn_fwd = bf16[64,512,512] custom-call(bf16[64,512,1536] %x, "
        f"s8[512,512] %c), {TARGET}": 0.1,
        f"%fused_attn_bwd.12 = bf16[64,512,1536] custom-call(bf16[64,512,1536] "
        f"%copy.752, bf16[64,512,512] %fused_attn_fwd.24, s8[512,512] %c), "
        f"{TARGET}": 1.5,                  # names the forward among its operands
        f"%flash_attn_fwd.3 = bf16[64,8,512,64] custom-call(%q), {TARGET}": 7.0,
        "%fused_attn_fwd.9 = bf16[64,512,512] fusion(%y)": 9.0}    # not Mosaic


def test_kernels_are_found_by_the_name_the_program_gives_them():
    ops = ops_by_name()
    assert program_names.kernel_seconds(ops, "fused_attn_fwd") == 0.5
    assert program_names.kernel_seconds(ops, "fused_attn_bwd") == 1.5
    assert program_names.kernel_seconds(ops, "fused_attn") == 0.0
    assert program_names.kernel_seconds(ops, "decode_attn") == 0.0


def test_the_two_directions_split_the_shape_matched_roofline():
    """Forward and backward by name are the same events that
    fused_attn_roofline finds by shape: its reading is their time-weighted
    mean, and neither direction reads over 100 at a kernel time above the
    least."""
    from benchmarks import arith
    ops = {k: v for k, v in ops_by_name().items() if "flash" not in k}
    run = a_run(trace={"window_s": 5.0, "busy_s": 4.9, "ops": ops,
                       "idle_gaps": []}, traced={"steps": 32})
    fwd = read("fused_attn_fwd_roofline", run)
    bwd = read("fused_attn_bwd_roofline", run)
    both = read("fused_attn_roofline", run)
    assert both == pytest.approx((0.5 * fwd + 1.5 * bwd) / 2.0)
    peaks = arith.peaks_for("TPU v5 lite")
    least = arith.least_seconds(arith.causal_attention_cost(
        64, 8, 512, 64, backward=False), peaks)[0] * 12 * 32
    assert fwd == pytest.approx(100.0 * least / 0.5) and 0 < fwd < 100
    assert 0 < bwd < 100


# -- the bridge: a program span on the profiler's clock ----------------------

def test_a_span_lies_on_the_profilers_host_plane_between_the_marks(tmp_path):
    """obs.span entered while a jax profiler session is live is a
    TraceAnnotation of the same name: it is in the .xplane.pb's host plane,
    on the trace's clock, inside the benchmark's two marks. fit/step
    (``profiler=False``) is not: it encloses the phases and would take every
    gap."""
    import jax
    import jax.numpy as jnp

    from dalle_tpu import obs
    import dalle_tpu.obs.device  # noqa: F401  (installs the annotation)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    with obs.span("fit/dispatch"):        # before the session: not recorded
        pass
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(xplane.MARK_OPEN):
            pass
        with obs.span("fit/step", profiler=False, step=7):
            with obs.span("fit/dispatch", step=7):
                jnp.ones((64, 64)).sum().block_until_ready()
            with obs.span("fit/sync", step=7) as sync:
                pass
        with jax.profiler.TraceAnnotation(xplane.MARK_CLOSE):
            pass
    finally:
        jax.profiler.stop_trace()
    host = xplane.load(xplane.find_xplane(str(tmp_path)))["host"]
    lo, hi = xplane.window(host)
    found = {}
    for name, a, b in host:
        found.setdefault(name, []).append((a, b))
    assert "fit/step" not in found
    assert len(found["fit/dispatch"]) == 1 and len(found["fit/sync"]) == 1
    (d0, d1), (s0, s1) = found["fit/dispatch"][0], found["fit/sync"][0]
    assert lo <= d0 < d1 <= s0 <= s1 <= hi
    # the same measurement on two clocks
    assert (s1 - s0) / 1e9 == pytest.approx(sync.duration, abs=2e-4)
    # and a gap under it gets its name
    assert xplane.name_gaps([(d0 + 1, d1 - 1)], host) == [
        ["fit/dispatch", (d1 - d0 - 2) / 1e9]]
