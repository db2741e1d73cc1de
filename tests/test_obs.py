"""grafttrace telemetry (dalle_tpu/obs/): spans, ring buffer, exports,
counters/gauges, Prometheus textfile, device telemetry, stall watchdog, and
the MetricsLogger/MFU satellites."""

import json
import os
import threading
import time

import numpy as np
import pytest

from dalle_tpu import obs
from dalle_tpu.obs import prometheus as prom
from dalle_tpu.obs import report as obs_report


@pytest.fixture
def tracer():
    """A fresh enabled tracer, disabled again afterwards (the global default
    must stay off: other test modules measure span cost as one None check)."""
    obs.disable()
    tr = obs.configure(capacity=256)
    yield tr
    obs.disable()


# -- span core --------------------------------------------------------------

def test_span_times_itself_with_the_ring_off():
    """One measurement, whatever is switched on: ``duration`` and the
    process-wide totals need no ring; the ring-only surfaces stay empty."""
    obs.disable()
    obs.reset_phase_totals()
    with obs.span("x") as sp:
        time.sleep(0.002)
    with obs.span("x"):
        pass
    assert sp.duration >= 0.002
    count, seconds = obs.phase_totals()["x"]
    assert count == 2 and seconds >= sp.duration
    assert obs.metrics_snapshot() == {}
    assert obs.open_spans() == {}
    obs.reset_phase_totals()
    assert obs.phase_totals() == {}


def test_phase_totals_are_exact_across_threads():
    """Per-thread cells merged at read: no update is lost, and a thread
    that has ended keeps its share."""
    obs.disable()
    obs.reset_phase_totals()

    def worker():
        for _ in range(500):
            with obs.span("t/work"):
                pass

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    worker()
    for t in threads:
        t.join()
    assert obs.phase_totals()["t/work"][0] == 2500
    obs.reset_phase_totals()


def test_record_span_stays_ring_only(tracer):
    obs.reset_phase_totals()
    obs.record_span("serve/request", time.perf_counter(), 0.5)
    assert "serve/request" not in obs.phase_totals()
    assert [r[0] for r in tracer.spans] == ["serve/request"]


def test_span_id_and_parent(tracer):
    """Every record carries its own id and the id of the span that was open
    beneath it on its thread; depth 0 and record_span have no parent."""
    with obs.span("outer") as outer:
        with obs.span("inner") as inner:
            pass
        with obs.span("inner2") as inner2:
            pass
    obs.record_span("late", time.perf_counter(), 0.0)
    rows = {r[0]: r for r in tracer.spans}
    assert rows["outer"][6] == outer.id and rows["outer"][7] is None
    assert rows["inner"][6] == inner.id and rows["inner"][7] == outer.id
    assert rows["inner2"][7] == outer.id and rows["late"][7] is None
    assert len({r[6] for r in tracer.spans}) == 4


def test_span_closed_out_of_order_leaves_the_stack_sound(tracer):
    """fit/warmup opens before the first fit/step and ends inside it."""
    warm = obs.span("warm").__enter__()
    with obs.span("step"):
        with obs.span("phase"):
            pass
        warm.__exit__(None, None, None)
        assert list(obs.open_spans().values()) == [["step"]]
        with obs.span("phase2"):
            pass
    assert obs.open_spans() == {}
    rows = {r[0]: r for r in tracer.spans}
    assert rows["step"][7] == warm.id and rows["phase2"][4] == 1


def test_obs_imports_and_spans_without_jax():
    """data/loaders.py and webdataset.py time themselves in processes that
    never load jax: the profiler's annotation class is installed by
    obs/device.py, not imported by obs/trace.py."""
    import subprocess
    import sys
    code = ("import sys\n"
            "from dalle_tpu import obs\n"
            "from dalle_tpu.obs.trace import span\n"
            "from dalle_tpu.data import device_prefetch\n"
            "with span('x') as sp: pass\n"
            "assert sp.duration is not None and obs.phase_totals()['x'][0] == 1\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    done = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, cwd=os.path.dirname(os.path.dirname(
                              os.path.abspath(__file__))))
    assert done.returncode == 0, done.stderr


def test_span_nesting_depth_and_order(tracer):
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    rows = list(tracer.spans)
    assert [(r[0], r[4]) for r in rows] == [("inner", 1), ("outer", 0)]
    inner, outer = rows
    assert 0 <= inner[2] <= outer[2]       # inner duration within outer's


def test_span_args_and_set(tracer):
    with obs.span("s", step=3) as sp:
        sp.set(extra=1)
    assert list(tracer.spans)[0][5] == {"step": 3, "extra": 1}
    assert sp.duration is not None and sp.duration >= 0


def test_span_decorator(tracer):
    @obs.span("deco")
    def f(x):
        return x + 1

    assert f(1) == 2 and f(2) == 3
    assert [r[0] for r in tracer.spans] == ["deco", "deco"]


def test_span_records_on_exception(tracer):
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert [r[0] for r in tracer.spans] == ["boom"]
    assert obs.open_spans() == {}          # stack unwound


def test_ring_overflow_is_counted():
    obs.disable()
    tr = obs.configure(capacity=8)
    try:
        for i in range(20):
            with obs.span(f"s{i}"):
                pass
        assert len(tr.spans) == 8
        assert tr.dropped == 12
        assert obs.metrics_snapshot()["obs.spans_dropped"] == 12
    finally:
        obs.disable()


def test_thread_local_stacks(tracer):
    """Spans in a worker thread must not nest under the main thread's open
    span (independent per-thread depth), and open_spans sees both."""
    seen = {}
    release = threading.Event()

    def worker():
        with obs.span("worker_span"):
            seen.update(obs.open_spans())
            release.wait(2.0)

    with obs.span("main_span"):
        t = threading.Thread(target=worker)
        t.start()
        while len(seen) < 2 and t.is_alive():
            time.sleep(0.005)
        release.set()
        t.join()
    stacks = list(seen.values())
    assert ["main_span"] in stacks and ["worker_span"] in stacks
    by_name = {r[0]: r for r in tracer.spans}
    assert by_name["worker_span"][4] == 0   # depth 0 in its own thread


def test_export_while_another_thread_records(tmp_path, tracer):
    """Exports snapshot the ring under the lock: iterating a deque that a
    prefetch-style thread is appending to would otherwise raise
    'deque mutated during iteration' right in fit's export-on-exit."""
    stop = threading.Event()

    def worker():
        while not stop.is_set():
            with obs.span("w"):
                pass

    t = threading.Thread(target=worker)
    t.start()
    try:
        for _ in range(40):
            obs.export_spans_jsonl(str(tmp_path / "s.jsonl"))
            obs.export_chrome_trace(str(tmp_path / "t.json"))
    finally:
        stop.set()
        t.join()


def test_configure_resize_keeps_newest_spans(tracer):
    for i in range(20):
        with obs.span(f"s{i}"):
            pass
    tr = obs.configure(capacity=4)          # shrink in place, not ignored
    assert tr is tracer and tr.capacity == 4
    assert [r[0] for r in tr.snapshot_spans()] == ["s16", "s17", "s18", "s19"]


# -- exports ----------------------------------------------------------------

def test_chrome_trace_export(tmp_path, tracer):
    with obs.span("parent", step=1):
        with obs.span("child"):
            pass
    path = str(tmp_path / "trace.json")
    n = obs.export_chrome_trace(path)
    doc = json.load(open(path))
    events = doc["traceEvents"]
    assert n == len(events) == 2
    ev = {e["name"]: e for e in events}
    assert all(e["ph"] == "X" for e in events)
    # microsecond containment: child inside parent
    assert ev["parent"]["ts"] <= ev["child"]["ts"]
    assert (ev["child"]["ts"] + ev["child"]["dur"]
            <= ev["parent"]["ts"] + ev["parent"]["dur"] + 1)
    assert ev["parent"]["args"] == {"step": 1, "id": ev["parent"]["args"]["id"],
                                    "parent": None}
    assert ev["child"]["args"]["parent"] == ev["parent"]["args"]["id"]


def test_spans_jsonl_export_and_report(tmp_path, tracer):
    for i in range(3):
        with obs.span("work", i=i):
            pass
    path = str(tmp_path / "spans.jsonl")
    assert obs.export_spans_jsonl(path) == 3
    rows = obs_report.load_jsonl(path)
    assert all(r["name"] == "work" and "dur_s" in r for r in rows)
    assert len({r["id"] for r in rows}) == 3
    assert all(r["parent"] is None and "i" in r["args"] for r in rows)
    agg = obs_report.span_aggregate(rows)
    assert agg[0]["name"] == "work" and agg[0]["count"] == 3
    text = obs_report.summarize_run(path)
    assert "work" in text and "slowest" in text


def test_report_metrics_rows(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as fh:
        for i in range(1, 6):
            fh.write(json.dumps({
                "step": i, "time": float(i), "step_time_s": 0.1 * i,
                "data_starvation": 0.8, "hbm_bytes_in_use": 1 << 20}) + "\n")
    text = obs_report.summarize_run(path)
    assert "INPUT-BOUND" in text and "hbm in use" in text


# -- counters / gauges / prometheus -----------------------------------------

def test_counters_and_gauges(tracer):
    obs.counter_add("obs.events_total", 2)
    obs.counter_add("obs.events_total", 3)
    obs.gauge_set("obs.depth", 4)
    snap = obs.metrics_snapshot()
    assert snap["obs.events_total"] == 5 and snap["obs.depth"] == 4.0


def test_prometheus_textfile(tmp_path):
    path = str(tmp_path / "m.prom")
    content = prom.write_textfile(
        path, {"obs.decode_tokens_total": 7, "obs.hbm/used": 3.5,
               "note": "not-a-number"})
    assert open(path).read() == content
    assert "# TYPE dalle_obs_decode_tokens_total counter" in content
    assert "dalle_obs_decode_tokens_total 7" in content
    assert "# TYPE dalle_obs_hbm_used gauge" in content
    assert "not-a-number" not in content
    assert not (tmp_path / "m.prom.tmp").exists()   # atomic replace


# -- device telemetry --------------------------------------------------------

def test_device_memory_stats_always_has_gauge():
    out = obs.device_memory_stats()
    assert isinstance(out["hbm_bytes_in_use"], int)


def test_device_telemetry_poll_and_compile_rate():
    import jax
    import jax.numpy as jnp
    tele = obs.DeviceTelemetry(window=100)
    first = tele.poll(0)
    assert "compiles_total" in first and "hbm_peak_bytes" in first
    jax.jit(lambda x: x * 2 + 1)(jnp.arange(7))     # fresh program: compiles
    second = tele.poll(10)
    assert second["compiles_total"] > first["compiles_total"]
    assert second["recompiles_per_100_steps"] > 0


def test_compile_counter_shared_with_recompile_guard():
    """The guard's counter and the obs counter are the SAME process-wide
    listener (lifted, not duplicated)."""
    from dalle_tpu.analysis import recompile_guard
    from dalle_tpu.obs import device
    assert recompile_guard.install_compile_counter() is (
        device.install_compile_counter())


# -- watchdog ----------------------------------------------------------------

def test_watchdog_fires_on_stall(tracer):
    logs, reports = [], []
    wd = obs.StallWatchdog(0.08, log=logs.append, poll_s=0.02,
                           on_stall=reports.append).start()
    try:
        wd.beat(7)
        with obs.span("stuck_step"):
            time.sleep(0.4)
    finally:
        wd.stop()
    assert wd.stall_count == 1              # one report per episode, not per poll
    rep = wd.last_report
    assert rep.step == 7 and rep.idle_s >= 0.08
    assert any("stuck_step" in " > ".join(v)
               for v in rep.open_spans.values())
    assert "test_watchdog_fires_on_stall" in rep.stack_dump
    assert reports == [rep]
    assert "STALL" in logs[0] and "stuck_step" in logs[0]


def test_watchdog_rearms_after_beat():
    wd = obs.StallWatchdog(0.05, log=lambda *_: None, poll_s=0.01,
                           dump_stacks=False).start()
    try:
        time.sleep(0.15)
        assert wd.stall_count == 1
        wd.beat(1)                          # re-arm
        time.sleep(0.15)
        assert wd.stall_count == 2
    finally:
        wd.stop()


def test_watchdog_quiet_with_heartbeat():
    wd = obs.StallWatchdog(0.2, log=lambda *_: None, poll_s=0.02,
                           dump_stacks=False).start()
    try:
        for i in range(10):
            wd.beat(i)
            time.sleep(0.02)
    finally:
        wd.stop()
    assert wd.stall_count == 0


def test_watchdog_rejects_zero_deadline():
    with pytest.raises(ValueError):
        obs.StallWatchdog(0.0)


# -- satellite: MetricsLogger scalar coercion --------------------------------

def test_metrics_logger_coerces_0d_arrays(tmp_path):
    import jax.numpy as jnp
    from dalle_tpu.train.metrics import MetricsLogger
    path = str(tmp_path / "m.jsonl")
    w = MetricsLogger(path=path)
    w.log(1, {"loss": np.float32(1.5), "zero_d": jnp.ones(()),
              "np0d": np.asarray(2.0), "plain": 3, "tag": "s",
              "flag": True, "vector": np.zeros(4)})
    w.close()
    rec = json.loads(open(path).read().strip())
    assert rec["loss"] == 1.5 and rec["zero_d"] == 1.0 and rec["np0d"] == 2.0
    assert rec["plain"] == 3 and rec["tag"] == "s" and rec["flag"] is True
    assert "vector" not in rec              # non-scalars still dropped


def test_metrics_logger_merges_obs_snapshot(tmp_path, tracer):
    from dalle_tpu.train.metrics import MetricsLogger
    obs.counter_add("obs.decode_tokens_total", 9)
    path = str(tmp_path / "m.jsonl")
    w = MetricsLogger(path=path)
    w.log(1, {"loss": 0.5})
    w.close()
    rec = json.loads(open(path).read().strip())
    assert rec["obs.decode_tokens_total"] == 9


# -- satellite: MFU only against a known peak ---------------------------------

def test_device_peak_tflops_unknown_kind_raises():
    from dalle_tpu.train import metrics as tm

    class FakeDevice:
        device_kind = "QuantumChip 9000"

    with pytest.raises(ValueError, match="QuantumChip 9000"):
        tm.device_peak_tflops(FakeDevice())


@pytest.mark.parametrize("kind,peak", [("TPU v5 lite", 197.0),
                                       ("TPU v5e", 197.0),
                                       ("TPU v5p", 459.0),
                                       ("TPU v4", 275.0)])
def test_device_peak_tflops_known_kind_gives_its_row(kind, peak):
    from dalle_tpu.train import metrics as tm

    class FakeDevice:
        device_kind = kind

    assert tm.device_peak_tflops(FakeDevice()) == peak


def test_throughput_meter_reports_mfu_for_known_kind(monkeypatch):
    from dalle_tpu.train import metrics as tm
    monkeypatch.setattr(tm, "device_peak_tflops", lambda device=None: 123.0)
    meter = tm.ThroughputMeter(8, interval=1, flops_per_step=1e9)
    time.sleep(0.01)
    rep = meter.step(2)
    assert 0 < rep["mfu"] < 1 and "mfu_estimated" not in rep


# -- graftscope: trace context (obs/context.py) ------------------------------

def test_trace_context_tags_spans_and_record_span(tracer):
    with obs.trace_context("t1"):
        with obs.span("a"):
            pass
        obs.record_span("b", time.perf_counter(), 0.01)
    with obs.span("c"):
        pass
    by = {r[0]: (r[5] or {}) for r in tracer.spans}
    assert by["a"]["trace_id"] == "t1"
    assert by["b"]["trace_id"] == "t1"
    assert "trace_id" not in by["c"]


def test_trace_context_nesting_restores_previous():
    assert obs.current_trace_id() is None
    with obs.trace_context("outer"):
        assert obs.current_trace_id() == "outer"
        with obs.trace_context("inner"):
            assert obs.current_trace_id() == "inner"
        assert obs.current_trace_id() == "outer"
    assert obs.current_trace_id() is None


def test_explicit_trace_id_wins_over_ambient(tracer):
    with obs.trace_context("ambient"):
        obs.record_span("x", time.perf_counter(), 0.0, trace_id="explicit")
        with obs.span("y", trace_id="mine"):
            pass
    by = {r[0]: r[5] for r in tracer.spans}
    assert by["x"]["trace_id"] == "explicit"
    assert by["y"]["trace_id"] == "mine"


def test_new_trace_ids_unique():
    ids = {obs.new_trace_id() for _ in range(256)}
    assert len(ids) == 256


# -- ring overflow accounting under concurrent writers -----------------------

def test_ring_overflow_accounting_concurrent_writers():
    """N writer threads hammer a tiny ring: the kept-span count equals the
    capacity and EVERY eviction is counted — dropped + kept == recorded
    exactly, even under contention (the accounting rides the record lock)."""
    obs.disable()
    tr = obs.configure(capacity=32)
    n_threads, per = 8, 200
    try:
        def worker(k):
            for i in range(per):
                with obs.span(f"w{k}"):
                    pass

        ts = [threading.Thread(target=worker, args=(k,))
              for k in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert len(tr.spans) == 32
        assert tr.dropped == n_threads * per - 32
        assert obs.metrics_snapshot()["obs.spans_dropped"] == tr.dropped
    finally:
        obs.disable()


# -- labeled counters/gauges + Prometheus rendering --------------------------

def test_labeled_counters_canonical_series_and_render(tracer):
    obs.counter_add("gw.rej_total", 1, labels={"tenant": "a", "reason": "q"})
    obs.counter_add("gw.rej_total", 2, labels={"reason": "q", "tenant": "a"})
    obs.counter_add("gw.rej_total", 1, labels={"tenant": "b", "reason": "q"})
    obs.counter_add("gw.rej_total", 5)          # unlabeled stays its own
    snap = obs.metrics_snapshot()
    assert snap['gw.rej_total{reason="q",tenant="a"}'] == 3
    assert snap['gw.rej_total{reason="q",tenant="b"}'] == 1
    assert snap["gw.rej_total"] == 5
    text = prom.render_textfile(snap)
    assert 'dalle_gw_rej_total{reason="q",tenant="a"} 3' in text
    assert 'dalle_gw_rej_total{reason="q",tenant="b"} 1' in text
    # ONE type line for the whole family (bare + labeled series share it),
    # labels never mangled into names
    assert text.count("# TYPE dalle_gw_rej_total counter") == 1
    assert "dalle_gw_rej_total_a" not in text


def test_label_values_escaped(tracer):
    obs.gauge_set("g", 1.0, labels={"k": 'a"b\\c'})
    (key,) = obs.metrics_snapshot().keys()
    assert key == 'g{k="a\\"b\\\\c"}'
    assert prom.sanitize_metric_name(key) == 'dalle_g{k="a\\"b\\\\c"}'


# -- per-request Perfetto tracks ---------------------------------------------

def test_chrome_trace_request_tracks(tmp_path, tracer):
    with obs.trace_context("req1"):
        with obs.span("s1"):
            pass
    with obs.span("untagged"):
        pass
    path = str(tmp_path / "t.json")
    obs.export_chrome_trace(path, request_tracks=True)
    doc = json.load(open(path))
    evs = doc["traceEvents"]
    req = [e for e in evs if e["pid"] == 1 and e.get("ph") == "X"]
    assert [e["name"] for e in req] == ["s1"]
    assert "source_tid" in req[0]["args"]
    meta = [e for e in evs if e.get("ph") == "M"]
    assert any(e["args"]["name"] == "request req1" for e in meta)
    # the real per-thread view keeps both spans
    real = [e for e in evs if e["pid"] != 1 and e.get("ph") == "X"]
    assert {e["name"] for e in real} == {"s1", "untagged"}


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_bundle_contents_and_delta(tmp_path, tracer):
    import os
    rec = obs.configure_recorder(str(tmp_path), min_dump_interval_s=0.0)
    try:
        obs.counter_add("x_total", 3)
        obs.record_event("failover", trace_id="t9")
        with obs.trace_context("t9"):
            with obs.span("serve/decode_row"):
                pass
        path = rec.dump("replica_death", extra={"replica_id": "r0"})
        assert os.path.basename(path).startswith("postmortem_replica_death")
        assert not [p for p in os.listdir(tmp_path)
                    if p.startswith(".tmp")]          # atomic: no staging left
        pm = json.load(open(os.path.join(path, "postmortem.json")))
        assert pm["reason"] == "replica_death"
        assert [e["kind"] for e in pm["events"]] == ["failover"]
        assert pm["events"][0]["trace_id"] == "t9"
        assert pm["extra"]["replica_id"] == "r0"
        assert pm["metrics_delta_since_last_dump"]["x_total"] == 3
        tr_doc = json.load(open(os.path.join(path, "trace.json")))
        assert any((e.get("args") or {}).get("trace_id") == "t9"
                   for e in tr_doc["traceEvents"])
        # deltas reset between dumps
        obs.counter_add("x_total", 2)
        pm2 = json.load(open(os.path.join(
            rec.dump("replica_death"), "postmortem.json")))
        assert pm2["metrics_delta_since_last_dump"]["x_total"] == 2
    finally:
        obs.disable_recorder()


def test_flight_recorder_rate_limit_and_event_bound(tmp_path):
    rec = obs.FlightRecorder(str(tmp_path), capacity=4,
                             min_dump_interval_s=60.0)
    for i in range(10):
        rec.event("e", i=i)
    assert len(rec.events) == 4 and rec.events_dropped == 6
    assert [e["i"] for e in rec.events] == [6, 7, 8, 9]   # newest kept
    assert rec.dump("stall") is not None
    assert rec.dump("stall") is None                      # rate-limited
    assert rec.dumps_suppressed == 1
    assert rec.dump("other") is not None                  # per-reason limit
    assert rec.dump("stall", force=True) is not None


def test_recorder_hooks_noop_without_recorder():
    obs.disable_recorder()
    obs.record_event("e")                 # must not raise
    assert obs.dump_recorder("r") is None


# -- state providers + watchdog snapshot -------------------------------------

def test_state_providers_collect_and_survive_errors():
    obs.register_state_provider("unit", lambda: {"q": 3})
    obs.register_state_provider("boom", lambda: 1 / 0)
    try:
        st = obs.collect_state()
        assert st["unit"] == {"q": 3}
        assert "provider error" in st["boom"]
    finally:
        obs.unregister_state_provider("unit")
        obs.unregister_state_provider("boom")
    assert "unit" not in obs.collect_state()


def test_watchdog_report_includes_serve_state():
    obs.register_state_provider("serve.engine[t]",
                                lambda: {"queue_depth": 7, "inflight": []})
    logs = []
    wd = obs.StallWatchdog(0.05, log=logs.append, poll_s=0.01,
                           dump_stacks=False).start()
    try:
        time.sleep(0.25)
    finally:
        wd.stop()
        obs.unregister_state_provider("serve.engine[t]")
    assert wd.stall_count >= 1
    assert wd.last_report.state["serve.engine[t]"]["queue_depth"] == 7
    assert "queue_depth" in logs[0]


def test_watchdog_stall_dumps_flight_bundle(tmp_path):
    import os
    obs.configure_recorder(str(tmp_path), min_dump_interval_s=0.0)
    try:
        wd = obs.StallWatchdog(0.05, log=lambda *_: None, poll_s=0.01,
                               dump_stacks=False).start()
        try:
            time.sleep(0.25)
        finally:
            wd.stop()
        assert [p for p in os.listdir(tmp_path)
                if p.startswith("postmortem_watchdog_stall")]
    finally:
        obs.disable_recorder()


# -- SLO burn-rate sentry ----------------------------------------------------

def test_burn_rate_sentry_multiwindow_breach_and_recovery(tracer):
    t = [0.0]
    breaches = []
    s = obs.BurnRateSentry(objective=0.99,
                           windows=((10.0, 2.0), (100.0, 2.0)),
                           min_events=4, on_breach=breaches.append,
                           clock=lambda: t[0])
    for _ in range(8):                    # healthy traffic: no burn
        t[0] += 0.5
        s.record(True)
    assert not s.burning and breaches == []
    for _ in range(4):                    # outage: 4/12 bad, burn 33x >= 2x
        t[0] += 0.5
        s.record(False, reason="quota")
    assert s.burning
    assert len(breaches) == 1             # exactly one ok->burning edge
    assert breaches[0]["burning"] and breaches[0]["dominating"] in ("10s",
                                                                    "100s")
    snap = obs.metrics_snapshot()
    assert snap["slo.burning"] == 1.0
    assert snap['slo.burn_rate{window="10s"}'] >= 2.0
    assert snap['slo.bad_events_total{reason="quota"}'] == 4
    # recovery: the short window drains of bad events -> multi-window AND
    # stops paging even though the long window still remembers the outage
    for _ in range(12):
        t[0] += 1.0
        s.record(True)
    assert not s.burning
    v = s.evaluate()
    w = {r["window"]: r for r in v["windows"]}
    assert w["10s"]["bad"] == 0 and w["100s"]["bad"] == 4
    assert not w["10s"]["burning"] and w["100s"]["burning"]
    assert len(breaches) == 1             # no re-fire without a new edge


def test_burn_rate_sentry_cold_start_never_pages(tracer):
    s = obs.BurnRateSentry(min_events=10, clock=lambda: 0.0)
    for _ in range(5):
        s.record(False, reason="quota")   # 100% errors but < min_events
    assert not s.burning


def test_window_label():
    from dalle_tpu.obs.slo import window_label
    assert window_label(300) == "5m"
    assert window_label(3600) == "1h"
    assert window_label(45) == "45s"


# -- request timeline reassembly ---------------------------------------------

def test_request_timeline_cross_thread_order():
    rows = [
        {"name": "gateway/sse_flush", "ts": 3.0, "dur_s": 0.1, "tid": 2,
         "args": {"trace_id": "rq"}},
        {"name": "serve/request_queue_wait", "ts": 1.0, "dur_s": 0.5,
         "tid": 1, "args": {"trace_id": "rq"}},
        {"name": "other", "ts": 1.5, "dur_s": 0.1, "tid": 1,
         "args": {"trace_id": "zz"}},
        {"name": "serve/prefill", "ts": 2.0, "dur_s": 0.3, "tid": 1,
         "args": {"trace_id": "rq", "mode": "window"}},
    ]
    tl = obs.request_timeline(rows, "rq")
    assert [e["name"] for e in tl] == ["serve/request_queue_wait",
                                      "serve/prefill", "gateway/sse_flush"]
    assert tl[0]["t_rel_s"] == 0.0
    assert tl[1]["t_rel_s"] == 1.0 and tl[2]["tid"] == 2
    text = obs.format_request_timeline(rows, "rq")
    assert "2 thread(s)" in text and "serve/prefill" in text
    assert obs.format_request_timeline(rows, "nope").startswith("(no spans")
    # engine-only runs match by integer request_id
    rows_id = [{"name": "serve/request", "ts": 1.0, "dur_s": 0.1, "tid": 1,
                "args": {"request_id": 7}}]
    assert [e["name"] for e in obs.request_timeline(rows_id, "7")] \
        == ["serve/request"]


def test_report_slo_verdict_line(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({
            "step": 0, "gateway.inflight": 0.0,
            'slo.burn_rate{window="5m"}': 120.0,
            'slo.burn_threshold{window="5m"}': 14.4,
            'slo.burn_rate{window="1h"}': 20.0,
            'slo.burn_threshold{window="1h"}': 14.4,
            "slo.burning": 1.0}) + "\n")
    text = obs_report.summarize_run(path)
    assert "slo burn rate" in text
    assert "BURNING (dominating window 5m)" in text
    with open(path, "w") as fh:
        fh.write(json.dumps({
            "step": 0, "gateway.inflight": 0.0,
            'slo.burn_rate{window="5m"}': 0.0,
            'slo.burn_threshold{window="5m"}': 14.4,
            "slo.burning": 0.0}) + "\n")
    assert "→ ok" in obs_report.summarize_run(path)


def test_report_gateway_by_tenant_parses_labeled_counters(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({
            "step": 0, "gateway.inflight": 1.0,
            "gateway.rejected_total": 3.0,
            'gateway.rejected_by_total{reason="quota",tenant="capped"}': 2.0,
            'gateway.rejected_by_total{reason="slo",tenant="best"}': 1.0,
        }) + "\n")
    gw = obs_report.gateway_accounting(
        obs_report.load_jsonl(path), [])
    assert gw["by_tenant"] == {"capped": 2, "best": 1}
    assert gw["verdict"] == "ADMISSION-LIMITED"


def test_report_paged_kv_hit_rate_and_verdict(tmp_path):
    """graftpage section: pool gauges + mode-tagged prefill spans render
    the radix hit-rate line; the verdict flips on tokens actually served
    from cache, and dense-slab runs get no section at all."""
    path = str(tmp_path / "metrics.jsonl")
    with open(path, "w") as fh:
        fh.write(json.dumps({
            "step": 0, "kv.pages_free": 10.0, "kv.pages_used": 14.0,
            "kv.pages_shared": 3.0, "kv.pages_cow_copies": 2.0,
            "kv.prefix_hit_tokens_total": 21.0}) + "\n")
        for mode in ("paged-hit", "paged-hit", "paged-partial", "paged"):
            fh.write(json.dumps({
                "name": "serve/prefill", "t0_rel_s": 0.0, "dur_s": 0.01,
                "trace_id": "t", "depth": 0,
                "args": {"mode": mode}}) + "\n")
    text = obs_report.summarize_run(path)
    assert "paged KV (graftpage)" in text
    assert "radix hit-rate 75% over 4 admissions (2 full, 1 partial)" in text
    assert "21 prompt tokens served from cache" in text
    assert "PAGED-KV: prefix-sharing" in text

    with open(path, "w") as fh:
        fh.write(json.dumps({
            "step": 0, "kv.pages_free": 0.0, "kv.pages_used": 24.0,
            "kv.prefix_hit_tokens_total": 0.0}) + "\n")
    cold = obs_report.summarize_run(path)
    assert "PAGED-KV: cold" in cold

    with open(path, "w") as fh:
        fh.write(json.dumps({"step": 0, "gateway.inflight": 0.0}) + "\n")
    assert "paged KV" not in obs_report.summarize_run(path)


# -- SIGUSR2 on-demand profiler (scripts/_common.py, PR 8 satellite) --------

def _load_common():
    import importlib.util
    import os as _os
    import sys as _sys
    scripts = _os.path.join(_os.path.dirname(_os.path.dirname(
        _os.path.abspath(__file__))), "scripts")
    if scripts not in _sys.path:
        _sys.path.insert(0, scripts)
    spec = importlib.util.spec_from_file_location(
        "_common_under_test", _os.path.join(scripts, "_common.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_sigusr2_profiler_bounded_single_capture(monkeypatch, tmp_path):
    """The handler must start exactly ONE bounded capture even when a
    second signal lands mid-capture, and the timer must stop it exactly
    once — a profiler left running fills the disk, which is the failure
    the bound exists to prevent."""
    import signal
    import types
    import jax
    _common = _load_common()
    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop", calls["stop"] + 1))
    prev = signal.getsignal(signal.SIGUSR2)
    try:
        args = types.SimpleNamespace(profiler_dir=None,
                                     profiler_capture_s=0.15)
        assert _common.install_sigusr2_profiler(str(tmp_path), args)
        handler = signal.getsignal(signal.SIGUSR2)
        assert callable(handler)
        handler(signal.SIGUSR2, None)
        # concurrent second signal while the capture is active: ignored
        # (one capture at a time — the active latch, not a second trace)
        handler(signal.SIGUSR2, None)
        assert calls["start"] == 1
        deadline = time.time() + 5.0
        while calls["stop"] == 0 and time.time() < deadline:
            time.sleep(0.01)
        assert calls["stop"] == 1, "bounded capture did not stop"
        assert calls["start"] == 1
        # capture dirs are timestamped under the target dir
        assert any(n.startswith("profile_") for n in os.listdir(str(tmp_path)))
    finally:
        signal.signal(signal.SIGUSR2, prev)


def test_sigusr2_profiler_rearms_after_stop(monkeypatch, tmp_path):
    import signal
    import types
    import jax
    _common = _load_common()
    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda path: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop", calls["stop"] + 1))
    prev = signal.getsignal(signal.SIGUSR2)
    try:
        args = types.SimpleNamespace(profiler_dir=None,
                                     profiler_capture_s=0.05)
        assert _common.install_sigusr2_profiler(str(tmp_path), args)
        handler = signal.getsignal(signal.SIGUSR2)
        handler(signal.SIGUSR2, None)
        deadline = time.time() + 5.0
        while calls["stop"] == 0 and time.time() < deadline:
            time.sleep(0.01)
        handler(signal.SIGUSR2, None)   # a NEW capture after the stop
        assert calls["start"] == 2
    finally:
        signal.signal(signal.SIGUSR2, prev)


def test_sigusr2_profiler_disabled_via_flag(tmp_path):
    import types
    _common = _load_common()
    args = types.SimpleNamespace(profiler_dir="off", profiler_capture_s=1.0)
    assert _common.install_sigusr2_profiler(str(tmp_path), args) is False
