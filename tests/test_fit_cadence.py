"""BaseTrainer.fit cadence paths that had no coverage — profile_step with
scan_steps > 1, the SIGUSR1 signal-save latch, log_artifacts firing only on
save boundaries, the loss-less NaN guard — plus the grafttrace step
breakdown and watchdog integration. A host-only FakeTrainer keeps every test
free of model compiles (the loop logic under test is pure host code)."""

import json
import os
import signal
import time

import numpy as np
import pytest

from dalle_tpu import obs
from dalle_tpu.config import DVAEConfig, ObsConfig, TrainConfig
from dalle_tpu.train.base_trainer import BaseTrainer
from dalle_tpu.train.metrics import ThroughputMeter


@pytest.fixture(autouse=True)
def _obs_off_after():
    """fit(obs.trace=True) enables the global tracer; tests must not leak it
    into other modules (MetricsLogger merges the snapshot into every log)."""
    yield
    obs.disable()


class RecordingCkpt:
    def __init__(self):
        self.saves = []
        self.preflights = 0

    def preflight(self, state, meta=None):
        self.preflights += 1

    def save(self, step, state, meta=None):
        self.saves.append(step)

    def latest_step(self):
        return self.saves[-1] if self.saves else None


class RecordingWriter:
    def __init__(self):
        self.records = []
        self.artifacts = []

    def log(self, step, metrics):
        self.records.append((step, dict(metrics)))

    def log_artifact(self, path, name, metadata=None):
        self.artifacts.append((path, name, dict(metadata or {})))


class FakeTrainer(BaseTrainer):
    """The fit() shell over a metrics-dict-producing fake step: no mesh, no
    model, no device program — cadence/obs logic only."""

    model_class = "Fake"

    def __init__(self, tc: TrainConfig, *, step_metrics=None, step_sleep=0.0):
        self.train_cfg = tc
        self.model_cfg = DVAEConfig()
        self.ckpt = RecordingCkpt()
        self.meter = ThroughputMeter(tc.batch_size, tc.log_every)
        self.extra_meta = {}
        self.state = None          # fit() returns it; no device state here
        self._last_good = None
        self._host_step = 0
        self._obs_dispatch_t0 = None
        self._obs_last_wait = 0.0
        self._obs_wait_accum = 0.0
        self._obs_window_t0 = None
        self.last_watchdog = None
        self.rollbacks = 0
        self.rolled_back_at = []
        self.single_calls = 0
        self.scan_calls = []
        self._step_metrics = step_metrics or (
            lambda step: {"loss": np.float32(0.25)})
        self._step_sleep = step_sleep

    def train_step(self, x):
        self.single_calls += 1
        if self._step_sleep:
            time.sleep(self._step_sleep)
        return self._finish_step(self._step_metrics(self._host_step))

    def train_steps(self, xs):
        k = xs.shape[0]
        self.scan_calls.append(k)
        self._host_step += k - 1
        return self._finish_step(self._step_metrics(self._host_step))

    def _snapshot_good(self):
        pass                       # nothing on a device to copy

    def _rollback(self):
        """The real bookkeeping (the unfetched records die with the state);
        with no snapshot there is nothing to restore, so mark the state."""
        self.rollbacks += 1
        self.rolled_back_at.append(self._host_step)
        super()._rollback()
        self.state = "restored"


def _tc(tmp_path, **kw):
    kw.setdefault("preflight_checkpoint", False)
    kw.setdefault("batch_size", 4)
    kw.setdefault("log_every", 1)
    return TrainConfig(checkpoint_dir=str(tmp_path), **kw)


def _batches(n, shape=(4, 8)):
    return iter([(np.zeros(shape, np.float32),) for _ in range(n)])


# -- profile_step window with scan_steps > 1 ---------------------------------

def test_profile_step_inside_scan_group(tmp_path, monkeypatch):
    """profile_step=3 with k=2 groups: steps (0,1) unprofiled, the (2,3)
    group CONTAINS step 3 and must be the one traced — the window check is
    prev < profile_step <= prev+k, not equality on a step the scan never
    stops at. (The profiler is stubbed — a real jax.profiler.trace costs
    ~18s on CPU; the slow-tier variant below exercises it for real.)"""
    import contextlib

    import jax
    traced = []

    @contextlib.contextmanager
    def fake_trace(logdir):
        traced.append(logdir)
        yield

    monkeypatch.setattr(jax.profiler, "trace", fake_trace)
    tc = _tc(tmp_path, scan_steps=2, profile_step=3)
    tr = FakeTrainer(tc)
    logs = []
    tr.fit(_batches(4), log=logs.append)
    assert tr.scan_calls == [2, 2]
    assert traced == [f"{tc.checkpoint_dir}/profile_step3"]   # one group only
    profile_lines = [l for l in logs if l.startswith("[profile]")]
    assert len(profile_lines) == 1 and "profile_step3" in profile_lines[0]


@pytest.mark.slow
def test_profile_step_real_profiler(tmp_path):
    """The unstubbed path: jax.profiler.trace really engages and leaves a
    trace directory behind (~18s on CPU → slow tier)."""
    tc = _tc(tmp_path, scan_steps=2, profile_step=3)
    tr = FakeTrainer(tc)
    tr.fit(_batches(4), log=lambda *a: None)
    assert os.path.isdir(f"{tc.checkpoint_dir}/profile_step3")


def test_profile_step_skipped_when_past_window(tmp_path, monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "trace",
                        lambda logdir: pytest.fail("profiler engaged outside "
                                                   "the profile_step window"))
    tc = _tc(tmp_path, scan_steps=2, profile_step=100)
    tr = FakeTrainer(tc)
    logs = []
    tr.fit(_batches(4), log=logs.append)
    assert not [l for l in logs if l.startswith("[profile]")]


# -- SIGUSR1 signal-save latch ------------------------------------------------

def test_sigusr1_saves_at_next_boundary_then_clears(tmp_path):
    """The handler only sets a flag; the save lands at the NEXT step
    boundary, exactly once, and the latch clears (taming's melk handler)."""
    tc = _tc(tmp_path, save_every_steps=0)   # no periodic saves
    tr = FakeTrainer(tc)
    tr.install_signal_checkpoint(log=lambda *a: None)
    os.kill(os.getpid(), signal.SIGUSR1)
    assert tr._signal_save                   # latched, nothing saved yet
    assert tr.ckpt.saves == []
    tr.fit(_batches(3), log=lambda *a: None)
    assert tr.ckpt.saves == [1]              # first boundary only
    assert tr._signal_save is False


def test_sigusr1_save_on_metrics_skipped_step(tmp_path):
    """Signal save landing on a metrics_every-skipped step must still fetch
    pending metrics (nothing is checkpointed without a NaN check)."""
    tc = _tc(tmp_path, save_every_steps=0, metrics_every=4)
    tr = FakeTrainer(tc)
    tr.install_signal_checkpoint(log=lambda *a: None)
    os.kill(os.getpid(), signal.SIGUSR1)
    writer = RecordingWriter()
    tr.fit(_batches(2), log=lambda *a: None, metrics_writer=writer)
    assert tr.ckpt.saves == [1]
    # step 1 is metrics-skipped (4∤1) but the save forced the on-demand fetch
    assert writer.records and writer.records[0][0] == 1
    assert writer.records[0][1]["loss"] == pytest.approx(0.25)


# -- log_artifacts fires only on save boundaries ------------------------------

def test_log_artifacts_only_on_save_boundaries(tmp_path):
    tc = _tc(tmp_path, save_every_steps=2, log_artifacts=True)
    tr = FakeTrainer(tc)
    writer = RecordingWriter()
    tr.fit(_batches(5), log=lambda *a: None, metrics_writer=writer)
    assert tr.ckpt.saves == [2, 4]
    assert [a[2]["step"] for a in writer.artifacts] == [2, 4]
    assert all(a[1] == "trained-fake" for a in writer.artifacts)
    # metrics flow every step regardless of artifact cadence
    assert [s for s, _ in writer.records] == [1, 2, 3, 4, 5]


def test_no_artifacts_without_flag(tmp_path):
    tc = _tc(tmp_path, save_every_steps=2, log_artifacts=False)
    tr = FakeTrainer(tc)
    writer = RecordingWriter()
    tr.fit(_batches(4), log=lambda *a: None, metrics_writer=writer)
    assert tr.ckpt.saves == [2, 4] and writer.artifacts == []


# -- NaN guard without a 'loss' key (satellite) -------------------------------

def test_nan_guard_falls_back_to_first_scalar(tmp_path):
    """No 'loss' key: the first finite-checkable scalar drives the check —
    a NaN there still rolls back instead of KeyErroring the loop."""
    metrics = {3: {"accuracy": float("nan")}}
    tr = FakeTrainer(_tc(tmp_path), step_metrics=lambda step: dict(
        metrics.get(step, {"accuracy": 0.9})))
    tr.fit(_batches(5), log=lambda *a: None)
    assert tr.rollbacks == 1


def test_nan_guard_warns_once_when_nothing_checkable(tmp_path):
    # log_every=0: the [step N] line formats floats only; this test's
    # string-valued metrics would break it (strings never reach it in the
    # real flow — _finish_step float()s everything)
    tr = FakeTrainer(_tc(tmp_path, log_every=0),
                     step_metrics=lambda step: {"tag": "hello"})
    # bypass _finish_step's float() coercion: return the dict directly
    tr._finish_step = lambda m: (
        setattr(tr, "_host_step", tr._host_step + 1) or m)
    logs = []
    tr.fit(_batches(4), log=logs.append)
    warns = [l for l in logs if "finite-checkable" in l]
    assert len(warns) == 1                   # once, not per step
    assert tr.rollbacks == 0


# -- the late metrics fetch: fit()'s loop logic (host-only; the real trainer's
# run of it lives in test_overlap.py) ----------------------------------------

def test_save_on_skipped_step_keeps_writer_monotonic(tmp_path):
    """A save boundary landing on a metrics-skipped step must flush the
    OLDER parked record before writing its own — wandb silently drops
    out-of-order steps, so writer steps must stay monotonic."""
    tc = _tc(tmp_path, metrics_every=3, save_every_steps=5)
    tr = FakeTrainer(tc)
    w = RecordingWriter()
    tr.fit(_batches(7), log=lambda *a: None, metrics_writer=w)
    steps = [s for s, _ in w.records]
    assert steps == sorted(steps), steps
    # parked step-3 record flushed at the step-5 save, save record present,
    # final parked boundary (6) flushed at fit exit
    assert steps == [3, 5, 6]
    assert tr.ckpt.saves == [5]


def test_breakdown_survives_coinciding_save_cadence(tmp_path):
    """save_every == metrics_every: every boundary is fetched in band; the
    parked breakdown must go into that record, not be dropped with the
    parked entry."""
    tc = _tc(tmp_path, metrics_every=1, save_every_steps=1)
    tr = FakeTrainer(tc)
    w = RecordingWriter()
    tr.fit(_batches(3), log=lambda *a: None, metrics_writer=w)
    assert [s for s, _ in w.records] == [1, 2, 3]
    assert all("t_batch_wait_s" in m for _, m in w.records), w.records


def test_fit_writes_each_step_once_in_order_the_last_at_exit(tmp_path):
    """One record a step under its true step number, in increasing order;
    the writer lags the loop (step N's record is not written before step
    N+1 is dispatched), and the last record comes from the flush at exit."""
    tr = FakeTrainer(_tc(tmp_path),
                     step_metrics=lambda step: {"loss": np.float32(step + 1)})
    w = RecordingWriter()
    written_at = {}
    logs = []
    tr.fit(_batches(5), log=logs.append, metrics_writer=w,
           on_step=lambda step: written_at.update(
               {step: [s for s, _ in w.records]}))
    assert [s for s, _ in w.records] == [1, 2, 3, 4, 5]
    assert [m["loss"] for _, m in w.records] == [1.0, 2.0, 3.0, 4.0, 5.0]
    assert all(step not in written for step, written in written_at.items())
    assert written_at[5] == [1, 2, 3]      # 4 is fetched, not yet written
    assert "[fit] metrics fetches: 4 late" in logs[-1]
    assert "1 in band" in logs[-1]


@pytest.mark.parametrize("nan_steps,n,written,rolled_back_at", [
    # NaN at step 3, read once step 4 (which ran on the poisoned state, so
    # NaN as well) is dispatched: one rollback, 4's parked record dropped
    ((3, 4), 6, [1, 2, 5, 6], [4]),
    # NaN at the last step: the exit flush finds it
    ((5,), 5, [1, 2, 3, 4], [5]),
], ids=["mid_run", "last_step"])
def test_nan_read_one_step_late_rolls_back_once(tmp_path, nan_steps, n,
                                                written, rolled_back_at):
    tr = FakeTrainer(_tc(tmp_path), step_metrics=lambda step: {
        "loss": np.float32("nan" if step + 1 in nan_steps else 0.5)})
    w = RecordingWriter()
    state = tr.fit(_batches(n), log=lambda *a: None, metrics_writer=w)
    assert tr.rolled_back_at == rolled_back_at
    assert [s for s, _ in w.records] == written
    assert state == "restored"             # what fit() returns is not NaN's


def test_save_boundary_fetches_its_step_before_the_save(tmp_path):
    """Nothing is checkpointed without the NaN check of the state saved: at
    a save boundary the current step's record is fetched (in band) and
    written before ckpt.save, and a NaN there skips the save."""
    tc = _tc(tmp_path, save_every_steps=2,
             obs=ObsConfig(trace=True, trace_dir=str(tmp_path / "obs")))
    tr = FakeTrainer(tc, step_metrics=lambda step: {
        "loss": np.float32("nan" if step + 1 == 4 else 0.5)})
    w = RecordingWriter()
    seen = {}
    save = tr.ckpt.save

    def save_after_its_record(step, state, meta=None):
        seen[step] = [s for s, _ in w.records]
        save(step, state, meta)

    tr.ckpt.save = save_after_its_record
    tr.fit(_batches(6), log=lambda *a: None, metrics_writer=w)
    assert seen == {2: [1, 2], 6: [1, 2, 3, 5, 6]}
    assert tr.ckpt.saves == [2, 6] and tr.rolled_back_at == [4]
    counters = obs.metrics_snapshot()
    # late: 1 (at step 2), 3 (at 4), 5 (at 6); in band: the boundaries 2, 4, 6
    assert counters["fit.fetch_late"] == 3
    assert counters["fit.fetch_in_band"] == 3


def test_bare_train_step_returns_its_own_metrics(tmp_path):
    """Outside fit() a step is fetched in band, before and after a fit()."""
    tr = FakeTrainer(_tc(tmp_path, log_every=100),     # no meter report
                     step_metrics=lambda step: {"loss": np.float32(step + 1)})
    x = np.zeros((4, 8), np.float32)
    assert tr.train_step(x) == {"loss": 1.0}
    tr.fit(_batches(2), log=lambda *a: None)
    assert tr._parked is None
    assert tr.train_step(x) == {"loss": 4.0}
    assert tr.train_steps(np.zeros((2, 4, 8), np.float32)) == {"loss": 6.0}


# -- grafttrace integration ---------------------------------------------------

def test_fit_emits_step_breakdown_and_starvation(tmp_path):
    """A slow iterator + fast step must show up as a high data_starvation
    ratio with the full wait/dispatch/sync split in every metrics record.
    (device_prefetch off: the prefetcher front-loads the slow pulls, which
    is the point of PR3 — this test pins the un-overlapped breakdown.)"""
    tc = _tc(tmp_path, device_prefetch=0, obs=ObsConfig(device_poll_every=1))

    def slow_batches():
        for _ in range(4):
            time.sleep(0.03)
            yield (np.zeros((4, 8), np.float32),)

    tr = FakeTrainer(tc)
    writer = RecordingWriter()
    tr.fit(slow_batches(), log=lambda *a: None, metrics_writer=writer)
    _, m = writer.records[-1]
    for col in ("t_batch_wait_s", "t_dispatch_s", "t_sync_s",
                "data_starvation", "hbm_bytes_in_use", "compiles_total"):
        assert col in m, col
    assert m["t_batch_wait_s"] >= 0.02
    # input-bound by construction, in every window between two fetches (the
    # flush at exit closes one that holds no batch wait)
    assert all(m["data_starvation"] > 0.5 for _, m in writer.records[:-1])


def test_fit_compute_bound_low_starvation(tmp_path):
    tr = FakeTrainer(_tc(tmp_path), step_sleep=0.03)
    writer = RecordingWriter()
    tr.fit(_batches(3), log=lambda *a: None, metrics_writer=writer)
    assert writer.records[-1][1]["data_starvation"] < 0.2


def test_fit_exports_trace_with_nested_spans(tmp_path):
    outdir = tmp_path / "obs"
    tc = _tc(tmp_path, obs=ObsConfig(trace=True, trace_dir=str(outdir)))
    tr = FakeTrainer(tc)
    tr.fit(_batches(3), log=lambda *a: None)
    doc = json.load(open(outdir / "trace.json"))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"fit/warmup", "fit/step", "fit/batch_wait", "fit/dispatch",
            "fit/sync", "fit/after_step"} <= names
    # the phases are children of their fit/step, in time and by parent id
    steps = {e["args"]["id"]: (e["ts"], e["ts"] + e["dur"])
             for e in doc["traceEvents"] if e["name"] == "fit/step"}
    for e in doc["traceEvents"]:
        if e["name"] in ("fit/batch_wait", "fit/dispatch", "fit/sync",
                         "fit/after_step"):
            if e["args"]["parent"] is None:       # the flush at fit()'s exit
                assert e["name"] == "fit/sync" and not e["args"]["late"]
                continue
            lo, hi = steps[e["args"]["parent"]]
            assert lo <= e["ts"] and e["ts"] + e["dur"] <= hi + 1
    rows = [json.loads(l) for l in open(outdir / "spans.jsonl")]
    assert any(r["name"] == "fit/sync" for r in rows)
    # fit/warmup: from fit()'s entry to the return of the first step's
    # dispatch, closed before that step's fit/after_step opens; the first
    # fetch comes an iteration later
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    warm, = by_name["fit/warmup"]
    first = {name: min(by_name[name], key=lambda r: r["rel_s"])
             for name in ("fit/dispatch", "fit/sync", "fit/after_step")}
    assert warm["rel_s"] <= by_name["fit/step"][0]["rel_s"]
    warm_end = warm["rel_s"] + warm["dur_s"]
    assert (first["fit/dispatch"]["rel_s"] + first["fit/dispatch"]["dur_s"]
            <= warm_end <= first["fit/after_step"]["rel_s"])
    assert warm_end <= first["fit/sync"]["rel_s"]


@pytest.mark.parametrize("save_every", [1, 0],
                         ids=["in_band_at_saves", "late"])
def test_fit_phases_tile_the_step_and_feed_the_record(tmp_path, save_every):
    """Over a 5-step fit(): fit/dispatch and fit/sync are siblings that never
    overlap; per ``step`` id the phase spans add up to their fit/step span
    within 1 %; and each record's t_* columns ARE the durations of the spans
    of the step it describes (t_after_s: of the iteration before it). The
    sync that fetches step N starts after the dispatch of step N+1 has ended
    (late), but for a save boundary, which fetches its own step (in band);
    the counters say how many of each."""
    outdir = tmp_path / "obs"
    tc = _tc(tmp_path, device_prefetch=0, save_every_steps=save_every,
             obs=ObsConfig(trace=True, trace_dir=str(outdir)))
    tr = FakeTrainer(tc, step_sleep=0.02)
    writer = RecordingWriter()
    tr.fit(_batches(5), log=lambda *a: None, metrics_writer=writer)
    counters = obs.metrics_snapshot()
    rows = [json.loads(l) for l in open(outdir / "spans.jsonl")]
    phases = ("fit/batch_wait", "fit/dispatch", "fit/sync", "fit/after_step")
    by_step = {}
    for r in rows:
        # (the last record's flush runs after the loop: a fit/sync with no
        # step)
        if (r["name"] in phases or r["name"] == "fit/step") and "step" in r["args"]:
            by_step.setdefault(r["args"]["step"], {}).setdefault(
                r["name"], []).append(r)
    # the sixth iteration only finds the iterator at its end
    assert sorted(by_step.pop(5)) == ["fit/batch_wait", "fit/step"]
    assert sorted(by_step) == [0, 1, 2, 3, 4]
    for step, spans in by_step.items():
        whole, = spans["fit/step"]
        parts = [r for name in phases for r in spans.get(name, [])]
        assert all(r["parent"] == whole["id"] for r in parts)
        assert sum(r["dur_s"] for r in parts) == pytest.approx(
            whole["dur_s"], rel=0.01)
        parts.sort(key=lambda r: r["rel_s"])      # siblings never overlap
        for a, b in zip(parts, parts[1:]):
            assert a["rel_s"] + a["dur_s"] <= b["rel_s"]
    assert len(writer.records) == 5
    for mstep, m in writer.records:
        spans = by_step[mstep - 1]                # the step it describes
        assert m["t_batch_wait_s"] == spans["fit/batch_wait"][0]["dur_s"]
        assert m["t_dispatch_s"] == spans["fit/dispatch"][0]["dur_s"]
        assert m["t_dispatch_s"] >= 0.02          # the step, not the sync
        if mstep >= 2:
            before = by_step[mstep - 2]["fit/after_step"]
            assert m["t_after_s"] == pytest.approx(
                sum(r["dur_s"] for r in before))
        else:
            assert "t_after_s" not in m
    syncs = [r for r in rows if r["name"] == "fit/sync"]
    assert len(syncs) == 5
    if save_every:
        # every boundary saves: each record by the sync of its own iteration
        for mstep, m in writer.records:
            sync, = by_step[mstep - 1]["fit/sync"]
            assert m["t_sync_s"] == sync["dur_s"] and not sync["args"]["late"]
        assert "fit.fetch_late" not in counters
        assert counters["fit.fetch_in_band"] == 5
    else:
        for mstep, m in writer.records[:-1]:
            # step N's record by the sync of the iteration that dispatched
            # step N+1, once that dispatch had ended
            sync, = by_step[mstep]["fit/sync"]
            dispatch, = by_step[mstep]["fit/dispatch"]
            assert m["t_sync_s"] == sync["dur_s"] and sync["args"]["late"]
            assert dispatch["rel_s"] + dispatch["dur_s"] <= sync["rel_s"]
        assert "fit/sync" not in by_step[0]       # nothing to fetch yet
        flush, = [r for r in syncs if "step" not in r["args"]]
        assert writer.records[-1][1]["t_sync_s"] == flush["dur_s"]
        assert not flush["args"]["late"]
        assert counters["fit.fetch_late"] == 4
        assert counters["fit.fetch_in_band"] == 1


def test_fit_phase_durations_need_no_ring(tmp_path):
    """The record's t_* columns and the totals with tracing off."""
    obs.disable()
    obs.reset_phase_totals()
    tr = FakeTrainer(_tc(tmp_path, device_prefetch=0), step_sleep=0.01)
    writer = RecordingWriter()
    tr.fit(_batches(3), log=lambda *a: None, metrics_writer=writer)
    assert not obs.enabled()
    assert all(m["t_dispatch_s"] >= 0.01 and m["t_sync_s"] >= 0
               for _, m in writer.records)
    totals = obs.phase_totals()
    assert totals["fit/dispatch"][0] == 3 and totals["fit/warmup"][0] == 1
    assert totals["fit/step"][0] == 4              # 3 steps + the end
    assert sum(m["t_dispatch_s"] for _, m in writer.records) == pytest.approx(
        totals["fit/dispatch"][1])
    assert tr._fit_phase is None and tr._fit_warmup is None
    obs.reset_phase_totals()


def test_fit_closes_its_spans_when_a_step_raises(tmp_path):
    obs.disable()
    tc = _tc(tmp_path, device_prefetch=0)

    def boom(step):
        raise RuntimeError("step failed")

    tr = FakeTrainer(tc, step_metrics=boom)
    with pytest.raises(RuntimeError):
        tr.fit(_batches(2), log=lambda *a: None)
    assert obs.open_spans() == {}
    assert tr._fit_phase is None and tr._fit_warmup is None


def test_fit_watchdog_fires_on_stalled_step(tmp_path):
    """A deliberately stalled fake step (sleep ≫ deadline) triggers the
    stall report mid-fit; the report names the open dispatch span."""
    tc = _tc(tmp_path, obs=ObsConfig(
        trace=True, watchdog_deadline_s=0.08))
    logs = []
    tr = FakeTrainer(tc, step_sleep=0.4)
    tr.fit(_batches(2), log=logs.append)
    wd = tr.last_watchdog
    assert wd is not None and wd.stall_count >= 1
    assert any("fit/dispatch" in " > ".join(v)
               for v in wd.last_report.open_spans.values())
    assert any("STALL" in l for l in logs)


def test_fit_writes_prometheus_textfile(tmp_path):
    prom_path = str(tmp_path / "metrics" / "dalle.prom")
    tc = _tc(tmp_path, obs=ObsConfig(trace=True, device_poll_every=1,
                                     prometheus_path=prom_path,
                                     trace_dir=str(tmp_path / "obs")))
    tr = FakeTrainer(tc)
    tr.fit(_batches(3), log=lambda *a: None)
    content = open(prom_path).read()
    assert "dalle_hbm_bytes_in_use" in content
    assert "dalle_t_dispatch_s" in content
    assert "dalle_host_step 3" in content
    assert "# TYPE dalle_compiles_total counter" in content


def test_fit_watchdog_quiet_on_healthy_run(tmp_path):
    tc = _tc(tmp_path, obs=ObsConfig(watchdog_deadline_s=30.0))
    tr = FakeTrainer(tc)
    tr.fit(_batches(5), log=lambda *a: None)
    assert tr.last_watchdog.stall_count == 0
