"""Shared trainer shell: mesh resolution, host step/rng bookkeeping, the fit
loop with NaN rollback (reference fork vae.py:100-110 / dalle.py:148-151),
preflight + periodic checkpointing with rotation (legacy/train_dalle.py:547-594),
and throughput metering — one implementation for every model family."""

from __future__ import annotations

import contextlib
import math
import os
import time
from typing import Callable, Optional

import jax
import numpy as np

from ..chaos import step_hook as _chaos_step_hook
from ..config import TrainConfig
from ..obs import (DeviceTelemetry, StallWatchdog, counter_add,
                   export_chrome_trace, export_spans_jsonl, span)
from ..obs import configure as obs_configure
from ..obs.device import capture_program
from .checkpoints import CheckpointManager


def _fmt_metrics(m: dict) -> str:
    """One-line metric rendering for fit()'s log: numbers get %.5g, the
    graftpulse breach columns (strings: detector/group names) print as-is."""
    return " ".join(
        f"{k}={v:.5g}" if isinstance(v, (int, float))
        and not isinstance(v, bool) else f"{k}={v}"
        for k, v in m.items())


@jax.jit
def _tree_copy(t):
    """Bit-exact on-device copy with FRESH buffers: ``jnp.copy`` is never
    input-forwarded by jit, so the result survives a later donation of the
    source (the whole point of the device rollback snapshot). Module-level:
    one jit cache shared by every trainer — equal tree structures compile
    once per process, not once per trainer instance."""
    import jax.numpy as jnp
    return jax.tree.map(jnp.copy, t)


class BaseTrainer:
    """Owns (mesh, state, step fn, checkpoints, meter). Subclasses set
    ``self.state``, ``self.step_fn``-driven ``train_step``, and
    ``model_class`` for checkpoint metadata."""

    model_class = "Model"

    # class-level defaults so duck-typed subclasses that skip __init__ (the
    # test suite's host-only FakeTrainer) still satisfy the fit()/breakdown
    # machinery added after them
    _last_good_device = None
    # what fit() has not fetched yet: the newest metrics boundary as (step,
    # device metrics, stamp, breakdown), read once the next step is
    # dispatched (_finish_step), and the newest step's (device metrics,
    # stamp), for a save that lands between two boundaries
    _parked = None
    _pending_metrics = None
    # metrics fetches since fit()'s entry: of a step already dispatched
    # over, and of the newest step (the host then waits for the device)
    _fetched_late = 0
    _fetched_in_band = 0
    # the open span of fit()'s current phase (None outside fit: a bare
    # train_step gets no breakdown), the id its iteration's spans share,
    # and fit/warmup while it is open
    _fit_phase = None
    _fit_step = None
    _fit_warmup = None
    _fit_log = staticmethod(print)   # fit()'s ``log``, for _run_step
    # graftpulse (obs/anomaly.py): built by fit() when ObsConfig.health is
    # set; every fetched metrics dict passes through _health_observe once
    health_sentry = None
    _health_last_step = -1
    # graftmend (docs/RESILIENCE.md): SIGTERM graceful-preemption latch and
    # the one-shot preemptive-snapshot rung (train/actions.py nan-precursor
    # action) — class-level so duck-typed FakeTrainers satisfy fit()
    _preempt = False
    preempted = False
    _preemptive_good = None
    _preemptive_good_device = None
    # rollback_snapshot="auto" gate (see _snapshot_mode): has this trainer
    # run a step, and the allocator peak those steps reached
    _stepped = False
    _step_peak_bytes = None

    def __init__(self, train_cfg: TrainConfig, mesh=None, backend=None):
        self.train_cfg = train_cfg
        if mesh is None and backend is not None:
            mesh = backend.mesh
        if mesh is None:
            from ..parallel import build_mesh
            mesh = build_mesh(train_cfg.mesh)
        self.mesh = mesh
        self.backend = backend
        self.base_key = jax.random.PRNGKey(train_cfg.seed)
        self.ckpt = CheckpointManager(
            train_cfg.checkpoint_dir, keep_n=train_cfg.keep_n_checkpoints,
            async_save=getattr(train_cfg, "async_checkpointing", False))
        self._last_good = None   # host copy of (params, opt_state) for rollback
        self._last_good_device = None   # on-device copy (rollback_snapshot)
        self._host_step = 0      # host mirror of state.step: no device sync
        # the starvation window between two records (_finish_breakdown)
        self._obs_wait_accum = 0.0
        self._obs_window_t0 = None
        self._obs_poll_bucket = -1
        self._telemetry = None
        self.last_watchdog = None
        # per-instance extras merged into checkpoint metadata, e.g. vae
        # identity for DALLE ckpts (reference legacy/train_dalle.py:535-582)
        self.extra_meta: dict = {}

    # subclasses implement train_step(*batch) -> metrics dict ---------------

    def _meta(self) -> dict:
        return {"hparams": self.model_cfg.to_dict(),
                "train": self.train_cfg.to_dict(),
                "model_class": self.model_class, **self.extra_meta}

    def restore(self, step: Optional[int] = None):
        """Resume model/opt/step from the checkpoint dir (reference
        legacy/train_dalle.py:249-272,531-532)."""
        self.state, meta = self.ckpt.restore(self.state, step)
        self._host_step = int(self.state.step)
        return meta

    def install_signal_checkpoint(self, log=print):
        """SIGUSR1 → checkpoint at the next step boundary (taming's "melk"
        handler, taming/main.py:544-557 — the signal only sets a flag; the
        save happens between steps where the state is consistent)."""
        import signal

        def handler(_sig, _frame):
            self._signal_save = True
            log("SIGUSR1: will checkpoint at the next step boundary")

        self._signal_save = False
        signal.signal(signal.SIGUSR1, handler)

    def install_preemption_handler(self, log=print):
        """SIGTERM → graceful preemption (the k8s/TPU-preemption contract,
        docs/RESILIENCE.md): the handler only latches flags; ``fit`` then
        finishes the in-flight step, forces a synchronous save through the
        SIGUSR1-latch path (which drains async checkpointing), and returns
        with ``self.preempted`` set so the CLI exits 0 with the state
        durable. A second SIGTERM during the wind-down is idempotent."""
        import signal

        def handler(_sig, _frame):
            self._signal_save = True
            self._preempt = True
            log("SIGTERM: graceful preemption — will checkpoint at the "
                "next step boundary and exit")

        self._preempt = False
        self.preempted = False
        # materialize the latch without clobbering a pending SIGUSR1 save
        self._signal_save = getattr(self, "_signal_save", False)
        signal.signal(signal.SIGTERM, handler)

    def _fetch_parked(self, current: bool) -> list:
        """What fit() still owes its writer, as (step, record) pairs, oldest
        first: the parked boundary, and with ``current`` (a save: nothing is
        checkpointed without a NaN check of the state saved) the newest
        step's metrics where ``metrics_every`` skipped it. A fetch of the
        newest step is in band: the host waits for the device."""
        out = []
        parked, self._parked = self._parked, None
        if parked is not None:
            out.append((parked[0], self._record(*parked)))
        step = self._host_step
        if (current and self._pending_metrics is not None
                and (parked is None or parked[0] != step)):
            out.append((step, self._record(step, *self._pending_metrics,
                                           None)))
        return out

    def _phase(self, name: Optional[str]):
        """Move fit()'s iteration on to the phase ``name``: close the open
        phase span and open the next as its sibling (``None``: close only),
        so a phase that starts in ``fit`` can end inside ``train_step``'s
        ``_finish_step`` and the phases tile the iteration. Returns the span
        just closed (None if ``name`` was already open). ``fit/warmup`` ends
        where the first ``fit/after_step`` starts."""
        cur = self._fit_phase
        if cur is not None:
            if cur.name == name:
                return None
            cur.__exit__(None, None, None)
            if cur.name == "fit/after_step":
                self._fit_after += cur.duration
        if name == "fit/after_step" and self._fit_warmup is not None:
            self._fit_warmup.__exit__(None, None, None)
            self._fit_warmup = None
        nxt = None
        if name:
            nxt = (span(name) if self._fit_step is None   # a bare train_step
                   else span(name, step=self._fit_step)).__enter__()
        self._fit_phase = nxt
        return cur

    @contextlib.contextmanager
    def _iteration(self):
        """One ``fit/step`` (in the ring for obs_report's step histogram,
        kept off the profiler because it encloses the phases), opened on its
        first phase, ``fit/batch_wait``."""
        self._fit_step = self._host_step
        self._fit_after = 0.0
        with span("fit/step", profiler=False, step=self._fit_step):
            try:
                self._phase("fit/batch_wait")
                yield
            finally:
                self._phase(None)
                self._fit_step = None
                # for the next record: the writer is called inside the
                # phase this describes
                self._fit_late["t_after_s"] = self._fit_after

    def _record(self, step: int, device_metrics, stamp: Optional[dict],
                part: Optional[dict]) -> dict:
        """The finished record of ``step``, through the one host fetch of a
        step's metrics: ``jax.device_get`` under ``fit/sync``. Inside fit()
        that is a phase (a sibling of ``fit/dispatch``, followed by
        ``fit/after_step``). The fetch is late when a later step has been
        dispatched over ``step``, so the device stays busy through it;
        otherwise it is in band and the host waits for the newest step (a
        bare step, a save boundary, fit()'s exit). Joined to the metrics:
        the columns the host knew when the step ran (``stamp``; under fit()
        the parked breakdown ``part``, to which the span's seconds add
        ``t_sync_s``), the throughput report, the health sentry's verdict."""
        late = step < self._host_step
        after = "fit/after_step" if self._fit_phase is not None else None
        self._phase("fit/sync")
        sync = self._fit_phase.set(late=late)
        try:
            metrics = {k: float(v)
                       for k, v in jax.device_get(device_metrics).items()}
        finally:
            self._phase(after)
        if late:
            self._fetched_late += 1
            counter_add("fit.fetch_late")
        else:
            self._fetched_in_band += 1
            counter_add("fit.fetch_in_band")
        if part is not None:
            part["t_sync_s"] = sync.duration
            metrics.update(self._finish_breakdown(part, step))
        if stamp:
            metrics.update(stamp)
        rep = self.meter.step(self._host_step)
        if rep:
            metrics.update(rep)
        return self._health_observe(step, metrics)

    def _create_state(self, params, apply_fn):
        """The single-optimizer trainers' state from freshly initialised
        params: placed on the mesh, given ``train_cfg.optim``'s optimizer
        state, committed; each step under its ``init/*`` span."""
        from ..parallel import commit_to_mesh, shard_params
        from .train_state import TrainState, make_optimizer
        tc = self.train_cfg
        with span("init/commit_to_mesh"):
            params = shard_params(self.mesh, params)
        with span("init/optimizer"):
            state = TrainState.create(
                apply_fn=apply_fn, params=params, tx=make_optimizer(tc.optim),
                lr_scale=1.0 if tc.runtime_lr_scale else None)
        with span("init/commit_to_mesh"):
            return commit_to_mesh(self.mesh, state)

    def _health_observe(self, step: int, metrics: dict) -> dict:
        """Run the graftpulse sentry over one FETCHED metrics dict (host
        floats) exactly once per metrics step — every path that finalizes a
        record (``_record``: late, in band at a save boundary, the exit flush)
        routes through here. Mutates ``metrics`` with breach columns."""
        sentry = self.health_sentry
        if sentry is None or not metrics or step == self._health_last_step:
            return metrics
        self._health_last_step = step
        sentry.observe(step, metrics)
        return metrics

    def _put(self, x, dtype=None, stacked: bool = False):
        """Convert one host batch leaf and place it on the mesh. A jax Array
        of the right dtype skips the ``np.asarray`` (which would drag it back
        to host) but still routes through the shard fn — ``device_put`` with
        the already-correct sharding is a no-op (the prefetch path stays
        zero-copy) while a direct caller's device array with some other
        placement gets resharded onto the mesh, matching the pre-prefetch
        semantics. A wrong-dtype device array pays the host round-trip the
        coercion always cost."""
        from ..parallel import shard_batch, shard_stacked_batch
        if not (isinstance(x, jax.Array)
                and (dtype is None or x.dtype == np.dtype(dtype))):
            x = np.asarray(x, dtype) if dtype is not None else np.asarray(x)
        return (shard_stacked_batch if stacked else shard_batch)(self.mesh, x)

    def _put_batch(self, batch: tuple, stacked: bool = False) -> tuple:
        """Convert + shard one fit() batch tuple exactly as ``train_step``
        would (dtype coercion included) — the hook the device prefetcher uses
        to move H2D off the critical path. The base implementation is the
        identity (host batches through, for trainers without a device path);
        real trainers override with their per-leaf dtypes."""
        return batch

    def _step_keys(self, k: int):
        """The exact per-step rng stream ``train_step`` would draw for the
        next k host steps — fold_in(base_key, host_step + i) — stacked for
        scanning. Single source of the scan/single rng-parity invariant
        (every trainer's ``train_steps`` must consume THIS stream)."""
        import jax.numpy as jnp
        return jnp.stack([jax.random.fold_in(self.base_key,
                                             self._host_step + i)
                          for i in range(k)])

    def _stack_batches(self, batches, k: int):
        """Group the batch stream into (stacked?, batch) pairs: full groups
        of k become stacked tuples for ``train_steps``; a final short group
        is yielded as plain single batches for ``train_step`` (which is
        already compiled — a (1, ...) stack would force one extra minutes-
        long compile of the scan program just to drain the tail). A group
        whose members disagree in shape (short batch mid-stream from
        drop_last=False loaders or webdataset ``batched(partial=True)``)
        also falls back to singles instead of crashing np.stack (warned
        once: if every group is ragged, scan_steps is effectively off)."""
        import itertools
        import warnings
        it = iter(batches)
        warned = False
        while True:
            group = list(itertools.islice(it, k))
            if not group:
                return
            homogeneous = all(
                len(b) == len(group[0]) and all(
                    np.shape(x) == np.shape(group[0][j])
                    for j, x in enumerate(b))
                for b in group)
            if len(group) < k or not homogeneous:
                if not homogeneous and not warned:
                    warnings.warn(
                        "scan_steps: batch group has mismatched shapes; "
                        "draining it as single steps (a loader with varying "
                        "batch shapes disables the scanned fast path)")
                    warned = True
                for b in group:
                    yield False, b
                if len(group) < k:
                    return
                continue
            yield True, tuple(np.stack(xs) for xs in zip(*group))

    def fit(self, batches, *, steps: Optional[int] = None, log=print,
            sample_fn: Optional[Callable[[int], None]] = None,
            metrics_writer=None,
            on_step: Optional[Callable[[int], None]] = None):
        """Epoch-agnostic loop over ``batches`` (iterable of tuples fed to
        ``train_step``) with the reference's parity behaviors.

        With ``train_cfg.scan_steps > 1`` full groups of k consecutive
        batches run through ``train_steps`` (k optimizer steps per device
        dispatch; the tail drains through ``train_step``); host-side events
        — metrics fetch, NaN check/rollback, checkpoint/log/sample cadence —
        then happen at k-step granularity. Cadences use boundary *crossing*
        (prev//N != cur//N), so a k that does not divide N stretches an
        event by at most k-1 steps, never to lcm(k, N); a NaN rollback
        rewinds the whole k-step group to the last good snapshot.

        Host-overlap layers (docs/PERFORMANCE.md): with
        ``train_cfg.device_prefetch > 0`` the next batches are converted and
        device_put through the trainer's ``_put_batch`` while the current
        step runs — note the lookahead means a fit() that exits on its
        ``steps`` budget has consumed up to ``device_prefetch`` extra
        batches from the iterator (callers sharing one iterator across fit
        calls should pass ``device_prefetch=0``); with
        ``train_cfg.async_checkpointing`` a mid-run save
        costs one device→host snapshot (the write overlaps following steps;
        SIGUSR1-latch saves and fit exit drain).

        The metrics fetch is one boundary late, always: step N's loss is
        read once step N+1 has been dispatched (``_finish_step``), so the
        host's dispatch overlaps the running step and the device does not
        wait for it; the host runs one step ahead and no further. What
        holds regardless: every record reaches ``metrics_writer`` once,
        under its true step, in increasing order, the last one by a flush
        at exit; a save boundary (cadence, SIGUSR1, SIGTERM) first fetches
        the CURRENT step in band, so nothing is checkpointed or taken as
        the rollback snapshot without a NaN check of the state saved; a NaN
        read one step late rolls back as ever and drops the record of the
        step that ran on the poisoned state; the exit flush is NaN-checked
        too, so with ``nan_rollback`` fit() never returns a state whose
        last step went NaN. ``on_step(N)``, ``sample_fn``, the watchdog
        beat and the health sentry's actions run when step N is dispatched
        and N-1 is known good; reading ``trainer.state`` in them waits for
        N. A bare ``train_step()`` outside fit() returns its own step's
        metrics.

        grafttrace (``train_cfg.obs``, docs/OBSERVABILITY.md): every
        iteration is a ``fit/step`` span whose four phases are siblings that
        tile it: ``fit/batch_wait`` (blocked on the batch iterator, then the
        chaos hook), ``fit/dispatch`` (host work and the jitted call),
        ``fit/sync`` (every metrics device_get) and ``fit/after_step`` (the
        watchdog beat, ``on_step``, the writer, the save decision with
        ``fit/checkpoint`` inside it, the sampling hook). The spans'
        durations are the record's ``t_batch_wait_s`` / ``t_dispatch_s`` /
        ``t_sync_s`` / ``t_after_s`` columns, beside a data-starvation
        ratio; ``fit/sync`` carries ``late`` and the counters
        ``fit.fetch_late`` / ``fit.fetch_in_band`` say how often the fetch
        found a later step queued (fit() logs both at exit); ``fit/warmup``
        runs from fit()'s entry to the return of the first step's dispatch
        (its program loaded or compiled; with a save at step 1, to the end
        of that step). With
        ``obs.watchdog_deadline_s > 0`` a heartbeat watchdog reports stalls
        (open spans + thread stacks) instead of hanging silently; with
        ``obs.trace`` the span ring is exported as Perfetto-openable
        ``trace.json`` + ``spans.jsonl`` when the loop ends.

        graftmend (docs/RESILIENCE.md): every iteration passes through the
        chaos hook (``chaos.step_hook`` — a no-op ``None`` check unless a
        FaultPlan is installed); ``on_step(step)`` is called after each
        completed step (the elastic runtime's heartbeat point — exceptions
        it raises propagate, which is how an elastic worker aborts the loop
        on a membership change); and after
        :meth:`install_preemption_handler`, a SIGTERM finishes the in-
        flight step, forces a synchronous drained save, sets
        ``self.preempted`` and returns — callers then exit 0."""
        tc = self.train_cfg
        oc = getattr(tc, "obs", None)
        tracing = bool(oc is not None and oc.trace)
        if tracing:
            obs_configure(oc.ring_capacity)
        watchdog = None
        if oc is not None and oc.watchdog_deadline_s > 0:
            watchdog = StallWatchdog(
                oc.watchdog_deadline_s, log=log,
                dump_stacks=oc.watchdog_dump_stacks).start()
            self.last_watchdog = watchdog
        if (oc is not None and getattr(oc, "health", False)
                and self.health_sentry is None):
            # graftpulse sentry: watches the health/* columns the jitted
            # step now emits (trainers pass obs.health into their step-body
            # factories); breaches fire gauges/events/flight bundles and
            # annotate the record obs_report's MODEL-HEALTH verdict reads.
            # Kept across fit() calls so EMA baselines survive resume.
            from ..obs.anomaly import HealthSentry
            self.health_sentry = HealthSentry.from_obs_config(oc)
        scan_k = max(getattr(tc, "scan_steps", 1), 1)
        if scan_k > 1:
            assert hasattr(self, "train_steps"), (
                f"{type(self).__name__} has no train_steps; scan_steps needs "
                "the scanned multi-step API")
            batches = self._stack_batches(batches, scan_k)
        else:
            batches = ((False, b) for b in batches)
        prefetcher = None
        if getattr(tc, "device_prefetch", 0) > 0:
            # double-buffered device placement: the next `depth` batches are
            # converted + device_put (through the trainer's _put_batch, so
            # dtypes/shardings match train_step exactly) while the current
            # step runs — batch wait and H2D leave the critical path
            from ..data.device_prefetch import DevicePrefetcher
            prefetcher = DevicePrefetcher(
                batches,
                lambda item: (item[0], self._put_batch(item[1],
                                                       stacked=item[0])),
                depth=tc.device_prefetch)
            batches = prefetcher
        self._fit_late = {}    # t_ckpt_s / t_after_s: land one record late
        self._fit_log = log
        self._fit_warmup = span("fit/warmup").__enter__()
        meta = self._meta()
        if tc.preflight_checkpoint:
            self.ckpt.preflight(self.state, meta)
        self._snapshot_good()

        def crossed(prev, cur, every):
            return every > 0 and prev // every != cur // every

        def emit(records, say: bool) -> bool:
            """NaN-check, log and write ``records`` in step order. True at
            the first NaN, with the rollback done: that record is not
            written, and whatever follows it came from the poisoned state
            and is dropped with it."""
            for mstep, m in records:
                if tc.nan_rollback and not math.isfinite(
                        self._nan_check_value(m, log)):
                    log(f"[step {mstep}] NaN loss — rolling back to last "
                        f"good state")
                    self._rollback()
                    return True
                if say:
                    log(f"[step {mstep}] " + _fmt_metrics(m))
                if metrics_writer is not None:
                    metrics_writer.log(mstep, m)
            return False

        self._fetched_late = self._fetched_in_band = 0
        self._obs_wait_accum = 0.0
        self._obs_window_t0 = time.perf_counter()
        it = iter(batches)
        _END = object()
        try:
            while True:
                with self._iteration():
                    item = next(it, _END)
                    if item is _END:
                        break
                    # chaos injection point: kill/hang/slow/corrupt faults
                    # fire here, BEFORE the dispatch — "mid-step" from the
                    # run's point of view (the last durable save < this
                    # step). Still inside fit/batch_wait: t_dispatch_s is the
                    # straggler detector's "blocked" signal (degrade/), which
                    # an injected host stall on the victim must not inflate
                    _chaos_step_hook(self._host_step)
                    # one phase ends where the next starts: a stall between
                    # two statements (another thread holding the interpreter)
                    # falls inside a phase and has a name
                    wait = self._phase("fit/dispatch")
                    self._fit_wait = wait.duration
                    self._obs_wait_accum += wait.duration
                    self._fit_h2d = (prefetcher.last_put_s
                                     if prefetcher is not None else 0.0)
                    stacked, batch = item
                    step_call = self.train_steps if stacked else self.train_step
                    k_this = batch[0].shape[0] if stacked else 1
                    prev_step = self._host_step
                    # profile the REAL step containing profile_step — no
                    # hidden extra update (the reference's flops profile also
                    # wraps a live step, legacy/train_dalle.py:492-499).
                    # fit/dispatch is opened anew once the session is live,
                    # so that it lies on the profile's host plane
                    logdir = None
                    if tc.profile_step and (prev_step < tc.profile_step
                                            <= prev_step + k_this):
                        logdir = (f"{tc.checkpoint_dir}/"
                                  f"profile_step{tc.profile_step}")
                        self._phase(None)
                    with (jax.profiler.trace(logdir) if logdir
                          else contextlib.nullcontext()):
                        self._phase("fit/dispatch")   # open unless profiled
                        # _finish_step, inside, moves on to fit/sync
                        m = step_call(*batch)
                        if logdir:
                            # the profile is of THIS step, whose metrics
                            # nothing fetches before the next dispatch
                            jax.block_until_ready(self.state)
                        self._phase("fit/after_step")
                    if logdir:
                        log(f"[profile] step {self._host_step}: trace → {logdir}")
                    step_num = self._host_step
                    if watchdog is not None:
                        watchdog.beat(step_num)
                    if on_step is not None:
                        on_step(step_num)
                    # latch the signal flag ONCE per iteration; a save
                    # decision must see the same value the fetch decision does
                    want_save = (crossed(prev_step, step_num, tc.save_every_steps) or
                                 getattr(self, "_signal_save", False))
                    # the record at hand is the previous boundary's
                    records = [(m.pop("metrics_step", step_num), m)] if m else []
                    if want_save:
                        # the save's NaN gate must see the CURRENT step:
                        # whatever is still parked is fetched now, in band.
                        # Older records go first, so writer steps stay
                        # monotonic (wandb silently drops out-of-order steps)
                        records += self._fetch_parked(current=True)
                    if not emit(records,
                                crossed(prev_step, step_num, tc.log_every)):
                        if want_save:
                            signal_save = getattr(self, "_signal_save", False)
                            with span("fit/checkpoint", step=step_num) as ckpt:
                                # async manager: returns after the snapshot;
                                # the write overlaps the next steps. An
                                # operator-requested (SIGUSR1) save drains so
                                # the latch means "durable now". Metadata is
                                # re-evaluated per save: extra_meta can
                                # change mid-run (the gumbel re-anneal
                                # action records its rebase there) and the
                                # sidecar must carry the CURRENT values
                                self.ckpt.save(step_num, self.state,
                                               self._meta())
                                if signal_save:
                                    self._ckpt_wait()
                                self._snapshot_good()
                            self._fit_late["t_ckpt_s"] = ckpt.duration
                            self._signal_save = False
                            if (getattr(tc, "log_artifacts", False)
                                    and metrics_writer is not None
                                    and hasattr(metrics_writer, "log_artifact")):
                                # the upload reads the step directory, so an
                                # in-flight async write must land first
                                self._ckpt_wait()
                                # only the just-written step's directory —
                                # uploading the whole checkpoint_dir would
                                # re-send every retained checkpoint each save
                                # (ref uploads the one new file,
                                # legacy/train_dalle.py:667-669)
                                metrics_writer.log_artifact(
                                    os.path.join(tc.checkpoint_dir, str(step_num)),
                                    name=f"trained-{self.model_class.lower()}",
                                    metadata={"step": step_num})
                        if want_save and getattr(self, "_preempt", False):
                            # SIGTERM wind-down: the save above ran through
                            # the signal-latch path (synchronous + drained),
                            # so the state is durable — exit the loop; the
                            # CLI then exits 0. A NaN at this boundary skips
                            # the save, so the latch stays set and the NEXT
                            # boundary (post-rollback, finite) winds down.
                            self.preempted = True
                            self._preempt = False
                            log(f"[step {step_num}] graceful preemption: "
                                f"checkpoint durable; exiting fit")
                        if sample_fn and crossed(prev_step, step_num,
                                                 getattr(tc, "sample_every_steps", 0)):
                            sample_fn(step_num)
                if self.preempted:
                    break
                # the steps budget must bound the loop even when steps go NaN
                if steps is not None and step_num >= steps:
                    break
        finally:
            if self._fit_warmup is not None:   # no step was dispatched
                self._fit_warmup.__exit__(None, None, None)
                self._fit_warmup = None
            # the last boundary is still parked: its record is written, and
            # it gets the NaN check too, so fit() never returns a state whose
            # last step went NaN while nan_rollback is on
            try:
                emit(self._fetch_parked(current=False), True)
            except Exception as exc:  # noqa: BLE001 - fit may be unwinding
                # from a device error, which this must not mask
                log(f"[fit] the last record was not flushed: {exc!r}")
            log(f"[fit] metrics fetches: {self._fetched_late} late (a later "
                f"step already dispatched), {self._fetched_in_band} in band")
            # drain in-flight async checkpoint writes: a fit() that returned
            # must leave durable checkpoints behind (duck-typed managers in
            # tests may not expose the drain)
            self._ckpt_wait()
            if watchdog is not None:
                watchdog.stop()
            if tracing:
                outdir = oc.trace_dir or os.path.join(tc.checkpoint_dir, "obs")
                os.makedirs(outdir, exist_ok=True)
                export_chrome_trace(os.path.join(outdir, "trace.json"))
                export_spans_jsonl(os.path.join(outdir, "spans.jsonl"))
        return self.state

    def _ckpt_wait(self):
        wait = getattr(self.ckpt, "wait_until_finished", None)
        if wait is not None:
            with span("ckpt/drain"):
                wait()

    def _nan_check_value(self, m: dict, log=print) -> float:
        """The scalar the NaN-rollback check inspects: ``loss`` when present
        (every in-repo trainer), else the first finite-checkable scalar — a
        metrics dict without one used to KeyError the whole fit loop. With
        nothing checkable the guard is inert (warned once)."""
        val = m.get("loss")
        if val is None:
            val = next((v for v in m.values()
                        if isinstance(v, (int, float))
                        and not isinstance(v, bool)), None)
            if val is None:
                if not getattr(self, "_warned_no_nan_scalar", False):
                    log("[nan-guard] metrics carry no 'loss' or other "
                        "finite-checkable scalar; NaN rollback is inactive")
                    self._warned_no_nan_scalar = True
                return 0.0   # finite → never triggers a rollback
        return val

    def _snapshot_mode(self, live) -> str:
        """Resolve ``rollback_snapshot`` ("auto" → "device"/"host"): the
        on-device copy doubles the (params, opt_state) HBM footprint, so auto
        only takes it when it fits beside a running step. What a step needs
        (activations, gradients) is invisible to the allocator until one
        has run, and on the TPU it never shows in ``bytes_in_use`` at all:
        a program's temporaries are a reservation made when it is loaded.
        At 1.4B on a 16 GB chip the state is 5.4 GB and the step reserves
        8.4 GB more, so "free HBM now" admits a copy that the next step
        then cannot load around (RESOURCE_EXHAUSTED "Attempting to reserve
        8.41G at the bottom of memory", chip run, PR 21). Auto therefore
        keeps the snapshot on the host until this trainer has stepped,
        then gates on the high-water marks of those steps — buffers plus
        program reservations; an allocator that reports a limit but no
        reservation peak stays on the host. Backends without a limit — CPU
        — always fit: "device" there is host RAM."""
        mode = getattr(self.train_cfg, "rollback_snapshot", "host")
        if mode != "auto":
            return mode
        from ..obs import device_memory_stats
        d0 = self.mesh.devices.flat[0]
        stats = device_memory_stats(d0)
        limit = stats.get("hbm_bytes_limit")
        if limit is None:
            return "device"
        if self._step_peak_bytes is None:
            if not self._stepped or not {
                    "hbm_peak_bytes", "hbm_peak_reserved_bytes"} <= set(stats):
                return "host"
            # no device snapshot has been resident yet, so these peaks are
            # the steps' own; later ones would include the snapshot itself
            self._step_peak_bytes = (stats["hbm_peak_bytes"]
                                     + stats["hbm_peak_reserved_bytes"])
        headroom = limit - self._step_peak_bytes

        # per-device snapshot bytes = what ONE device actually holds — the
        # sum of its shards. global/mesh_size would undercount replicated
        # leaves (a dp-only mesh replicates the whole tree on every device)
        def _on_d0(x):
            try:
                return sum(s.data.nbytes for s in x.addressable_shards
                           if s.device == d0)
            except Exception:  # noqa: BLE001 - conservative on exotic arrays
                return x.nbytes
        per_device = sum(_on_d0(x) for x in jax.tree.leaves(live))
        # 1.15× covers copy transients + rounding
        return "device" if per_device * 1.15 < headroom else "host"

    def _snapshot_good(self):
        # NaN loss is observed AFTER apply_gradients has run, so the optimizer
        # moments are poisoned too — snapshot and restore both (the reference
        # fork reloads the whole checkpoint, vae.py:100-110)
        live = (self.state.params, self.state.opt_state)
        self._last_good_shardings = jax.tree.map(lambda x: x.sharding, live)
        # free the PREVIOUS snapshot before the headroom gate and the copy:
        # gating with it still resident makes auto oscillate device/host on
        # alternating saves (the old snapshot eats exactly the headroom the
        # new one needs), and holding both through the copy would spike to
        # 3× the state footprint
        self._last_good_device = None
        # a fresh boundary snapshot supersedes any parked preemptive rung
        # (which is now the OLDER state — rolling back to it would discard
        # progress the boundary snapshot preserves)
        self._preemptive_good = None
        self._preemptive_good_device = None
        mode = self._snapshot_mode(live)
        with span("ckpt/snapshot_good", mode=mode):
            if mode == "device":
                # donated-safe on-device copy — no host fetch, which at
                # flagship scale is a multi-second device-idle window
                self._last_good_device = _tree_copy(live)
                self._last_good = None
            else:
                self._last_good = jax.device_get(live)
                self._last_good_device = None

    def take_preemptive_snapshot(self):
        """graftmend breach→action (train/actions.py): copy the CURRENT
        (params, opt_state) into a ONE-SHOT rung above the save-boundary
        snapshot. Fired on a nan-precursor breach — the classic divergence
        shape is inf-in-grads → loss NaN a few steps later, and without
        this rung the eventual rollback rewinds to the last save boundary,
        burning up to ``save_every_steps`` of progress. The first rollback
        consumes this rung (burn ≈ breach→NaN steps); if the restored
        state goes NaN again — the precursor state itself was already
        contaminated — the next rollback falls through to the durable
        boundary snapshot, so the ladder never loops on a poisoned rung.
        Same device/host placement policy as :meth:`_snapshot_good`."""
        live = (self.state.params, self.state.opt_state)
        self._preemptive_shardings = jax.tree.map(lambda x: x.sharding, live)
        self._preemptive_good = None
        self._preemptive_good_device = None
        mode = self._snapshot_mode(live)
        with span("ckpt/preemptive_snapshot", mode=mode):
            if mode == "device":
                self._preemptive_good_device = _tree_copy(live)
            else:
                self._preemptive_good = jax.device_get(live)

    def _rollback(self):
        # metrics computed from the poisoned state must die with it: the
        # parked record of the step that fit() had already dispatched when
        # it read the NaN would otherwise trigger a second, spurious
        # rollback at the next boundary, discarding the good step just
        # trained from the restored state
        self._parked = None
        self._pending_metrics = None
        with span("ckpt/rollback"):
            if self._preemptive_good_device is not None:
                # one-shot rung: install directly (no defensive copy — the
                # rung is consumed; a repeat NaN falls to the boundary
                # snapshot below, never back here)
                restored, self._preemptive_good_device = (
                    self._preemptive_good_device, None)
            elif self._preemptive_good is not None:
                host, self._preemptive_good = self._preemptive_good, None
                restored = jax.tree.map(jax.device_put, host,
                                        self._preemptive_shardings)
            elif self._last_good_device is not None:
                # install a COPY: the restored tree becomes the live state and
                # gets donated into the next step — the snapshot itself must
                # stay valid in case that step goes NaN again
                restored = _tree_copy(self._last_good_device)
            elif self._last_good is not None:
                restored = jax.tree.map(jax.device_put, self._last_good,
                                        self._last_good_shardings)
            else:
                return
            params, opt_state = restored
            self.state = self.state.replace(params=params, opt_state=opt_state)

    def _run_step(self, jitted, *args):
        """``jitted(*args)``, a step's dispatch. The first of a fit() (its
        warm-up is still open) also hands the program just dispatched to
        ``obs.device.capture_program`` as ``"train/step"``, under the span
        ``warmup/scopes``, while the device runs that step: the call's own
        executable and arguments, so nothing compiles or loads twice, and a
        reader of a device trace can join its operations to the program's
        ``jax.named_scope``s (docs/OBSERVABILITY.md "Device time by
        scope")."""
        out = jitted(*args)
        if self._fit_warmup is not None:
            with span("warmup/scopes"):
                capture_program("train/step", jitted, *args,
                                log=self._fit_log)
        return out

    def _finish_step(self, metrics, stamp: Optional[dict] = None) -> dict:
        """Post-step bookkeeping: advance the host step and hand back a
        record. ``stamp``: columns of this step that the host already knows
        (the dVAE's temperature); they travel with the step's record.

        A bare ``train_step()`` / ``train_steps()`` call gets its own step's
        metrics, fetched in band, with the throughput report keyed on the
        post-increment step. Under fit() the fetch is one boundary late:
        this step's device metrics are parked with its breakdown
        (batch-wait/dispatch/h2d splits; the sync, the data-starvation ratio
        and, at ``obs.device_poll_every`` cadence, the HBM and recompile
        gauges join at the fetch), and the record handed back is the
        previous boundary's, tagged ``metrics_step``. That step has
        finished or is about to, and this one is already queued behind it,
        so the device does not idle through the host's dispatch. The first
        boundary of a fit() hands back nothing; the fetch of N-1 is what
        keeps the host from running more than one step ahead.

        With ``metrics_every > 1`` only every Nth step is a boundary; the
        others return an empty dict and fit() skips their NaN check and
        logging."""
        self._host_step += 1
        self._stepped = True
        step = self._host_step
        self._pending_metrics = (metrics, stamp)
        every = max(getattr(self.train_cfg, "metrics_every", 1), 1)
        if step % every != 0:
            return {}
        if self._fit_phase is None:
            return self._record(step, metrics, stamp, None)
        parked = self._parked
        # the dispatch ends here
        dispatch = self._phase("fit/sync" if parked is not None
                               else "fit/after_step")
        self._parked = (step, metrics, stamp,
                        self._partial_breakdown(dispatch.duration))
        if parked is None:
            return {}
        record = self._record(*parked)
        record["metrics_step"] = parked[0]
        return record

    def _partial_breakdown(self, t_dispatch_s: float) -> dict:
        """Where did the step go? The splits knowable when the dispatch
        ends, each the duration of a span of fit(): ``fit/batch_wait``,
        ``fit/dispatch``, the consumed batch's ``data/h2d`` and, one record
        late, the previous iteration's ``fit/checkpoint`` and
        ``fit/after_step``. ``_record`` adds ``t_sync_s`` and the window's
        gauges (``_finish_breakdown``). Only under fit(): a bare
        ``train_step()`` call has no batch-wait context and gets no
        breakdown."""
        out = {"t_batch_wait_s": self._fit_wait,
               "t_dispatch_s": t_dispatch_s,
               # host-side H2D enqueue cost of the consumed batch (0 without
               # device prefetch — the put then rides inside batch_wait)
               "t_h2d_s": self._fit_h2d}
        # saves and the writer run after the metrics are fetched, so their
        # cost lands one record late — obs_report accounts checkpoint steps
        # as their own category
        out.update(self._fit_late)
        self._fit_late.clear()
        return out

    def _finish_breakdown(self, out: dict, step: int) -> dict:
        """Windowed starvation ratio (the waiting-on-data share of the whole
        window since the last record, so ``metrics_every``-skipped steps are
        covered: 'input-bound vs compute-bound' as a logged metric instead
        of a guess) + device-gauge poll (HBM used/peak, compiles,
        recompiles-per-100-steps every ``obs.device_poll_every`` steps) +
        Prometheus mirror, merged into ``out`` (the per-step splits)."""
        now = time.perf_counter()
        window_t0 = getattr(self, "_obs_window_t0", None)
        if window_t0 is not None and now > window_t0:
            out["data_starvation"] = min(self._obs_wait_accum / (now - window_t0), 1.0)
        self._obs_window_t0 = now
        self._obs_wait_accum = 0.0
        oc = getattr(self.train_cfg, "obs", None)
        if oc is not None and oc.device_poll_every > 0:
            # by the record's step: the exit flush runs at the host step
            # of the fetch before it
            bucket = step // oc.device_poll_every
            if bucket != getattr(self, "_obs_poll_bucket", -1):
                self._obs_poll_bucket = bucket
                if getattr(self, "_telemetry", None) is None:
                    self._telemetry = DeviceTelemetry()
                # gauges flow through the metrics dict only — mirroring them
                # into the tracer's gauge map would re-export every value a
                # second time under an obs.-prefixed alias in each record
                out.update(self._telemetry.poll(self._host_step))
                if oc.prometheus_path:
                    from ..obs import metrics_snapshot
                    from ..obs import write_textfile as prom_write
                    prom_write(oc.prometheus_path,
                               {**out, **metrics_snapshot(),
                                "host_step": self._host_step})
        return out
