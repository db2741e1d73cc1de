"""grafttrace core: spans, counters/gauges, ring buffer, Chrome trace export.

The repo's only runtime instrumentation before this module was a samples/sec
print and a one-shot profiler capture (train/metrics.py) — enough to know a
run is slow, never enough to know *why*. grafttrace adds the missing layer:

  * ``span(name)`` — a context manager / decorator timing a named region,
    with thread-local nesting. One measurement (two ``perf_counter`` reads)
    feeds three sinks: always, ``sp.duration`` and the process-wide totals
    per name (``phase_totals()``); with the ring on (``configure()``), a
    record with ``id`` and ``parent``; while a jax profiler session is
    live, a ``TraceAnnotation`` of the same name on the profiler's own
    clock (the annotation class is installed by ``obs/device.py``, the
    jax-importing side: this module imports no jax). A couple of
    microseconds with the ring off, so spans can live on per-step hot paths
    without moving the numbers they measure.
  * an in-process ring buffer of completed spans (bounded; overflow is
    *counted*, never silent) that exports both JSONL (one span per line,
    greppable, ``scripts/obs_report.py``'s input) and Chrome ``trace_event``
    JSON, openable directly in Perfetto / chrome://tracing.
  * process-wide counters and gauges (``counter_add``/``gauge_set``) that
    merge into ``MetricsLogger`` records and the Prometheus textfile
    exporter (obs/prometheus.py).

Spans recorded from multiple threads keep independent stacks (the prefetch
thread's decode spans overlap the main thread's dispatch spans in Perfetto —
that overlap IS the picture of a healthy input pipeline). ``open_spans()``
exposes the live per-thread stacks for the stall watchdog's reports.
"""

from __future__ import annotations

import functools
import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Optional

from .context import current_trace_id

# ---------------------------------------------------------------------------
# global state: one process-wide tracer (None = tracing disabled) plus the
# per-thread open-span stacks. The stacks registry is keyed by thread ident
# so the watchdog can report "last open span" for every thread.
# ---------------------------------------------------------------------------

_TLS = threading.local()
# thread ident -> (thread name, open-span stack, totals by span name); a
# thread only ever writes its own entry's stack and totals, so the hot path
# takes no lock
_STACKS: dict = {}
_STACKS_LOCK = threading.Lock()   # registering a thread, folding a dead one
_RETIRED: dict = {}         # totals of threads whose ident was reused
_SPAN_IDS = itertools.count(1)
_annotation = None          # jax.profiler.TraceAnnotation once obs.device loads
_tracer: Optional["Tracer"] = None

# Native histogram discipline (graftlens): bucket boundaries are declared at
# the call site (or defaulted), never derived from observed data, and capped
# so one histogram can never explode the registry — the same bounded-
# cardinality rule unbounded-metric-label enforces for label values.
MAX_HISTOGRAM_BUCKETS = 32
DEFAULT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                   0.5, 1.0, 2.5, 5.0, 10.0, 30.0)


def _fmt_le(bound: float) -> str:
    return format(bound, "g")


def _bucket_key(key: str, le: str) -> str:
    """Flat registry key for one cumulative bucket: ``name_bucket{le="x"}``,
    merging ``le`` into an existing sorted label block when the histogram
    itself is labeled."""
    base, brace, rest = key.partition("{")
    if not brace:
        return f'{base}_bucket{{le="{le}"}}'
    items = rest[:-1].split(",")
    items.append(f'le="{le}"')
    items.sort()
    return f'{base}_bucket{{{",".join(items)}}}'


class _Histogram:
    """One native histogram: fixed boundaries, per-bucket counts, sum/count,
    and the latest (trace_id, value, ts) exemplar per bucket."""

    __slots__ = ("buckets", "counts", "sum", "count", "exemplars")

    def __init__(self, buckets):
        self.buckets = tuple(buckets)
        self.counts = [0] * (len(self.buckets) + 1)   # last = +Inf overflow
        self.sum = 0.0
        self.count = 0
        self.exemplars: dict = {}                     # bucket idx -> exemplar


def _thread_state() -> tuple:
    """(open-span stack, {name: [count, seconds]}) of the calling thread."""
    st = getattr(_TLS, "state", None)
    if st is None:
        st = _TLS.state = ([], {})
        ident = threading.get_ident()
        with _STACKS_LOCK:
            dead = _STACKS.get(ident)   # an ended thread whose ident is reused
            if dead is not None:
                _merge_totals(_RETIRED, dead[2])
            _STACKS[ident] = (threading.current_thread().name, *st)
    return st


def _merge_totals(into: dict, cells: dict) -> None:
    for name, (count, seconds) in list(cells.items()):
        have = into.get(name, (0, 0.0))
        into[name] = (have[0] + count, have[1] + seconds)


def set_profiler_annotation(cls) -> None:
    """Install the class whose instances put a span on a live jax profiler
    session's host plane (``jax.profiler.TraceAnnotation``; its
    ``is_enabled()`` says whether one is live, so none is made otherwise).
    Called by ``obs/device.py`` at import, so this module stays importable
    without jax."""
    global _annotation
    _annotation = cls


def phase_totals() -> dict:
    """``{span name: (count, seconds)}`` over every span closed in this
    process so far, on every thread, whether or not the ring is on."""
    out: dict = {}
    with _STACKS_LOCK:
        _merge_totals(out, _RETIRED)
        for _name, _stack, cells in list(_STACKS.values()):
            _merge_totals(out, cells)
    return out


def reset_phase_totals() -> None:
    """Zero the totals (for tests)."""
    with _STACKS_LOCK:
        _RETIRED.clear()
        for _name, _stack, cells in list(_STACKS.values()):
            cells.clear()


class Tracer:
    """Process-wide span sink: a bounded ring of completed spans plus
    counter/gauge maps. Span records are plain tuples
    ``(name, rel_start_s, dur_s, thread_ident, depth, args, id, parent)`` —
    relative to ``time_origin`` (a ``perf_counter`` anchor paired with a
    wall-clock epoch, so exports can be mapped back to absolute time);
    ``parent`` is the id of the span that was open beneath it on its thread
    (None at depth 0, as for every ``record_span``)."""

    def __init__(self, capacity: int = 65536):
        self.capacity = capacity
        self.spans: deque = deque(maxlen=capacity)
        self.counters: dict = {}
        self.gauges: dict = {}
        self.histograms: dict = {}   # labeled name -> _Histogram
        self.dropped = 0          # spans evicted from the ring (never silent)
        self.total_recorded = 0   # monotonic span count (telemetry cursors)
        self._lock = threading.Lock()
        self.t_origin = time.perf_counter()
        self.epoch_origin = time.time()

    def _record(self, name, t0, dur, depth, args, span_id, parent):
        # locked: exports iterate the deque from other threads, and a deque
        # mutated mid-iteration raises RuntimeError (the lock is uncontended
        # on the hot path — ~100ns next to two perf_counter calls)
        with self._lock:
            if len(self.spans) == self.spans.maxlen:
                self.dropped += 1
            self.total_recorded += 1
            self.spans.append((name, t0 - self.t_origin, dur,
                               threading.get_ident(), depth, args,
                               span_id, parent))

    def snapshot_spans(self) -> list:
        with self._lock:
            return list(self.spans)

    def spans_since(self, since_seq: int = 0):
        """Incremental span read for the telemetry exporter: every span in
        the ring carries an implicit monotonic sequence number (position in
        ``total_recorded`` order); returns ``(cursor, rows)`` where rows are
        the raw span tuples recorded after ``since_seq`` and ``cursor`` is
        the value to pass next time. Spans that overflowed the ring before a
        pull are gone (counted in ``dropped``) — the cursor still advances
        past them, so a slow puller never re-reads or wedges."""
        with self._lock:
            total = self.total_recorded
            rows = list(self.spans)
        first_seq = total - len(rows) + 1
        skip = max(0, since_seq - first_seq + 1)
        return total, rows[skip:]

    def snapshot_metrics(self) -> dict:
        """Counters + gauges + flattened histograms as one flat dict (copied
        under the lock). Histograms flatten to the Prometheus native-
        histogram spelling — cumulative ``name_bucket{le="b"}`` counters
        plus ``name_sum`` / ``name_count`` — so every existing consumer
        (MetricsLogger, the textfile exporter, obs_report, the fleet
        collector's counter merge) handles them with no schema change."""
        with self._lock:
            out = dict(self.counters)
            out.update(self.gauges)
            for key, h in self.histograms.items():
                running = 0
                for i, bound in enumerate(h.buckets):
                    running += h.counts[i]
                    out[_bucket_key(key, _fmt_le(bound))] = float(running)
                out[_bucket_key(key, "+Inf")] = float(h.count)
                out[f"{key}_sum"] = h.sum
                out[f"{key}_count"] = float(h.count)
        if self.dropped:
            out["obs.spans_dropped"] = self.dropped
            out["obs.spans_dropped_total"] = float(self.dropped)
        return out

    def snapshot_exemplars(self) -> dict:
        """Latest (trace_id, value, unix_ts) exemplar per histogram bucket,
        keyed by the same flat ``name_bucket{le="b"}`` key the metrics
        snapshot emits — obs/prometheus.py renders these as OpenMetrics
        ``# {trace_id="..."} value ts`` exemplar suffixes."""
        out = {}
        with self._lock:
            for key, h in self.histograms.items():
                for idx, ex in h.exemplars.items():
                    le = (_fmt_le(h.buckets[idx]) if idx < len(h.buckets)
                          else "+Inf")
                    out[_bucket_key(key, le)] = ex
        return out


class span:
    """Time a named region: ``with span("fit/dispatch"): ...`` or
    ``@span("data/decode")``. Keyword args become span args in the export
    (e.g. ``span("fit/step", step=12)``); ``sp.set(...)`` attaches more from
    inside the region. After exit ``sp.duration`` holds the measured seconds,
    ring on or off, and the same seconds are in ``phase_totals()``.

    ``profiler=False`` keeps the span off a live jax profiler session. It is
    for a span that encloses whole steps (``fit/step``): a reader that gives a
    device gap to the host span covering most of it (benchmarks/xplane.py)
    would hand every gap to the enclosing span."""

    __slots__ = ("name", "args", "duration", "id", "parent", "_profiler",
                 "_t0", "_state", "_annotation")

    def __init__(self, name: str, *, profiler: bool = True, **args):
        self.name = name
        self.args = args or None
        self.duration = None
        self._profiler = profiler

    def set(self, **args) -> "span":
        if self.args is None:
            self.args = args
        else:
            self.args.update(args)
        return self

    def __enter__(self) -> "span":
        state = self._state = getattr(_TLS, "state", None) or _thread_state()
        stack = state[0]
        self.id = next(_SPAN_IDS)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        note = None
        if (self._profiler and _annotation is not None
                and _annotation.is_enabled()):   # a profiler session is live
            note = _annotation(self.name)
            note.__enter__()
        self._annotation = note
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *_exc) -> bool:
        dur = self.duration = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
        stack, totals = self._state
        if stack and stack[-1] is self:
            stack.pop()
            depth = len(stack)
        elif self in stack:
            # closed out of order: fit/warmup opens before the first
            # fit/step and ends inside it
            depth = stack.index(self)
            del stack[depth]
        else:
            depth = 0
        cell = totals.get(self.name)
        if cell is None:
            totals[self.name] = [1, dur]
        else:
            cell[0] += 1
            cell[1] += dur
        tr = _tracer
        if tr is not None:
            # ambient trace context (obs/context.py): a span recorded while
            # a request's trace_context is bound on this thread inherits its
            # trace_id, so cross-layer request timelines need no explicit
            # plumbing on every span site. An explicit trace_id arg wins.
            tid = current_trace_id()
            if tid is not None:
                if self.args is None:
                    self.args = {"trace_id": tid}
                else:
                    self.args.setdefault("trace_id", tid)
            tr._record(self.name, self._t0, dur, depth, self.args,
                       self.id, self.parent)
        return False

    def __call__(self, fn):
        name, args, profiler = self.name, self.args, self._profiler

        @functools.wraps(fn)
        def wrapped(*a, **kw):
            with span(name, profiler=profiler, **(args or {})):
                return fn(*a, **kw)

        return wrapped


# ---------------------------------------------------------------------------
# module-level API
# ---------------------------------------------------------------------------

def configure(capacity: int = 65536) -> Tracer:
    """Enable tracing. Idempotent: an already-live tracer is kept (nested
    subsystems can all call configure without clobbering spans — the ring is
    process-wide and accumulates until ``disable()``), but a changed
    ``capacity`` resizes the ring in place (keeping the newest spans) rather
    than being silently ignored."""
    global _tracer
    if _tracer is None:
        _tracer = Tracer(capacity)
    elif capacity != _tracer.capacity:
        with _tracer._lock:
            _tracer.spans = deque(_tracer.spans, maxlen=capacity)
            _tracer.capacity = capacity
    return _tracer


def disable() -> None:
    """Turn tracing off and drop the ring (mainly for tests)."""
    global _tracer
    _tracer = None


def enabled() -> bool:
    return _tracer is not None


def get_tracer() -> Optional[Tracer]:
    return _tracer


def _label_escape(value) -> str:
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def labeled_name(name: str, labels: Optional[dict]) -> str:
    """Canonical registry key for a labeled series: the Prometheus sample
    spelling ``name{k="v",...}`` with sorted keys and escaped values. Two
    calls with equal labels in any order land on ONE series — dimensions
    stay labels (obs/prometheus.py renders them as such), never mangled
    into the metric name."""
    if not labels:
        return name
    items = ",".join(f'{k}="{_label_escape(v)}"'
                     for k, v in sorted(labels.items()))
    return f"{name}{{{items}}}"


def counter_add(name: str, value: float = 1.0,
                labels: Optional[dict] = None) -> None:
    tr = _tracer
    if tr is None:
        return
    name = labeled_name(name, labels)
    with tr._lock:
        tr.counters[name] = tr.counters.get(name, 0) + value


def gauge_set(name: str, value: float,
              labels: Optional[dict] = None) -> None:
    tr = _tracer
    if tr is None:
        return
    name = labeled_name(name, labels)
    with tr._lock:
        tr.gauges[name] = float(value)


def histogram_observe(name: str, value: float,
                      buckets: Optional[tuple] = None,
                      labels: Optional[dict] = None,
                      trace_id: Optional[str] = None) -> None:
    """Observe one sample into a native histogram (TTFT, queue wait, decode
    step, chunk prefill — the latency shapes a single gauge cannot carry).
    ``buckets`` fixes the boundaries on first observation (default
    ``DEFAULT_BUCKETS``; must be sorted, ≤ ``MAX_HISTOGRAM_BUCKETS`` — the
    histogram-unbounded-buckets lint enforces that they are also *literals*,
    never data-derived). The sample's trace_id (explicit, else the thread's
    ambient one) is kept as the bucket's exemplar, so a p95 spike on a
    dashboard links straight back to one request timeline. No-op when
    tracing is off."""
    tr = _tracer
    if tr is None:
        return
    if trace_id is None:
        trace_id = current_trace_id()
    key = labeled_name(name, labels)
    value = float(value)
    with tr._lock:
        h = tr.histograms.get(key)
        if h is None:
            bounds = tuple(buckets) if buckets is not None else DEFAULT_BUCKETS
            if len(bounds) > MAX_HISTOGRAM_BUCKETS:
                raise ValueError(
                    f"histogram {name!r}: {len(bounds)} buckets exceeds "
                    f"MAX_HISTOGRAM_BUCKETS={MAX_HISTOGRAM_BUCKETS}")
            if list(bounds) != sorted(bounds):
                raise ValueError(f"histogram {name!r}: buckets not sorted")
            h = tr.histograms[key] = _Histogram(bounds)
        idx = len(h.buckets)
        for i, bound in enumerate(h.buckets):
            if value <= bound:
                idx = i
                break
        h.counts[idx] += 1
        h.sum += value
        h.count += 1
        if trace_id is not None:
            h.exemplars[idx] = (trace_id, value, time.time())


def metrics_snapshot() -> dict:
    """Current counters+gauges ({} when tracing is disabled). Recorder-ring
    overflow rides along as ``obs.events_dropped_total`` so telemetry loss
    reaches Prometheus (graftlens satellite: the count existed, the export
    path did not)."""
    tr = _tracer
    if tr is None:
        return {}
    out = tr.snapshot_metrics()
    from .recorder import get_recorder   # lazy: recorder imports us in dump()
    rec = get_recorder()
    if rec is not None and rec.events_dropped:
        out["obs.events_dropped_total"] = float(rec.events_dropped)
    return out


def exemplars_snapshot() -> dict:
    """Current histogram exemplars ({} when tracing is disabled)."""
    tr = _tracer
    return tr.snapshot_exemplars() if tr is not None else {}


def record_span(name: str, start_perf_s: float, duration_s: float,
                **args) -> None:
    """Record a completed span retrospectively — for long-lived OVERLAPPING
    regions that cannot respect the per-thread with-block stack discipline
    (e.g. one span per in-flight serve request: N requests overlap in one
    thread, so entering N ``span`` contexts would corrupt the stack the
    watchdog reads). ``start_perf_s`` is a ``time.perf_counter()`` timestamp
    captured at region start; the record lands in the same ring as regular
    spans (depth 0) and exports identically. No-op when tracing is off.
    Like ``span``, inherits the thread's ambient trace_id (obs/context.py)
    unless one is passed explicitly. Ring only: it reaches neither
    ``phase_totals()`` nor the profiler."""
    tr = _tracer
    if tr is None:
        return
    tid = current_trace_id()
    if tid is not None and "trace_id" not in args:
        args["trace_id"] = tid
    tr._record(name, start_perf_s, duration_s, 0, args or None,
               next(_SPAN_IDS), None)


def open_spans() -> dict:
    """Live per-thread open-span stacks, outermost first:
    ``{"MainThread:140..": ["fit/step", "fit/dispatch"], ...}``. The stall
    watchdog's "where is it stuck" signal."""
    out = {}
    for ident, (tname, stack, _totals) in list(_STACKS.items()):
        names = [sp.name for sp in list(stack)]
        if names:
            out[f"{tname}:{ident}"] = names
    return out


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------

def span_row_json(tr: Tracer, row: tuple) -> dict:
    """One ring record as the JSON object of ``spans.jsonl`` and of the
    telemetry payload (obs/collect.py): absolute ``ts`` (unix seconds),
    ``dur_s``, thread id, nesting depth, ``id``, ``parent`` and args."""
    name, rel, dur, tid, depth, args, span_id, parent = row
    rec = {"name": name, "ts": tr.epoch_origin + rel, "rel_s": rel,
           "dur_s": dur, "tid": tid, "depth": depth, "id": span_id,
           "parent": parent}
    if args:
        rec["args"] = args
    return rec


def export_spans_jsonl(path: str, tracer: Optional[Tracer] = None) -> int:
    """Write the ring as JSONL — one ``span_row_json`` object per line.
    Returns the number of spans written."""
    tr = tracer or _tracer
    if tr is None:
        return 0
    rows = tr.snapshot_spans()
    with open(path, "w") as fh:
        for row in rows:
            fh.write(json.dumps(span_row_json(tr, row)) + "\n")
    return len(rows)


def export_chrome_trace(path: str, tracer: Optional[Tracer] = None, *,
                        request_tracks: bool = False) -> int:
    """Write the ring as Chrome ``trace_event`` JSON (complete "X" events,
    microsecond timestamps) — open in Perfetto or chrome://tracing. Returns
    the number of events written.

    ``request_tracks=True`` additionally reassembles every trace_id-tagged
    span onto a per-request timeline track under a synthetic "requests"
    process: one row per trace_id holding that request's spans from EVERY
    thread it crossed (gateway connection thread, engine worker, a failover
    replica), in wall-clock order — queue-wait → prefill → per-row decode →
    SSE flush read left to right on one row. The real per-thread tracks are
    kept alongside; the request rows are a second view of the same spans."""
    tr = tracer or _tracer
    if tr is None:
        return 0
    pid = os.getpid()
    events = []
    rows = tr.snapshot_spans()
    for name, rel, dur, tid, depth, args, span_id, parent in rows:
        events.append({"name": name, "ph": "X", "pid": pid, "tid": tid,
                       "ts": rel * 1e6, "dur": dur * 1e6,
                       "args": dict(args or {}, id=span_id, parent=parent)})
    if request_tracks:
        # synthetic process 1: one virtual tid per trace_id, named after it
        track_ids: dict = {}
        events.append({"ph": "M", "pid": 1, "tid": 0,
                       "name": "process_name",
                       "args": {"name": "requests (graftscope)"}})
        for name, rel, dur, tid, _depth, args, _id, _parent in rows:
            trace_id = (args or {}).get("trace_id")
            if trace_id is None:
                continue
            vtid = track_ids.get(trace_id)
            if vtid is None:
                vtid = track_ids[trace_id] = len(track_ids) + 1
                events.append({"ph": "M", "pid": 1, "tid": vtid,
                               "name": "thread_name",
                               "args": {"name": f"request {trace_id}"}})
            events.append({"name": name, "ph": "X", "pid": 1, "tid": vtid,
                           "ts": rel * 1e6, "dur": dur * 1e6,
                           "args": dict(args, source_tid=tid)})
    doc = {"traceEvents": events, "displayTimeUnit": "ms",
           "metadata": {"epoch_origin": tr.epoch_origin,
                        "spans_dropped": tr.dropped}}
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return len(events)
