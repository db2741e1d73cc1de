"""grafttrace — span-based runtime telemetry for training and decode.

The observability layer the ROADMAP's "fast as the hardware allows" goal
needs: ``span`` timing regions into a ring buffer (Perfetto/JSONL export),
counters/gauges that merge into ``MetricsLogger`` records and a Prometheus
textfile, device telemetry (HBM + live recompile rate), and a stall
watchdog. See docs/OBSERVABILITY.md for the operator guide.

The ring, counters and exports are off by default (``configure()`` turns
them on: ``TrainConfig.obs.trace`` / ``--obs.trace true`` from the CLIs). A
``span`` always times itself (``sp.duration``, ``phase_totals()``: a couple
of microseconds) and, while a jax profiler session is live, lands on its host
plane under the same name.

Two submodules are the runtime halves of static analysis layers and are
imported explicitly by the smokes (never re-exported here):
:mod:`dalle_tpu.obs.lockorder` records observed lock-acquisition edges
against graftsync's golden lock graph, and :mod:`dalle_tpu.obs.wiretap`
records observed wire-frame shapes against graftwire's golden protocol
contract (``contracts/wire.json``).
"""

from .anomaly import (Breach, CodebookCollapseDetector, GradExplosionDetector,
                      HealthSentry, LossSpikeDetector, NaNPrecursorDetector)
from .collect import (ClockOffsetEstimator, TelemetryCollector,
                      TelemetryExporter, UsageLedger, read_telemetry_dir,
                      telemetry_payload)
from .context import current_trace_id, new_trace_id, trace_context
from .prometheus import render_textfile, sanitize_metric_name, write_textfile
from .recorder import (FlightRecorder, collect_state, configure_recorder,
                       disable_recorder, dump_recorder, get_recorder,
                       install_signal_dump, record_event,
                       register_state_provider, unregister_state_provider)
from .report import (format_request_timeline, request_timeline,
                     span_overhead_s, summarize_run)
from .slo import BurnRateSentry
from .trace import (DEFAULT_BUCKETS, configure, counter_add, disable, enabled,
                    exemplars_snapshot, export_chrome_trace,
                    export_spans_jsonl, gauge_set, histogram_observe,
                    metrics_snapshot, open_spans, phase_totals, record_span,
                    reset_phase_totals, span)
from .watchdog import StallWatchdog

_DEVICE_NAMES = ("CompileCounter", "DeviceTelemetry", "device_memory_stats",
                 "install_compile_counter")

# graftpulse in-jit taps (obs/health.py) import jax; resolved lazily like
# obs.device so the host-side anomaly/report layers stay jax-free
_HEALTH_NAMES = ("layer_groups", "tree_health", "codebook_health",
                 "gumbel_health", "decode_quality")

__all__ = [
    *_DEVICE_NAMES, *_HEALTH_NAMES,
    "Breach", "CodebookCollapseDetector", "GradExplosionDetector",
    "HealthSentry", "LossSpikeDetector", "NaNPrecursorDetector",
    "ClockOffsetEstimator", "TelemetryCollector", "TelemetryExporter",
    "UsageLedger", "read_telemetry_dir", "telemetry_payload",
    "current_trace_id", "new_trace_id", "trace_context",
    "render_textfile", "sanitize_metric_name", "write_textfile",
    "FlightRecorder", "collect_state", "configure_recorder",
    "disable_recorder", "dump_recorder", "get_recorder",
    "install_signal_dump", "record_event", "register_state_provider",
    "unregister_state_provider", "format_request_timeline",
    "request_timeline", "span_overhead_s", "summarize_run",
    "BurnRateSentry", "DEFAULT_BUCKETS",
    "configure", "counter_add", "disable", "enabled", "exemplars_snapshot",
    "export_chrome_trace", "export_spans_jsonl", "gauge_set",
    "histogram_observe", "metrics_snapshot", "open_spans", "phase_totals",
    "record_span", "reset_phase_totals", "span", "StallWatchdog",
]


def __getattr__(name):
    # obs.device is the one jax-importing submodule; resolving it lazily
    # keeps `from ..obs.trace import span` in the host-side data pipeline
    # (loaders/webdataset) from dragging jax into pure-numpy importers
    if name in _DEVICE_NAMES:
        from . import device
        return getattr(device, name)
    if name in _HEALTH_NAMES:
        from . import health
        return getattr(health, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
