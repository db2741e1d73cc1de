"""What the per-layer metrics read by the program's own names: ``fit()``'s
phase spans (in its records, and over the device's idle gaps once
``obs.span`` is on the profiler's clock), the set-up spans' totals, and the
Pallas kernels' names in the device's events. On a program that lacks a
name (the commit before the PR that brought it) every reader here finds
nothing and returns None."""

from __future__ import annotations

import re
import statistics

from benchmarks import arith, xplane

# a span of the program is "<layer>/<what>" in lower case: "fit/dispatch",
# "dalle/step", "data/h2d". jax's own host events ("PjitFunction(step)",
# "np.asarray(jax.Array)", "tpu::System::Execute=>Done") are not.
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*(/[a-z0-9_]+)+")


def record_median_ms(run: dict, key: str):
    """Median over the window's records of a ``t_*`` column of ``fit()``'s
    breakdown (seconds, each the duration of one span), in ms."""
    values = [m[key] for _, _, m in run["records"] if key in m]
    return 1e3 * statistics.median(values) if values else None


def idle_share_pct(run: dict, span: str):
    """The share of the traced window that the device idled under the host
    span ``span``: seconds under that name among ``idle_gaps`` over
    ``window_s``. None without a trace, and None when no span of the program
    names any gap (the bridge to the profiler is missing or broken: a metric
    that is absent, not 0); 0.0 when others do and this one does not."""
    trace = run.get("trace")
    if not trace or not trace.get("idle_gaps"):
        return None
    gaps = {name: seconds for name, seconds in trace["idle_gaps"]}
    if not any(PROGRAM_SPAN.fullmatch(name) for name in gaps):
        return None
    return 100.0 * gaps.get(span, 0.0) / trace["window_s"]


def phase_total_s(name: str):
    """Seconds the run's process spent under the span ``name`` so far
    (``dalle_tpu.obs.phase_totals()``: always on, no ring needed)."""
    from dalle_tpu import obs
    totals = getattr(obs, "phase_totals", None)
    if totals is None:
        return None
    count_and_seconds = totals().get(name)
    return count_and_seconds[1] if count_and_seconds else None


def kernel_seconds(ops: dict, kernel: str) -> float:
    """Device time of the Mosaic calls that the program named ``kernel``
    (``pl.pallas_call(name=...)``): the trace names an operation by its HLO
    instruction, "%<kernel>.<n> = ... custom-call(...)"; a kernel's name
    among another instruction's operands does not count."""
    named = re.compile(r"%?" + re.escape(kernel) + r"(\.\d+)?")
    return sum(s for name, s in ops.items() if xplane.is_mosaic(name)
               and named.fullmatch(name.split(" = ", 1)[0].strip()))


def fused_attention_roofline_pct(run: dict, kernel: str, backward: bool):
    """One direction of the fused attention kernel against its roofline: the
    least time for one call a layer a step (``arith.causal_attention_cost``:
    a forward that the backward recomputes adds time, not work) over the
    device time of the events named ``kernel``."""
    if not run.get("trace") or not run["traced"]["steps"]:
        return None
    seconds = kernel_seconds(run["trace"]["ops"], kernel)
    if seconds <= 0:
        return None
    m = run["config"]["model"]
    n = m["text_seq_len"] + m["image_fmap_size"] ** 2
    cost = arith.causal_attention_cost(run["cell"]["traffic"]["batch"],
                                       m["heads"], n, m["dim_head"],
                                       backward=backward)
    least = arith.least_seconds(cost, arith.peaks_for(run["device"]["kind"]))[0]
    return 100.0 * least * m["depth"] * run["traced"]["steps"] / seconds
