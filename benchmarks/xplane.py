"""Reduction of a jax profiler trace (``.xplane.pb``) to the numbers the
benchmark reports: device busy time (the union of the intervals in which an
operation ran), idle share, time by operation name, and the longest idle gaps
named by what the host was doing in them.

``load`` reads the file with nothing but jax; everything after it works on
plain lists of ``(name, start_ns, end_ns)``, which the tests build by hand.
"""

from __future__ import annotations

import glob
import os

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE_PREFIX = "/host:"
MARK_OPEN = "bench/trace_open"
MARK_CLOSE = "bench/trace_close"


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str) -> dict:
    """{"devices": {plane name: [(name, start, end), ...]},
        "host": [(name, start, end), ...]} in nanoseconds on the trace's
    clock. Device events are those of each device plane's "XLA Ops" line;
    host events are every event of every host plane's lines."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if (plane.name.startswith(DEVICE_PLANE_PREFIX)
                and plane.name[len(DEVICE_PLANE_PREFIX):].isdigit()):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            devices[plane.name] = ops
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
    return {"devices": devices, "host": host}


MOSAIC_MARK = "tpu_custom_call"   # how the trace names a Pallas kernel's call


def is_mosaic(name: str) -> bool:
    """True for the events of Pallas (Mosaic) kernels: the trace names a
    device operation by its whole HLO instruction, custom-call target and
    all. The program gives its kernels no names of their own yet."""
    return MOSAIC_MARK in name


def short_name(name: str, limit: int = 120) -> str:
    """An HLO instruction's text cut to what a reader needs: layouts and
    operands' layouts dropped, the rest cut at ``limit`` characters, with
    ``[mosaic]`` in front where it is a Pallas kernel's call."""
    import re
    short = re.sub(r"\{[^{}]*\}", "", name)
    short = short if len(short) <= limit else short[:limit - 3] + "..."
    return ("[mosaic] " + short) if is_mosaic(name) else short


def clip(events, lo, hi):
    """Events cut to [lo, hi]; those outside are dropped."""
    out = []
    for name, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((name, a, b))
    return out


def union_intervals(events) -> list:
    """Sorted, disjoint [start, end] covering every event."""
    merged = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_ns(events) -> int:
    return sum(b - a for a, b in union_intervals(events))


def gaps(events, lo, hi) -> list:
    """The idle intervals of [lo, hi]: where no event ran."""
    out, at = [], lo
    for a, b in union_intervals(clip(events, lo, hi)):
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def self_time_by_name(events) -> dict:
    """name -> duration in ns with the time of events nested inside an event
    taken out of it, so the values add up to the busy time."""
    out = {}
    stack = []   # (name, end, self time so far)
    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= a:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0) + done[2]
        if stack:
            stack[-1][2] -= min(b, stack[-1][1]) - a
        stack.append([name, b, b - a])
    for done in stack:
        out[done[0]] = out.get(done[0], 0) + done[2]
    return out


def window(host_events):
    """[start, end] of the traced window in ns: from the benchmark's own
    open mark to its close mark on the host's planes. None without both."""
    opens = [a for name, a, _ in host_events if name == MARK_OPEN]
    closes = [b for name, _, b in host_events if name == MARK_CLOSE]
    if not opens or not closes:
        return None
    return min(opens), max(closes)


def name_gaps(gap_list, host_events, top: int = 10) -> list:
    """[[host span name, seconds]]: each gap goes to the host span that
    overlaps most of it (the benchmark's own marks aside), gaps of one name
    are summed, the largest ``top`` are kept."""
    shortest = min((hi - lo for lo, hi in gap_list), default=0)
    # a span much shorter than the shortest gap cannot explain one
    spans = [e for e in host_events if e[0] not in (MARK_OPEN, MARK_CLOSE)
             and e[2] - e[1] >= shortest / 10]
    spans.sort(key=lambda e: e[1])
    totals = {}
    for lo, hi in gap_list:
        best, best_overlap = "no host span", 0
        for name, a, b in spans:
            if a >= hi:
                break
            overlap = min(b, hi) - max(a, lo)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        totals[best] = totals.get(best, 0) + (hi - lo)
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[name, ns / 1e9] for name, ns in ranked]


def reduce(trace: dict, top: int = 10) -> dict:
    """The whole reduction. ``busy_s`` is averaged over the device planes;
    ``ops`` (self time by name, seconds) and the gaps are the first
    device's."""
    win = window(trace["host"])
    if win is None:
        # the extent of the device's operations would drop the idle time
        # before the first and after the last: idle would read too low
        raise ValueError("the trace lacks the benchmark's open or close "
                         "mark: no window to measure busy and idle in")
    lo, hi = win
    per_device = {name: clip(ev, lo, hi)
                  for name, ev in sorted(trace["devices"].items())}
    if not per_device or not any(per_device.values()):
        raise ValueError("no device operation ran inside the traced window")
    busy = [busy_ns(ev) for ev in per_device.values()]
    first = next(iter(per_device.values()))
    ops = {k: v / 1e9 for k, v in self_time_by_name(first).items()}
    # only gaps long enough to see; the host's planes hold many events
    long_gaps = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:200]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy) / 1e9,
        "ops": ops,
        "device_ops": [[short_name(k), v] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": name_gaps(long_gaps, trace["host"], top),
        "events": first,
    }
