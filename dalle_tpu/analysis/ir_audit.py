"""graftir — jaxpr/HLO-level program contracts for the registered entry points.

graftlint (PR 1) reads source text; the hazards that actually burn TPU time
live in the traced program: a silent bf16→f32 ``convert_element_type`` in the
step, a refactor that doubles the collective count under fsdp, a
``donate_argnums`` XLA quietly declines to alias, a host callback hiding
behind a library call. This module extracts a **program contract** from the
ClosedJaxpr (and, for compiled entries, the optimized HLO) of an entry point:

  * primitive histogram — every primitive, counted recursively through
    nested jaxprs (scan/cond/while/pjit/custom_vjp/pallas_call kernels);
  * dtype-promotion events — each ``convert_element_type`` that WIDENS a
    value to a floating dtype, with source provenance (file::function);
  * host-transfer sites — callback/infeed/outfeed primitives in the program;
  * collective inventory — kind × per-device operand bytes × mesh axes,
    parsed from the compiled HLO (GSPMD inserts collectives at compile time,
    so the jaxpr alone cannot see them);
  * donation effectiveness — donated inputs actually aliased to outputs in
    the compiled executable (``input_output_alias``);
  * an analytic peak-memory estimate — linear liveness scan over the jaxpr
    (deterministic, version-stable; compared with tolerance).

Contracts serialize to golden JSON under ``contracts/`` and are enforced by
``scripts/ir_audit.py --check`` (CI). Waivers are source comments next to
the code they excuse, graftlint-style::

    # graftir: allow=donation -- <reason>

and apply to the entry whose ``source`` file carries them. A waiver without
a reason is itself a finding. The entry registry lives in
:mod:`dalle_tpu.analysis.contracts`.
"""

from __future__ import annotations

import dataclasses
import io
import json
import os
import re
import tokenize
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .core import REPO_ROOT

SCHEMA = 2

# drift checks a source waiver can silence, and the invariant checks.
# "precision" covers both the contract's quantization-boundary-map drift
# (below) and the precision-flow rule findings scripts/precision_audit.py
# enforces (analysis/precision_flow.py).
RULES = ("primitives", "promotions", "transfers", "collectives", "memory",
         "donation", "precision")

# memory estimate is analytic; small jaxpr-preserving refactors can move it
# a little without a real regression — compare with tolerance
MEMORY_RTOL = 0.05

_WAIVER_RE = re.compile(r"#\s*graftir:\s*allow=([\w\-]+)(?:\s*--\s*(.*))?")

_TRANSFER_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                   "infeed", "outfeed"}

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "collective-permute", "all-to-all")

_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}


# --------------------------------------------------------------------------
# jaxpr walking
# --------------------------------------------------------------------------

def _jax():
    import jax
    return jax


def _aval_bytes(aval) -> int:
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    try:
        itemsize = np.dtype(dtype).itemsize
    except TypeError:
        # extended dtypes (PRNG key<fry> etc.) aren't numpy dtypes but do
        # carry their storage itemsize
        itemsize = getattr(dtype, "itemsize", 0)
    return int(size) * int(itemsize)


def _sub_jaxprs(params: dict):
    """Nested (Closed)Jaxprs hiding in an eqn's params, recursively."""
    import jax.extend.core as jex_core

    def walk(val):
        if isinstance(val, jex_core.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, jex_core.Jaxpr):
            yield val
        elif isinstance(val, (list, tuple)):
            for v in val:
                yield from walk(v)

    for val in params.values():
        yield from walk(val)


def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and its nested jaxprs (static occurrence count:
    an eqn inside a scan body is counted once, not ``length`` times)."""
    stack = [jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            stack.extend(_sub_jaxprs(eqn.params))


def unwrap_jaxpr(closed):
    """The traced body of a jitted fn is one pjit eqn — descend to it so the
    top-level liveness scan sees the real program."""
    j = closed.jaxpr
    while len(j.eqns) == 1 and j.eqns[0].primitive.name in ("pjit", "jit",
                                                            "closed_call"):
        inner = list(_sub_jaxprs(j.eqns[0].params))
        if not inner:
            break
        j = inner[0]
    return j


def primitive_histogram(closed) -> Dict[str, int]:
    counts = Counter(eqn.primitive.name for eqn in iter_eqns(closed.jaxpr))
    return dict(sorted(counts.items()))


def _site_of(eqn) -> Tuple[str, int]:
    """("relpath::function", line) of the user frame that emitted ``eqn`` —
    the contract keys on file::function only, so unrelated edits that shift
    line numbers don't read as drift."""
    from jax._src import source_info_util

    def rel(path):
        try:
            r = os.path.relpath(path, REPO_ROOT)
        except ValueError:
            return None
        return None if r.startswith("..") else r.replace(os.sep, "/")

    # innermost frame inside this repo: library frames between it and the
    # primitive (flax's LayerNorm, optax's losses) name no site we can edit
    frames = list(source_info_util.user_frames(eqn.source_info.traceback))
    if not frames:
        return "<unknown>", 0
    frame = next((f for f in frames if rel(f.file_name)), frames[0])
    path = rel(frame.file_name) or os.path.basename(frame.file_name)
    # function_name is the qualified name; contracts key on the bare one
    func = frame.function_name.rsplit(".", 1)[-1]
    return f"{path}::{func}", int(frame.start_line or 0)


def promotion_events(closed) -> List[dict]:
    """convert_element_type eqns that WIDEN to a floating dtype (bf16→f32,
    int8→bf16 dequant, f32→f64...), aggregated by (src, dst, site)."""
    agg: Dict[Tuple[str, str, str], dict] = {}
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src_aval, dst_aval = eqn.invars[0].aval, eqn.outvars[0].aval
        src = np.dtype(src_aval.dtype)
        dst = np.dtype(dst_aval.dtype)
        if not (np.issubdtype(dst, np.floating)
                and dst.itemsize > src.itemsize):
            continue
        site, line = _site_of(eqn)
        key = (src.name, dst.name, site)
        ev = agg.setdefault(key, {"src": src.name, "dst": dst.name,
                                  "site": site, "count": 0, "bytes": 0})
        ev["count"] += 1
        ev["bytes"] += _aval_bytes(dst_aval)
    return sorted(agg.values(), key=lambda e: (e["site"], e["src"], e["dst"]))


def transfer_sites(closed) -> List[dict]:
    """Host round-trip primitives in the program (callbacks, infeed/outfeed).
    ``device_get``-style syncs cannot appear inside a traced program — those
    are source-level and covered by graftlint's host-sync-in-jit rule."""
    agg: Dict[Tuple[str, str], dict] = {}
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if name not in _TRANSFER_PRIMS:
            continue
        site, _ = _site_of(eqn)
        ev = agg.setdefault((name, site),
                            {"primitive": name, "site": site, "count": 0})
        ev["count"] += 1
    return sorted(agg.values(), key=lambda e: (e["primitive"], e["site"]))


def peak_memory_estimate(closed) -> dict:
    """Analytic liveness scan over the (unwrapped) jaxpr: walk eqns in
    program order, track live value bytes (a var dies after its last use),
    charge each eqn its outputs plus the transient peak of its nested
    jaxprs. An ESTIMATE — XLA fuses and rematerializes — but deterministic
    for a given program, which is what a drift check needs."""
    import jax.core as core
    import jax.extend.core as jex_core

    def scan(jaxpr) -> Tuple[int, int]:
        """(peak_bytes, resident_in_out_bytes) for one jaxpr."""
        last_use: Dict = {}
        for i, eqn in enumerate(jaxpr.eqns):
            for v in eqn.invars:
                if isinstance(v, jex_core.Var):
                    last_use[v] = i
        n = len(jaxpr.eqns)
        for v in jaxpr.outvars:
            if isinstance(v, jex_core.Var):
                last_use[v] = n
        live: Dict = {}
        for v in list(jaxpr.invars) + list(jaxpr.constvars):
            live[v] = _aval_bytes(v.aval)
        live_bytes = sum(live.values())
        peak = live_bytes
        for i, eqn in enumerate(jaxpr.eqns):
            inner = 0
            for sub in _sub_jaxprs(eqn.params):
                inner = max(inner, scan(sub)[0])
            out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars
                            if not isinstance(v, core.DropVar))
            peak = max(peak, live_bytes + out_bytes + inner)
            for v in eqn.outvars:
                if isinstance(v, core.DropVar):
                    continue
                if v not in live:
                    live[v] = _aval_bytes(v.aval)
                    live_bytes += live[v]
            dead = [v for v, at in last_use.items() if at == i and v in live]
            for v in dead:
                live_bytes -= live.pop(v)
                del last_use[v]
        return peak, live_bytes

    j = unwrap_jaxpr(closed)
    arg_bytes = sum(_aval_bytes(v.aval) for v in j.invars)
    out_bytes = sum(_aval_bytes(getattr(v, "aval", None)) for v in j.outvars
                    if hasattr(v, "aval"))
    peak, _ = scan(j)
    return {"peak_bytes_est": int(peak), "arg_bytes": int(arg_bytes),
            "out_bytes": int(out_bytes)}


# --------------------------------------------------------------------------
# compiled-HLO parsing: collectives + donation aliasing
# --------------------------------------------------------------------------

def _parse_hlo_shapes(arglist: str) -> int:
    """Total bytes of the HLO operand list ``f32[8,16]{1,0} %a, bf16[4] %b``."""
    total = 0
    for dtype, dims in re.findall(r"\b(\w+)\[([\d,]*)\]", arglist):
        if dtype not in _HLO_DTYPE_BYTES:
            continue
        numel = 1
        for d in dims.split(","):
            if d:
                numel *= int(d)
        total += numel * _HLO_DTYPE_BYTES[dtype]
    return total


def parse_replica_groups(text: str) -> List[frozenset]:
    """HLO ``replica_groups`` in either the explicit ``{{0,1},{2,3}}`` form or
    the iota form ``[4,2]<=[8]`` / ``[4,2]<=[2,2,2]T(2,1,0)``."""
    text = text.strip()
    if text.startswith("{"):
        return [frozenset(int(x) for x in g.split(","))
                for g in re.findall(r"\{([\d,]+)\}", text)]
    m = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", text)
    if not m:
        return []
    gshape = [int(x) for x in m.group(1).split(",")]
    dims = [int(x) for x in m.group(2).split(",")]
    ids = np.arange(int(np.prod(dims))).reshape(dims)
    if m.group(3):
        ids = ids.transpose([int(x) for x in m.group(3).split(",")])
    ids = ids.reshape(gshape)
    return [frozenset(int(x) for x in row) for row in ids]


def mesh_axis_groups(mesh, axes: Sequence[str]) -> List[frozenset]:
    """Device-id groups a collective over ``axes`` of ``mesh`` would form."""
    names = list(mesh.axis_names)
    ids = np.vectorize(lambda d: d.id)(mesh.devices)
    order = [i for i, n in enumerate(names) if n not in axes] + \
            [i for i, n in enumerate(names) if n in axes]
    moved = np.transpose(ids, order)
    group = int(np.prod([mesh.shape[a] for a in axes]))
    return [frozenset(int(x) for x in row)
            for row in moved.reshape(-1, group)]


def axes_for_groups(mesh, groups: List[frozenset]) -> str:
    """Mesh axis names matching a set of replica groups; smallest matching
    subset of the >1-sized axes wins (a size-1 axis never changes groups)."""
    import itertools
    if not groups or all(len(g) <= 1 for g in groups):
        return "none"
    real = [a for a in mesh.axis_names if mesh.shape[a] > 1]
    want = set(groups)
    for r in range(1, len(real) + 1):
        for combo in itertools.combinations(real, r):
            if set(mesh_axis_groups(mesh, combo)) == want:
                return ",".join(combo)
    return "unmatched"


def axes_for_pairs(mesh, pairs: List[Tuple[int, int]]) -> str:
    """Mesh axes a ``source_target_pairs`` permutation moves data across:
    the union, over pairs, of axes whose device coordinates differ between
    source and target. A ring shift along one axis names that axis; a GSPMD
    resharding permute names every axis it crosses."""
    coords: Dict[int, dict] = {}
    it = np.nditer(np.vectorize(lambda d: d.id)(mesh.devices),
                   flags=["multi_index"])
    for did in it:
        coords[int(did)] = dict(zip(mesh.axis_names, it.multi_index))
    moved = set()
    for a, b in pairs:
        ca, cb = coords.get(a), coords.get(b)
        if ca is None or cb is None:
            return "unknown"
        moved.update(ax for ax in mesh.axis_names if ca[ax] != cb[ax])
    if not moved:
        return "none"
    return ",".join(ax for ax in mesh.axis_names if ax in moved)


def collective_inventory(hlo_text: str, mesh=None) -> List[dict]:
    """Collective instructions in optimized HLO: kind × per-device operand
    bytes × mesh axes, aggregated with counts. ``-done`` halves of async
    pairs are skipped (the ``-start`` carries the operands). Axis
    attribution reads ``replica_groups`` where present; a
    ``collective-permute`` instead carries ``source_target_pairs``, from
    which :func:`axes_for_pairs` recovers the crossed mesh axes."""
    agg: Dict[Tuple[str, int, str], dict] = {}
    # operands print by name only (``all-reduce(%dot)``), so their bytes
    # come from the defining instruction's result shape; names are unique
    # per computation, and an operand is always defined above its use
    def_re = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+)\s*=\s*"
                        r"(\([^)]*\)|\S+)\s")
    defined: Dict[str, int] = {}
    op_re = re.compile(
        r"=\s*(?:\([^)]*\)|\S+)\s+(" + "|".join(_COLLECTIVE_OPS) +
        r")(-start)?\((.*?)\)(?:,|\s)")
    rg_re = re.compile(r"replica_groups=(\{\{[\d,{} ]*\}\}|\[[\d,]+\]<=\["
                       r"[\d,]+\](?:T\([\d,]+\))?)")
    stp_re = re.compile(r"source_target_pairs=\{((?:\{\d+,\d+\},?)*)\}")
    for line in hlo_text.splitlines():
        if line.rstrip().endswith("{"):     # a new computation's scope
            defined = {}
        d = def_re.match(line)
        if d:
            defined[d.group(1)] = _parse_hlo_shapes(d.group(2))
        m = op_re.search(line)
        if not m or f"{m.group(1)}-done" in line:
            continue
        kind = m.group(1)
        nbytes = sum(defined.get(name, 0)
                     for name in re.findall(r"%[\w.\-]+", m.group(3)))
        axes = "unknown"
        rg = rg_re.search(line)
        stp = stp_re.search(line)
        if rg and mesh is not None:
            axes = axes_for_groups(mesh, parse_replica_groups(rg.group(1)))
        elif stp and mesh is not None:
            pairs = [(int(a), int(b)) for a, b in
                     re.findall(r"\{(\d+),(\d+)\}", stp.group(1))]
            axes = axes_for_pairs(mesh, pairs)
        key = (kind, nbytes, axes)
        ev = agg.setdefault(key, {"kind": kind, "bytes": nbytes,
                                  "axes": axes, "count": 0})
        ev["count"] += 1
    return sorted(agg.values(),
                  key=lambda e: (e["kind"], e["axes"], -e["bytes"]))


def donation_report(hlo_text: str, donated_leaves: int) -> dict:
    """input_output_alias pairs in the compiled module header vs the number
    of donated argument leaves. ``aliased < donated`` means XLA declined to
    reuse some donated buffer — the donation is silently not saving the
    memory the code claims it does."""
    marker = "input_output_alias={"
    aliased = 0
    start = hlo_text.find(marker)
    if start != -1:
        # the annotation nests braces ({ {0}: (0, {}, may-alias), ... }) —
        # scan to the BALANCED close; a regex alternation stops at the
        # first inner '}'
        i = j = start + len(marker)
        depth = 1
        while j < len(hlo_text) and depth:
            depth += {"{": 1, "}": -1}.get(hlo_text[j], 0)
            j += 1
        aliased = len(re.findall(r"\(\s*\d+\s*,\s*\{[^}]*\}\s*,\s*"
                                 r"(?:may|must)-alias\)", hlo_text[i:j]))
    return {"donated": int(donated_leaves), "aliased": int(aliased)}


# --------------------------------------------------------------------------
# contract build / serialize / diff
# --------------------------------------------------------------------------

def build_contract(name: str, built) -> dict:
    """Extract the full contract dict for a BuiltEntry (see contracts.py)."""
    from . import precision_flow
    jax = _jax()
    closed = jax.make_jaxpr(built.fn)(*built.args)
    roles = getattr(built, "roles", None)
    if roles is None:
        roles = precision_flow.infer_roles(built.args)
    contract = {
        "schema": SCHEMA,
        "entry": name,
        "primitives": primitive_histogram(closed),
        "promotions": promotion_events(closed),
        "transfers": transfer_sites(closed),
        "collectives": [],
        "donation": None,
        "memory": peak_memory_estimate(closed),
        "precision": precision_flow.analyze(closed, roles).boundary,
        "vmem": built.vmem,
    }
    if built.compile:
        jitted = built.fn if hasattr(built.fn, "lower") else jax.jit(built.fn)
        hlo = jitted.lower(*built.args).compile().as_text()
        contract["collectives"] = collective_inventory(hlo, built.mesh)
        if built.donated:
            contract["donation"] = donation_report(hlo, built.donated)
    return contract


def save_contract(contract: dict, path: str) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(contract, fh, indent=1, sort_keys=True)
        fh.write("\n")


def load_contract(path: str) -> Optional[dict]:
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as fh:
        return json.load(fh)


def _fmt_bytes(n: int) -> str:
    if n >= 1 << 20:
        return f"{n / (1 << 20):.1f} MB"
    if n >= 1 << 10:
        return f"{n / (1 << 10):.1f} KB"
    return f"{n} B"


def _keyed(events: Iterable[dict], keys: Sequence[str]) -> Dict[tuple, dict]:
    return {tuple(e[k] for k in keys): e for e in events}


def _diff_events(old, new, keys, render) -> List[str]:
    o, n = _keyed(old, keys), _keyed(new, keys)
    lines = []
    for k in sorted(set(o) | set(n), key=str):
        oe, ne = o.get(k), n.get(k)
        oc = (oe or {}).get("count", 0)
        nc = (ne or {}).get("count", 0)
        if oc == nc:
            # count-stable but byte-volume drift (an upcast moved from a
            # small tensor to a big one at the same site keeps count==1) —
            # only for event kinds whose bytes are NOT part of the key
            ob = (oe or {}).get("bytes")
            nb = (ne or {}).get("bytes")
            if oe and ne and "bytes" not in keys and ob is not None \
                    and ob != nb:
                lines.append(f"~ {render(ne)} [bytes {_fmt_bytes(ob)} -> "
                             f"{_fmt_bytes(nb)}]")
            continue
        ev = ne or oe
        sign = nc - oc
        lines.append(f"{'+' if sign > 0 else ''}{sign} {render(ev)}"
                     f" [{oc} -> {nc}]")
    return lines


def diff_contracts(old: dict, new: dict) -> Dict[str, List[str]]:
    """Per-rule human-readable drift lines; empty dict == no drift."""
    out: Dict[str, List[str]] = {}

    prim = []
    po, pn = old.get("primitives", {}), new.get("primitives", {})
    for name in sorted(set(po) | set(pn)):
        a, b = po.get(name, 0), pn.get(name, 0)
        if a != b:
            prim.append(f"{name}: {a} -> {b} ({b - a:+d})")
    if prim:
        out["primitives"] = prim

    coll = _diff_events(
        old.get("collectives", []), new.get("collectives", []),
        ("kind", "bytes", "axes"),
        lambda e: f"{e['kind']} {_fmt_bytes(e['bytes'])} on axis "
                  f"'{e['axes']}'")
    if coll:
        out["collectives"] = coll

    prom = _diff_events(
        old.get("promotions", []), new.get("promotions", []),
        ("src", "dst", "site"),
        lambda e: f"promotion {e['src']}->{e['dst']} "
                  f"({_fmt_bytes(e['bytes'])}) at {e['site']}")
    if prom:
        out["promotions"] = prom

    tr = _diff_events(
        old.get("transfers", []), new.get("transfers", []),
        ("primitive", "site"),
        lambda e: f"host transfer {e['primitive']} at {e['site']}")
    if tr:
        out["transfers"] = tr

    om = old.get("memory", {}).get("peak_bytes_est", 0)
    nm = new.get("memory", {}).get("peak_bytes_est", 0)
    if om and abs(nm - om) > om * MEMORY_RTOL:
        out["memory"] = [
            f"peak est {_fmt_bytes(om)} -> {_fmt_bytes(nm)} "
            f"({(nm - om) / om:+.1%}, tol {MEMORY_RTOL:.0%})"]

    # precision: the quantization boundary map (graftnum,
    # analysis/precision_flow.py) — which matmuls consume int8 and at what
    # accumulator width, where dequants happen and which axes their
    # per-channel scales ride, plus the value-class histogram
    po, pn = old.get("precision") or {}, new.get("precision") or {}
    prec: List[str] = []
    co, cn = po.get("class_counts", {}), pn.get("class_counts", {})
    for cls in sorted(set(co) | set(cn)):
        a, b = co.get(cls, 0), cn.get(cls, 0)
        if a != b:
            prec.append(f"value class {cls}: {a} -> {b} ({b - a:+d})")
    prec += _diff_events(
        po.get("int8_dots", []), pn.get("int8_dots", []),
        ("site", "accum"),
        lambda e: f"int8 dot (accum '{e['accum']}') at {e['site']}")
    prec += _diff_events(
        po.get("dequants", []), pn.get("dequants", []),
        ("site", "dst", "scale_axes"),
        lambda e: f"dequant ->{e['dst']} (scale axes {e['scale_axes']}) "
                  f"at {e['site']}")
    if prec:
        out["precision"] = prec
    return out


# --------------------------------------------------------------------------
# waivers
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Waiver:
    rule: str
    reason: str
    line: int


def collect_waivers(source_rel: str,
                    repo_root: Optional[str] = None
                    ) -> Tuple[Dict[str, Waiver], List[str]]:
    """(waivers by rule, problems) from REAL comment tokens of ``source_rel``.
    A waiver must carry a reason (``-- why``); a bare allow is a problem, as
    is an unknown rule name — both would otherwise silently waive nothing or
    the wrong thing. ``repo_root`` resolves lazily so tests can monkeypatch
    the module's ``REPO_ROOT``."""
    path = os.path.join(repo_root or REPO_ROOT, source_rel)
    waivers: Dict[str, Waiver] = {}
    problems: List[str] = []
    if not os.path.exists(path):
        return waivers, problems
    with open(path, encoding="utf-8") as fh:
        src = fh.read()
    try:
        tokens = list(tokenize.generate_tokens(io.StringIO(src).readline))
    except (tokenize.TokenError, IndentationError):
        return waivers, problems
    for tok in tokens:
        if tok.type != tokenize.COMMENT:
            continue
        m = _WAIVER_RE.search(tok.string)
        if not m:
            continue
        rule, reason = m.group(1), (m.group(2) or "").strip()
        if rule not in RULES:
            problems.append(f"{source_rel}:{tok.start[0]}: unknown graftir "
                            f"rule '{rule}' in waiver (known: "
                            f"{', '.join(RULES)})")
            continue
        if not reason:
            problems.append(f"{source_rel}:{tok.start[0]}: graftir waiver "
                            f"for '{rule}' has no reason — write "
                            f"'# graftir: allow={rule} -- <why>'")
            continue
        waivers[rule] = Waiver(rule, reason, tok.start[0])
    return waivers, problems


# --------------------------------------------------------------------------
# audit orchestration (used by the CLI and the tests)
# --------------------------------------------------------------------------

@dataclasses.dataclass
class EntryReport:
    name: str
    drift: Dict[str, List[str]]          # rule -> lines (unwaived)
    waived: Dict[str, List[str]]         # rule -> lines (suppressed)
    problems: List[str]                  # waiver syntax issues etc.
    updated: bool = False

    @property
    def failed(self) -> bool:
        return bool(self.drift or self.problems)


def contract_path(contracts_dir: str, name: str) -> str:
    return os.path.join(contracts_dir, f"{name}.json")


def audit_entry(name: str, spec, contracts_dir: str, *, update: bool = False,
                repo_root: Optional[str] = None) -> Tuple[EntryReport, dict]:
    """Build the live contract for one registry entry, compare (or rewrite)
    its golden, apply waivers. Returns (report, live contract)."""
    built = spec.build()
    live = build_contract(name, built)
    waivers, problems = collect_waivers(spec.source, repo_root)

    drift: Dict[str, List[str]] = {}
    waived: Dict[str, List[str]] = {}

    # donation is an invariant, not a golden: every donated leaf aliased
    don = live.get("donation")
    if don is not None and don["aliased"] < don["donated"]:
        line = (f"only {don['aliased']} of {don['donated']} donated buffers "
                "are aliased in the compiled executable — XLA is silently "
                "keeping the old state live")
        if "donation" in waivers:
            waived.setdefault("donation", []).append(
                f"{line} (waived: {waivers['donation'].reason})")
        else:
            drift["donation"] = [line]

    path = contract_path(contracts_dir, name)
    if update:
        save_contract(live, path)
        return EntryReport(name, drift, waived, problems, updated=True), live

    golden = load_contract(path)
    if golden is None:
        drift["missing"] = [f"no golden contract at {path} — run "
                            "scripts/ir_audit.py --update"]
        return EntryReport(name, drift, waived, problems), live

    for rule, lines in diff_contracts(golden, live).items():
        if rule in waivers:
            waived.setdefault(rule, []).extend(
                f"{ln} (waived: {waivers[rule].reason})" for ln in lines)
        else:
            drift[rule] = lines
    return EntryReport(name, drift, waived, problems), live


def render_report(reports: Sequence[EntryReport], sources: Dict[str, str],
                  scope: str) -> str:
    lines = []
    failed = [r for r in reports if r.failed]
    for r in reports:
        if not (r.drift or r.waived or r.problems):
            continue
        lines.append(f"{r.name} ({sources.get(r.name, '?')}):")
        for rule, ls in sorted(r.drift.items()):
            for ln in ls:
                lines.append(f"  {rule}: {ln}")
        for rule, ls in sorted(r.waived.items()):
            for ln in ls:
                lines.append(f"  {rule} [waived]: {ln}")
        for p in r.problems:
            lines.append(f"  waiver-problem: {p}")
    n = len(failed)
    if n:
        lines.append(f"graftir: contract drift in {n} "
                     f"entr{'y' if n == 1 else 'ies'} ({scope})")
        lines.append("intentional change? regenerate with "
                     "scripts/ir_audit.py --update and commit the diff")
    else:
        lines.append(f"graftir: contracts clean ({scope})")
    return "\n".join(lines)


def explain(contract: dict) -> str:
    """Pretty-print one contract (the --explain CLI path)."""
    c = contract
    lines = [f"entry: {c['entry']}", "primitives:"]
    for name, count in sorted(c["primitives"].items(),
                              key=lambda kv: (-kv[1], kv[0])):
        lines.append(f"  {count:5d}  {name}")
    for key, render in (
            ("collectives", lambda e: f"{e['count']}x {e['kind']} "
                                      f"{_fmt_bytes(e['bytes'])} on axis "
                                      f"'{e['axes']}'"),
            ("promotions", lambda e: f"{e['count']}x {e['src']}->{e['dst']} "
                                     f"{_fmt_bytes(e['bytes'])} at "
                                     f"{e['site']}"),
            ("transfers", lambda e: f"{e['count']}x {e['primitive']} at "
                                    f"{e['site']}")):
        lines.append(f"{key}:")
        if not c.get(key):
            lines.append("  (none)")
        for e in c.get(key) or []:
            lines.append(f"  {render(e)}")
    prec = c.get("precision") or {}
    lines.append("precision:")
    cc = prec.get("class_counts", {})
    if cc:
        lines.append("  classes: " + ", ".join(
            f"{k}={v}" for k, v in sorted(cc.items())))
    for e in prec.get("int8_dots") or []:
        lines.append(f"  {e['count']}x int8 dot (accum '{e['accum']}') at "
                     f"{e['site']}")
    for e in prec.get("dequants") or []:
        lines.append(f"  {e['count']}x dequant ->{e['dst']} (scale axes "
                     f"{e['scale_axes']}) at {e['site']}")
    if not prec:
        lines.append("  (none)")
    mem = c.get("memory", {})
    lines.append(f"memory: peak est {_fmt_bytes(mem.get('peak_bytes_est', 0))}"
                 f" (args {_fmt_bytes(mem.get('arg_bytes', 0))}, outputs "
                 f"{_fmt_bytes(mem.get('out_bytes', 0))})")
    don = c.get("donation")
    if don:
        lines.append(f"donation: {don['aliased']}/{don['donated']} donated "
                     "buffers aliased")
    if c.get("vmem"):
        lines.append(f"vmem: {json.dumps(c['vmem'], sort_keys=True)}")
    return "\n".join(lines)
