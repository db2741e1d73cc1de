"""graftnum — precision-flow audit: forward dataflow over closed jaxprs.

graftir (ir_audit.py) pins every ``convert_element_type`` site, but it only
*diffs* precision — nothing reasons about it. Mixed-precision failures are
exactly the silent kind static analysis catches best: a bf16 accumulation
inside a softmax/norm/loss reduction, an int8 matmul accumulating at low
width, a dequant scale riding the wrong axis, a value quantized twice
(double rounding), an upcast that quietly erases the HBM win (cf. FP8
training, Micikevicius et al. 2022; LLM.int8, Dettmers et al. 2022).

This module runs a forward dataflow analysis over a ClosedJaxpr with

  * a **precision lattice** per value — f32 / bf16 / f16 / int8 / int /
    bool, plus JAX's weak-typed flag (counted in the boundary map);
  * **provenance** per value — where it was seeded from: ``param``, ``kv``
    (cache storage), ``scale`` (quantization scales), ``activation``,
    ``const`` — inferred from the entry's argument pytree paths
    (:func:`infer_roles`) and propagated through every primitive;
  * a **quantization state machine** per value: int8 storage (``q``) →
    dequantized-but-unscaled (``dq``, the int8→float convert) →
    dequantized-and-scaled (``dqs``, the multiply by a scale). Movement
    ops (reshape/transpose/broadcast/slice/gather/...) carry the state;
    real arithmetic produces fresh activations.

The quantization-safety rules enforced on the flow (each finding carries
``file::function`` provenance via graftir's source-info walker):

  ``low-precision-reduction``  reductions (softmax denominators, norm
      statistics, loss accumulation — ``reduce_sum``/``cumsum``/...) must
      accumulate at ≥ f32; a bf16/f16 operand is a finding.
  ``int8-dot-accum``  every ``dot_general`` consuming an int8 operand must
      declare a ≥ 32-bit ``preferred_element_type`` accumulator.
  ``unscaled-dequant``  a dequantized int8 value must be multiplied by its
      scale before any matmul consumes it (the ``assert_float_params``
      garbage-output hazard, caught statically).
  ``dequant-scale-axis``  the dequant scale must be constant along every
      axis the consuming matmul contracts over — per-channel scales ride
      the output (minormost-safe) axis, never the contraction axis.
  ``double-rounding``  re-quantizing an already-dequantized value.
  ``quant-upcast``  widening a dequantized value to a wider float — the
      upcast defeats the quantization's HBM/MXU win.
  ``orphaned-scale``  a scale input that never reaches a dequantizing
      multiply (its quantized partner is being consumed scale-less
      somewhere, or the scale is dead weight shipped to the device).

Findings are waivable per entry source file with the existing graftir
mechanism: ``# graftir: allow=precision -- <reason>``. The per-entry
**boundary map** (which matmuls consume int8, accumulator dtypes, dequant
sites and scale axes, value-class counts) is also serialized as the
``precision`` section of the graftir contract goldens under ``contracts/``,
so a quantization-boundary change is reviewable drift like any other
program change. CI runs both: ``scripts/precision_audit.py`` (rules +
boundary-map artifact) and ``scripts/ir_audit.py --check`` (drift).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

PRECISION_RULES = (
    "low-precision-reduction", "int8-dot-accum", "unscaled-dequant",
    "dequant-scale-axis", "double-rounding", "quant-upcast", "orphaned-scale",
)

# reductions that ACCUMULATE (error compounds with width) — max/min/argmax
# compare and are precision-safe at any width
_ACCUM_REDUCES = {"reduce_sum", "reduce_prod", "cumsum", "cumprod",
                  "cumlogsumexp", "reduce_window_sum"}

# ops that move data without computing on it: quantization state and axis
# tracking ride through these (gather/pad/dus lose axis tracking but keep
# the state — see _map_axes)
_MOVEMENT = {"reshape", "transpose", "broadcast_in_dim", "slice",
             "dynamic_slice", "squeeze", "rev", "copy", "stop_gradient",
             "gather", "pad", "expand_dims"}

# join ops: output state is the operands' agreement (a cache buffer updated
# with fresh rows stays quantized storage only if both halves are)
_JOIN = {"concatenate", "select_n", "dynamic_update_slice"}

_HIGHER_SPECIAL = {"scan", "while", "cond", "pallas_call"}


def _jnp():
    import jax.numpy as jnp
    return jnp


def _is_float(dtype) -> bool:
    return _jnp().issubdtype(dtype, _jnp().floating)


def _itemsize(dtype) -> int:
    try:
        return np.dtype(dtype).itemsize
    except TypeError:
        return int(getattr(dtype, "itemsize", 0))


def _is_int8(dtype) -> bool:
    try:
        return np.dtype(dtype) == np.dtype(np.int8)
    except TypeError:
        return False   # extended dtypes (PRNG key<fry> etc.)


def classify_dtype(dtype) -> str:
    """Lattice class name of a dtype (the boundary-map vocabulary)."""
    try:
        name = np.dtype(dtype).name
    except TypeError:
        return "other"
    table = {"float64": "f32", "float32": "f32", "bfloat16": "bf16",
             "float16": "f16", "int8": "int8", "uint8": "int8",
             "bool": "bool"}
    if name in table:
        return table[name]
    if name.startswith(("int", "uint")):
        return "int"
    return "other"


# --------------------------------------------------------------------------
# value info + role inference
# --------------------------------------------------------------------------

@dataclasses.dataclass
class VInfo:
    """Per-value dataflow fact: provenance roles, the set of axes the value
    is known to VARY along (None = unknown → axis rules stay silent, the
    zero-false-positive contract), quantization state, and — for ``dqs``
    values — the scale's varying axes in the value's current coordinates."""
    prov: frozenset = frozenset()
    varies: Optional[frozenset] = None
    quant: str = ""                      # "" | "q" | "dq" | "dqs"
    scale_varies: Optional[frozenset] = None
    scale_src: frozenset = frozenset()   # input-leaf ids of scales carried
    # (site, line) of a float-widening convert applied to this dequantized
    # value — only a FINDING if a matmul later consumes the widened value
    # (a norm's internal f32 stats upcast is required, not a hazard)
    upcast: Optional[Tuple[str, int]] = None


def _shape_varies(aval) -> Optional[frozenset]:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return None
    return frozenset(i for i, s in enumerate(shape) if s != 1)


def _key_str(k) -> str:
    for attr in ("key", "name", "idx"):
        if hasattr(k, attr):
            return str(getattr(k, attr))
    return str(k)


def _role_of_path(keys: Sequence[str]) -> str:
    last = keys[-1] if keys else ""
    in_cache = any(k == "cache" or k.startswith("kv_") for k in keys)
    if "quant" in keys or last in ("kernel_scale", "shared_emb_scale"):
        return "scale"
    if last == "scale" and in_cache:
        return "scale"
    if "params" in keys:
        return "param"
    if last == "kv" or in_cache:
        return "kv"
    return "activation"


def infer_roles(args: tuple) -> List[Tuple[str, str]]:
    """[(role, label)] aligned with ``jax.tree_util.tree_leaves(args)`` —
    the flattening order ``jax.make_jaxpr`` gives the jaxpr invars.
    Roles come from pytree path names: the ``quant`` collection and cache
    ``scale`` leaves are scales, ``params`` subtrees are params, cache
    ``kv`` buffers are KV storage, everything else is activation-shaped.
    (Optimizer-state mirrors of params deliberately do NOT match the scale
    patterns — a ``mu`` leaf named ``scale`` is a param moment, not a
    quantization scale.)"""
    import jax
    leaves = jax.tree_util.tree_flatten_with_path(args)[0]
    out = []
    for keypath, _leaf in leaves:
        keys = [_key_str(k) for k in keypath]
        out.append((_role_of_path(keys), "/".join(keys) or "arg"))
    return out


# --------------------------------------------------------------------------
# findings / boundary-map accumulation
# --------------------------------------------------------------------------

class _Ctx:
    def __init__(self):
        self.findings: Dict[Tuple[str, str], dict] = {}
        self.int8_dots: Dict[Tuple[str, str], dict] = {}
        self.dequants: Dict[Tuple[str, str, str], dict] = {}
        self.used_scales: set = set()
        self.seeded_scales: Dict[int, str] = {}

    def finding(self, rule: str, eqn, detail: str):
        from .ir_audit import _site_of
        self.finding_at(rule, _site_of(eqn), detail)

    def finding_at(self, rule: str, site_line: Tuple[str, int], detail: str):
        site, line = site_line
        key = (rule, site)
        f = self.findings.setdefault(key, {
            "rule": rule, "site": site, "line": line, "detail": detail,
            "count": 0})
        f["count"] += 1

    def int8_dot(self, eqn, accum: str):
        from .ir_audit import _site_of
        site, _ = _site_of(eqn)
        ev = self.int8_dots.setdefault((site, accum), {
            "site": site, "accum": accum, "count": 0})
        ev["count"] += 1

    def dequant(self, eqn, dst: str, scale_axes: str):
        from .ir_audit import _site_of
        site, _ = _site_of(eqn)
        ev = self.dequants.setdefault((site, dst, scale_axes), {
            "site": site, "dst": dst, "scale_axes": scale_axes, "count": 0})
        ev["count"] += 1


# --------------------------------------------------------------------------
# axis mapping through movement ops
# --------------------------------------------------------------------------

def _map_axes(eqn, axes: Optional[frozenset]) -> Optional[frozenset]:
    """Transform a set of varying axes of eqn's FIRST operand into output
    coordinates. None in → None out; unmappable ops (gather, pad, dynamic
    windows) also degrade to None — unknown silences the axis rules rather
    than mis-firing them."""
    if axes is None:
        return None
    name = eqn.primitive.name
    in_aval = eqn.invars[0].aval
    out_aval = eqn.outvars[0].aval
    if name in ("copy", "stop_gradient", "convert_element_type", "rev"):
        return axes
    if name == "transpose":
        perm = eqn.params["permutation"]
        return frozenset(j for j, p in enumerate(perm) if p in axes)
    if name == "broadcast_in_dim":
        bd = eqn.params["broadcast_dimensions"]
        return frozenset(bd[i] for i in axes if in_aval.shape[i] != 1)
    if name == "squeeze":
        dims = set(eqn.params["dimensions"])
        remap = {}
        j = 0
        for i in range(len(in_aval.shape)):
            if i in dims:
                continue
            remap[i] = j
            j += 1
        return frozenset(remap[i] for i in axes if i in remap)
    if name in ("slice", "dynamic_slice"):
        return frozenset(i for i in axes if out_aval.shape[i] != 1)
    if name == "reshape":
        old = [(i, s) for i, s in enumerate(in_aval.shape) if s != 1]
        new = [(i, s) for i, s in enumerate(out_aval.shape) if s != 1]
        if [s for _, s in old] != [s for _, s in new]:
            return None
        remap = {oi: ni for (oi, _), (ni, _) in zip(old, new)}
        return frozenset(remap[i] for i in axes if i in remap)
    return None


def _is_scale_like(info: VInfo) -> bool:
    """Evidence that a value IS a quantization scale: seeded 'scale'
    provenance (the ``quant`` collection, cache scale buffers — carried by
    ``scale_src`` too) or an amax-derived chain ('scale' is added to the
    provenance of ``reduce_max(abs(...))`` results, the shape of every
    in-program quantizer — ops/attention._quantize_int8)."""
    return bool(info.scale_src) or "scale" in info.prov


def _join(infos: List[VInfo], varies=None) -> VInfo:
    prov = frozenset().union(*(i.prov for i in infos)) if infos else frozenset()
    src = frozenset().union(*(i.scale_src for i in infos)) if infos \
        else frozenset()
    quants = {i.quant for i in infos}
    quant = quants.pop() if len(quants) == 1 else ""
    return VInfo(prov, varies, quant, None, src)


# --------------------------------------------------------------------------
# the flow
# --------------------------------------------------------------------------

def _info_of(env, v) -> VInfo:
    import jax.extend.core as jex_core
    if isinstance(v, jex_core.Literal) or not hasattr(v, "count"):
        quant = ""
        dt = getattr(getattr(v, "aval", None), "dtype", None)
        if dt is not None and _is_int8(dt):
            quant = "q"
        return VInfo(prov=frozenset({"const"}),
                     varies=_shape_varies(getattr(v, "aval", None)),
                     quant=quant)
    return env.get(v, VInfo(prov=frozenset({"const"})))


def _main_sub(eqn):
    from .ir_audit import _sub_jaxprs
    for sub in _sub_jaxprs(eqn.params):
        if len(sub.invars) == len(eqn.invars):
            return sub
    return None


def _flow(jaxpr, in_infos: List[VInfo], ctx: _Ctx) -> List[VInfo]:
    import jax.core as core
    jnp = _jnp()
    env: Dict = {}
    for v, info in zip(jaxpr.invars, in_infos):
        env[v] = info
    for v in jaxpr.constvars:
        env[v] = VInfo(prov=frozenset({"const"}),
                       varies=_shape_varies(v.aval))

    def setout(eqn, info: VInfo):
        for ov in eqn.outvars:
            if isinstance(ov, core.DropVar):
                continue
            dt = getattr(ov.aval, "dtype", None)
            if dt is not None and _is_int8(dt) and info.quant != "q":
                # int8 IS quantized storage in these programs (ids are
                # int32, masks bool) — values quantized in-program (the KV
                # cache append path) enter the state machine here
                env[ov] = dataclasses.replace(info, quant="q",
                                              scale_varies=None)
            else:
                env[ov] = info

    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        infos = [_info_of(env, v) for v in eqn.invars]

        if name in _HIGHER_SPECIAL or _main_sub(eqn) is not None:
            _flow_higher(eqn, infos, env, ctx)
            continue

        if name == "convert_element_type":
            src_dt = eqn.invars[0].aval.dtype
            dst_dt = eqn.outvars[0].aval.dtype
            a = infos[0]
            quant, sv, upcast = a.quant, a.scale_varies, a.upcast
            if a.quant == "q" and _is_float(dst_dt):
                quant, sv = "dq", None
            elif a.quant in ("dq", "dqs"):
                if _is_float(dst_dt) and \
                        _itemsize(dst_dt) > _itemsize(src_dt):
                    from .ir_audit import _site_of
                    upcast = _site_of(eqn)
                elif jnp.issubdtype(dst_dt, jnp.integer):
                    ctx.finding(
                        "double-rounding", eqn,
                        f"re-quantization {np.dtype(src_dt).name}->"
                        f"{np.dtype(dst_dt).name} of an already-dequantized "
                        "int8 value — double rounding compounds the "
                        "quantization error")
                    quant, sv, upcast = "q", None, None
            setout(eqn, VInfo(a.prov, a.varies, quant, sv, a.scale_src,
                              upcast))
            continue

        if name in _MOVEMENT:
            a = infos[0]
            varies = _map_axes(eqn, a.varies)
            sv = _map_axes(eqn, a.scale_varies)
            prov = frozenset().union(*(i.prov for i in infos))
            src = frozenset().union(*(i.scale_src for i in infos))
            setout(eqn, VInfo(prov, varies, a.quant, sv, src, a.upcast))
            continue

        if name in _JOIN:
            if name == "dynamic_update_slice":
                data = infos[:2]            # (operand, update); rest: indices
            elif name == "select_n":
                data = infos[1:]            # first operand is the predicate
            else:
                data = infos
            setout(eqn, _join(data, varies=_shape_varies(
                eqn.outvars[0].aval)))
            continue

        if name == "mul":
            a, b = infos[0], infos[1]
            out_varies = None
            if a.varies is not None and b.varies is not None:
                out_varies = a.varies | b.varies
            # a multiply only COMPLETES a dequant when the partner carries
            # scale EVIDENCE — seeded 'scale' provenance (quant collection,
            # cache scale buffers) or an amax-derived chain (the in-program
            # _quantize_int8 path). An arbitrary float multiply (a dropout
            # or attention mask) must NOT silence unscaled-dequant: the
            # value stays 'dq' and a later true scale-mul can still
            # complete it.
            pending = None
            if a.quant == "dq" and b.quant == "" and _is_scale_like(b):
                pending = (a, b)
            elif b.quant == "dq" and a.quant == "" and _is_scale_like(a):
                pending = (b, a)
            if pending is not None:
                dq, sc = pending
                dst = np.dtype(eqn.outvars[0].aval.dtype).name
                axes = ("?" if sc.varies is None
                        else ",".join(str(i) for i in sorted(sc.varies))
                        or "-")
                ctx.dequant(eqn, dst, axes)
                ctx.used_scales.update(sc.scale_src)
                setout(eqn, VInfo(dq.prov | sc.prov, out_varies, "dqs",
                                  sc.varies, frozenset(), dq.upcast))
                continue
            if "dqs" in (a.quant, b.quant) and "" in (a.quant, b.quant):
                d = a if a.quant == "dqs" else b
                setout(eqn, VInfo(a.prov | b.prov, out_varies, "dqs",
                                  d.scale_varies,
                                  a.scale_src | b.scale_src, d.upcast))
                continue
            if "dq" in (a.quant, b.quant) and "" in (a.quant, b.quant):
                d = a if a.quant == "dq" else b
                setout(eqn, VInfo(a.prov | b.prov, out_varies, "dq",
                                  None, a.scale_src | b.scale_src,
                                  d.upcast))
                continue
            setout(eqn, VInfo(a.prov | b.prov, out_varies, "",
                              None, a.scale_src | b.scale_src))
            continue

        if name == "dot_general":
            (lc, rc), _batch = eqn.params["dimension_numbers"]
            pet = eqn.params.get("preferred_element_type")
            contr = (frozenset(lc), frozenset(rc))
            has_int8 = False
            for idx, (v, info) in enumerate(zip(eqn.invars[:2], infos[:2])):
                dt = v.aval.dtype
                if _is_int8(dt):
                    has_int8 = True
                if info.quant == "dq":
                    ctx.finding(
                        "unscaled-dequant", eqn,
                        "dequantized int8 operand reaches a matmul without "
                        "its per-channel scale — the output is garbage "
                        "(the assert_float_params hazard, statically)")
                if info.quant == "dqs" and info.scale_varies is not None:
                    bad = info.scale_varies & contr[idx]
                    if bad:
                        ctx.finding(
                            "dequant-scale-axis", eqn,
                            f"dequant scale varies along contracted axis "
                            f"{sorted(bad)} of the matmul operand — "
                            "per-channel scales must ride the output "
                            "(minormost-safe) axis, not the contraction")
                if info.quant in ("dq", "dqs") and info.upcast is not None:
                    ctx.finding_at(
                        "quant-upcast", info.upcast,
                        "dequantized int8 value widened to a wider float "
                        "before a matmul consumes it — the upcast defeats "
                        "the quantization's HBM/MXU win")
            if has_int8:
                accum = ("none" if pet is None
                         else np.dtype(pet).name)
                ctx.int8_dot(eqn, accum)
                if pet is None or _itemsize(pet) < 4:
                    ctx.finding(
                        "int8-dot-accum", eqn,
                        f"int8 dot_general accumulates at "
                        f"'{accum}' — declare preferred_element_type="
                        "float32 (or int32) so the MXU accumulator "
                        "keeps full width")
            prov = frozenset().union(*(i.prov for i in infos)) if infos \
                else frozenset()
            setout(eqn, VInfo(prov, _shape_varies(eqn.outvars[0].aval)))
            continue

        if name in _ACCUM_REDUCES:
            dt = eqn.invars[0].aval.dtype
            if _is_float(dt) and _itemsize(dt) < 4:
                ctx.finding(
                    "low-precision-reduction", eqn,
                    f"{name} accumulates at {np.dtype(dt).name} — "
                    "reductions (softmax/normalization/loss accumulation) "
                    "must run at ≥ float32")

        # default: fresh value; provenance and scale taint flow through,
        # quantization state does not survive arithmetic
        prov = frozenset().union(*(i.prov for i in infos)) if infos \
            else frozenset()
        # amax-chain tagging: |x| → max reduce is how every in-program
        # quantizer derives its scales — mark the result 'scale' so the
        # dequant-completion check (see mul) has evidence for scales that
        # were never input leaves (the KV cache's _quantize_int8 path)
        if name == "abs":
            prov |= {"_abs"}
        elif name == "reduce_max" and infos and "_abs" in infos[0].prov:
            prov |= {"scale"}
        src = frozenset().union(*(i.scale_src for i in infos)) if infos \
            else frozenset()
        out_aval = getattr(eqn.outvars[0], "aval", None) if eqn.outvars \
            else None
        same_shape = infos and all(
            getattr(v.aval, "shape", None) == getattr(out_aval, "shape", ())
            for v in eqn.invars if hasattr(v, "aval"))
        varies = None
        if same_shape and all(i.varies is not None for i in infos):
            varies = frozenset().union(*(i.varies for i in infos))
        setout(eqn, VInfo(prov, varies, "", None, src))

    return [_info_of(env, v) for v in jaxpr.outvars]


def _flow_higher(eqn, infos: List[VInfo], env, ctx: _Ctx) -> None:
    """Recurse into nested jaxprs, mapping operand infos positionally."""
    import jax.core as core
    from .ir_audit import _sub_jaxprs
    name = eqn.primitive.name

    def setout(out_infos):
        outs = [v for v in eqn.outvars]
        for ov, info in zip(outs, out_infos or []):
            if not isinstance(ov, core.DropVar):
                env[ov] = info
        for ov in outs[len(out_infos or []):]:
            if not isinstance(ov, core.DropVar):
                env[ov] = VInfo(prov=frozenset({"const"}))

    if name == "pallas_call":
        # kernel bodies compute on Refs — opaque to value dataflow (their
        # primitive mix still lands in the contract histogram/class counts)
        setout([])
        return
    if name == "scan":
        body = next(iter(_sub_jaxprs(eqn.params)), None)
        if body is None or len(body.invars) != len(eqn.invars):
            setout([])
            return
        # consts and carry pass through whole; only the xs arrive sliced
        # along the scan axis (and only the ys come back stacked), so axis
        # tracking degrades just for those
        nc = eqn.params.get("num_consts", 0)
        ncar = eqn.params.get("num_carry", 0)
        mapped = list(infos[:nc + ncar]) + [
            dataclasses.replace(i, varies=None, scale_varies=None)
            for i in infos[nc + ncar:]]
        outs = _flow(body, mapped, ctx)
        setout(list(outs[:ncar]) + [
            dataclasses.replace(o, varies=None, scale_varies=None)
            for o in outs[ncar:]])
        return
    if name == "while":
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        cond_j = eqn.params["cond_jaxpr"].jaxpr
        body_j = eqn.params["body_jaxpr"].jaxpr
        carry = infos[cn + bn:]
        _flow(cond_j, infos[:cn] + carry, ctx)
        outs = _flow(body_j, infos[cn:cn + bn] + carry, ctx)
        setout(outs)
        return
    if name == "cond":
        branch_outs = []
        for br in eqn.params["branches"]:
            branch_outs.append(_flow(br.jaxpr, infos[1:], ctx))
        if not branch_outs:
            setout([])
            return
        joined = [_join(list(col)) for col in zip(*branch_outs)]
        setout(joined)
        return
    sub = _main_sub(eqn)
    if sub is None:
        setout([])
        return
    outs = _flow(sub, infos, ctx)
    if len(outs) == len(eqn.outvars):
        setout(outs)
    else:
        setout([])


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@dataclasses.dataclass
class PrecisionReport:
    findings: List[dict]      # rule/site/line/detail/count, sorted
    boundary: dict            # the contract "precision" section


def class_counts(closed) -> Dict[str, int]:
    """Lattice-class histogram of every eqn output (recursively, pallas
    kernel bodies included) plus the weak-typed count."""
    import jax.core as core
    from .ir_audit import iter_eqns
    counts: Dict[str, int] = {}
    weak = 0
    for eqn in iter_eqns(closed.jaxpr):
        for ov in eqn.outvars:
            if isinstance(ov, core.DropVar):
                continue
            aval = ov.aval
            cls = classify_dtype(getattr(aval, "dtype", None))
            counts[cls] = counts.get(cls, 0) + 1
            if getattr(aval, "weak_type", False):
                weak += 1
    if weak:
        counts["weak"] = weak
    return dict(sorted(counts.items()))


def analyze(closed, roles: Optional[List[Tuple[str, str]]] = None
            ) -> PrecisionReport:
    """Run the precision flow over ``closed`` (a ClosedJaxpr). ``roles``:
    [(role, label)] aligned with the jaxpr invars (see :func:`infer_roles`);
    unlabeled invars default to activations."""
    jaxpr = closed.jaxpr
    ctx = _Ctx()
    in_infos: List[VInfo] = []
    for i, v in enumerate(jaxpr.invars):
        role, label = (roles[i] if roles is not None and i < len(roles)
                       else ("activation", f"arg{i}"))
        dtype = getattr(v.aval, "dtype", None)
        quant = "q" if (dtype is not None and _is_int8(dtype)) else ""
        scale_src = frozenset()
        if role == "scale":
            scale_src = frozenset({i})
            ctx.seeded_scales[i] = label
        in_infos.append(VInfo(frozenset({role}), _shape_varies(v.aval),
                              quant, None, scale_src))
    _flow(jaxpr, in_infos, ctx)

    findings = sorted(ctx.findings.values(),
                      key=lambda f: (f["rule"], f["site"]))
    for i, label in sorted(ctx.seeded_scales.items()):
        if i not in ctx.used_scales:
            findings.append({
                "rule": "orphaned-scale", "site": "<inputs>", "line": 0,
                "detail": f"scale input '{label}' never reaches a "
                          "dequantizing multiply — its quantized partner "
                          "is consumed scale-less or the scale is dead "
                          "weight", "count": 1})
    boundary = {
        "class_counts": class_counts(closed),
        "int8_dots": sorted(ctx.int8_dots.values(),
                            key=lambda e: (e["site"], e["accum"])),
        "dequants": sorted(ctx.dequants.values(),
                           key=lambda e: (e["site"], e["dst"],
                                          e["scale_axes"])),
    }
    return PrecisionReport(findings=findings, boundary=boundary)


def analyze_fn(fn, args, roles: Optional[List[Tuple[str, str]]] = None
               ) -> PrecisionReport:
    """Trace ``fn(*args)`` and analyze; roles default to the argument
    pytree's inferred provenance."""
    import jax
    closed = jax.make_jaxpr(fn)(*args)
    if roles is None:
        roles = infer_roles(args)
    return analyze(closed, roles)


def render_findings(entry: str, findings: List[dict]) -> List[str]:
    """Human-readable finding lines (the precision_audit report format)."""
    out = []
    for f in findings:
        n = f" (x{f['count']})" if f.get("count", 1) > 1 else ""
        out.append(f"{entry}: [{f['rule']}] {f['site']}: {f['detail']}{n}")
    return out
