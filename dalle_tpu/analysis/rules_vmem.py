"""vmem-ceiling: keep Pallas scoped-VMEM ceilings and estimators in lockstep.

The b695782 lesson: Mosaic's 16M scoped-vmem ceiling is a compiler default,
and ops/fused_attention.py raises it per kernel from a byte ESTIMATOR that
is known to underestimate the compiler's real demand (21.55M estimated vs
25.68M reported at the medium calibration point). The ≥25% headroom rule is
what keeps an admitted shape from busting its requested ceiling with no
dense fallback. Nothing at runtime checks that rule — a PR that edits the
estimator, the tier table, or the admission gate independently compiles
fine and fails on hardware. This rule re-derives the contract at lint time:

  * every (gate, ceiling) tier is internally ordered (gate < ceiling);
  * the admission budget equals the first tier's gate;
  * the MEDIUM calibration shape (n=513, h·d=1024) routes to the 32M tier
    and its estimate carries ≥25% headroom under that ceiling;
  * that headroom still covers the compiler's measured 25.68M demand —
    i.e. the estimator has not drifted below the one real data point;
  * the largest admitted estimate still fits the top tier with headroom;
  * no ops file hard-codes a ``vmem_limit_bytes=`` literal outside the
    tier table (rogue ceilings bypass the whole contract).
"""

from __future__ import annotations

import ast
import os
from typing import Iterable, List, Sequence

from .core import REPO_ROOT, FileContext, Finding, ProjectRule, register_rule

_FUSED_PATH = "dalle_tpu/ops/fused_attention.py"

# the one measured calibration point (commit b695782):
# medium config n=513, h·d=1024; compiler reported 25.68M scoped-vmem demand;
# the tier that admits it is 32M.
_CAL_N, _CAL_HD = 513, 1024
_CAL_COMPILER_BYTES = int(25.68 * 1024 * 1024)
_CAL_EXPECTED_LIMIT = 32 * 1024 * 1024
_HEADROOM_NUM, _HEADROOM_DEN = 1, 4   # ≥25% over the estimate


def check_estimator_contract(mod) -> List[str]:
    """Invariant messages for a module shaped like ops.fused_attention.
    Split out (module injected) so tests can feed a broken fake."""
    msgs: List[str] = []
    limits: Sequence = getattr(mod, "_VMEM_RAISED_LIMITS", ())
    budget = getattr(mod, "_VMEM_RAISED_BUDGET", None)
    bwd_bytes = getattr(mod, "_bwd_bytes", None)
    compiler_params = getattr(mod, "_compiler_params", None)
    if not limits or budget is None or bwd_bytes is None or compiler_params is None:
        return ["fused_attention no longer exposes _VMEM_RAISED_LIMITS/"
                "_VMEM_RAISED_BUDGET/_bwd_bytes/_compiler_params — the "
                "vmem-ceiling rule cannot verify the contract; update "
                "analysis/rules_vmem.py with it"]

    for gate, limit in limits:
        if gate >= limit:
            msgs.append(f"tier ({gate}, {limit}): gate must be below its "
                        "ceiling")
    if budget != limits[0][0]:
        msgs.append(f"_VMEM_RAISED_BUDGET ({budget}) != first tier gate "
                    f"({limits[0][0]}) — the admission gate and the tier "
                    "table have drifted apart")

    est = bwd_bytes(_CAL_N, _CAL_HD)
    need = est + est * _HEADROOM_NUM // _HEADROOM_DEN
    cp = compiler_params(est)
    got = getattr(cp, "vmem_limit_bytes", None) if cp is not None else None
    if got != _CAL_EXPECTED_LIMIT:
        msgs.append(
            f"medium calibration (n={_CAL_N}, hd={_CAL_HD}): estimator gives "
            f"{est} bytes, which routes to ceiling {got} — expected the "
            f"{_CAL_EXPECTED_LIMIT} (32M) tier. Estimator and tier table "
            "were edited inconsistently")
    elif need > got:
        msgs.append(
            f"medium calibration: estimate {est} + 25% headroom = {need} "
            f"exceeds its own ceiling {got}")
    if need < _CAL_COMPILER_BYTES:
        msgs.append(
            f"medium calibration: estimate {est} + 25% headroom = {need} no "
            f"longer covers the compiler's measured {_CAL_COMPILER_BYTES} "
            "demand — the estimator drifted below the known data point; "
            "recalibrate before trusting the admission gate")

    # the largest estimate the gate admits must fit the top tier with headroom
    top = limits[-1][1]
    worst = budget + budget * _HEADROOM_NUM // _HEADROOM_DEN
    if worst > top:
        msgs.append(
            f"admission budget {budget} + 25% headroom = {worst} exceeds the "
            f"top ceiling {top} — a gate-admitted shape could bust scoped "
            "VMEM with no dense fallback")
    return msgs


def _known_limits(mod) -> set:
    """CEILING values only — a tier's admission gate (e.g. 30M) is not a
    valid ceiling to request; hard-coding it would admit the calibration
    shape with <25% headroom, the exact bust this rule exists to prevent."""
    return {limit for _, limit in getattr(mod, "_VMEM_RAISED_LIMITS", ())}


@register_rule
class VmemCeiling(ProjectRule):
    name = "vmem-ceiling"
    description = ("pltpu.CompilerParams vmem ceilings must stay consistent "
                   "with the kernel VMEM estimator (≥25% headroom rule)")
    triggers = ("dalle_tpu/ops/", "dalle_tpu/analysis/")

    def check_project(self, ctxs, repo_root=REPO_ROOT) -> Iterable[Finding]:
        findings: List[Finding] = []
        if os.path.realpath(repo_root) != os.path.realpath(REPO_ROOT):
            # the contract check executes the IMPORTED dalle_tpu, which is
            # this checkout's — silently validating it against a foreign
            # checkout's sources would lint green on a broken tree
            return [Finding(
                self.name, _FUSED_PATH, 1,
                "vmem-ceiling verifies the imported dalle_tpu package and "
                f"cannot vouch for a foreign checkout at {repo_root}; run "
                "that checkout's own scripts/lint.py")]
        try:
            from dalle_tpu.ops import fused_attention as mod
        except Exception as e:  # noqa: BLE001 - import failure IS the finding
            return [Finding(self.name, _FUSED_PATH, 1,
                            f"cannot import ops.fused_attention: {e!r}")]
        anchor = self._anchor_line(ctxs)
        try:
            msgs = check_estimator_contract(mod)
        except Exception as e:  # noqa: BLE001 - a raising contract IS the finding
            msgs = [f"estimator contract check raised {e!r} — the ceiling "
                    "machinery is broken, not just drifted"]
        for msg in msgs:
            findings.append(Finding(self.name, _FUSED_PATH, anchor, msg))

        known = _known_limits(mod)
        for ctx in ctxs:
            if not ctx.rel_path.startswith("dalle_tpu/ops/"):
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                for kw in node.keywords:
                    if (kw.arg == "vmem_limit_bytes"
                            and isinstance(kw.value, ast.Constant)
                            and isinstance(kw.value.value, int)
                            and kw.value.value not in known):
                        findings.append(Finding(
                            self.name, ctx.rel_path, node.lineno,
                            f"hard-coded vmem_limit_bytes={kw.value.value} "
                            "is not in fused_attention._VMEM_RAISED_LIMITS — "
                            "route ceilings through the tier table so the "
                            "headroom contract covers them"))
        return findings

    @staticmethod
    def _anchor_line(ctxs) -> int:
        """Line of the tier table assignment, for a clickable finding."""
        for ctx in ctxs:
            if ctx.rel_path != _FUSED_PATH:
                continue
            for node in ctx.tree.body:
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "_VMEM_RAISED_LIMITS"
                        for t in node.targets):
                    return node.lineno
        return 1
