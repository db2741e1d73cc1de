#!/usr/bin/env python
"""Execute .github/workflows/ci.yml's test-job steps locally (VERDICT r4 #7).

No GitHub runner or container runtime exists in this sandbox, so the
workflow can't run under act/docker. This harness is the honest substitute:
it PARSES the workflow (so a YAML/step regression fails here) and executes
each `run` step of the `test` job verbatim with the job's env — except
steps that need the network (pip installs), which are SKIPPED with a
recorded reason. A green run proves the workflow's commands are executable
as written against this checkout.

Run: python scripts/ci_local.py   (the workflow's pytest step already runs
the fast tier — pyproject addopts default to -m "not slow")

The graftlint stage runs FIRST, before any workflow step: static findings
are cheaper than a test tier, so they should gate it. --changed-only
narrows the lint to files with UNCOMMITTED changes vs HEAD (the fast
mid-edit loop) — after a commit it lints nothing, so the pre-push / CI
gate is the default full lint. The graftir contract stage follows (IR-level
drift is cheaper to surface than a test tier). The workflow's own
lint/ir_audit steps are skipped here to avoid running each pass twice.
"""

import argparse
import os
import subprocess
import sys

import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NETWORK_MARKERS = ("pip install", "apt-get", "curl ", "wget ")


def run_lint_stage(changed_only: bool) -> int:
    """The graftlint stage. Returns the lint exit code."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "lint.py")]
    if changed_only:
        cmd.append("--changed-only")
    print(f"== [lint] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_ir_audit_stage() -> int:
    """The graftir stage: rebuild every registered entry point's live
    program contract (tracing; compiling the trainer/serve entries for
    collectives + donation aliasing) and diff against the goldens under
    contracts/. Drift fails with the human-readable report; the report +
    drift.json land in ./ir_artifacts — the dir ci.yml uploads
    (scripts/ir_audit.py; the workflow's matching step is skipped below)."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "ir_audit.py"),
           "--check", "--report", os.path.join(ROOT, "ir_artifacts")]
    print(f"== [graftir] {' '.join(cmd[1:])}")
    return subprocess.run(cmd, cwd=ROOT).returncode


def run_precision_audit_stage() -> int:
    """The graftnum stage: trace every registered entry point and run the
    precision-flow analysis (low-precision accumulation, int8 matmul
    accumulator width, dequant scale discipline, double rounding, orphaned
    scales — analysis/precision_flow.py). Findings name file::function and
    fail the stage; waivers are '# graftir: allow=precision -- why' source
    comments. The per-entry quantization boundary map + report land in
    ./precision_artifacts — the dir ci.yml uploads alongside ir_artifacts
    (scripts/precision_audit.py; the workflow's matching step is skipped
    below)."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts",
                                        "precision_audit.py"),
           "--report", os.path.join(ROOT, "precision_artifacts")]
    print(f"== [graftnum] {' '.join(cmd[1:])}")
    return subprocess.run(cmd, cwd=ROOT).returncode


def run_sync_audit_stage() -> int:
    """The graftsync stage: the whole-module static concurrency model over
    the threaded control plane — guarded-field/lockset violations,
    acquisition-order cycles, blocking calls under a lock, thread-lifecycle
    hygiene — plus drift of the lock-acquisition graph against the golden
    in contracts/sync.json (scripts/sync_audit.py; the workflow's matching
    step is skipped below). Waivers are '# graftsync: allow=<rule> -- why'
    source comments. Report + findings + SARIF land in ./sync_artifacts —
    the dir ci.yml uploads. The runtime half runs inside the gateway/fleet
    smokes (obs/lockorder.py cross-checks the observed graph)."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "sync_audit.py"),
           "--check", "--report", os.path.join(ROOT, "sync_artifacts")]
    print(f"== [graftsync] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_wire_audit_stage() -> int:
    """The graftwire stage: the cross-process wire-protocol model over the
    fleet RPC (sender vs receiver field schemas per verb, verb dispatch
    symmetry, request/replica lifecycle machines vs emitted events —
    analysis/wire_flow.py + rules_wire.py) plus drift of the protocol
    against the golden in contracts/wire.json (scripts/wire_audit.py; the
    workflow's matching step is skipped below). Waivers are
    '# graftwire: allow=<rule> -- why' source comments. Report + findings +
    SARIF land in ./wire_artifacts — the dir ci.yml uploads. The runtime
    half runs inside the gateway/fleet smokes (obs/wiretap.py asserts
    every observed frame ⊆ the golden)."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "wire_audit.py"),
           "--check", "--report", os.path.join(ROOT, "wire_artifacts")]
    print(f"== [graftwire] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_obs_smoke_stage() -> int:
    """The grafttrace + host-overlap + graftpulse smoke stage: a short
    synthetic traced fit (device prefetch + async checkpointing +
    model-health taps ON; the metrics fetch is one step late, as always) that must produce a well-formed
    Perfetto trace, the step-time breakdown AND health/* columns in the
    metrics JSONL, steady-state batch_wait+sync ≈ 0 with the taps fused
    in, a bounded checkpoint-boundary step, a quiet watchdog, <1% span
    overhead, the no-host-transfer/scalar-all-reduce-only tap contract
    (pinned goldens + a live health-on/off probe), and the injected
    codebook collapse → exactly one flight bundle + MODEL-HEALTH DEGRADED
    verdict (scripts/obs_smoke.py; the workflow's matching step is skipped
    below). Artifacts (incl. breakdown.json + health_artifacts/) land in
    ./obs_artifacts — the dir ci.yml uploads."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "obs_smoke.py"),
           "--outdir", os.path.join(ROOT, "obs_artifacts")]
    print(f"== [obs] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_serve_smoke_stage() -> int:
    """The continuous-batching serve stage: a short offered-load run that
    must keep slot occupancy ≥ 90% while the queue is nonempty, produce
    token-exact outputs vs the sequential single-request reference for
    every request, and leave valid per-request TTFT/latency spans
    (scripts/serve_smoke.py; the workflow's matching step is skipped
    below). Artifacts land in ./serve_artifacts — the dir ci.yml
    uploads."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "serve_smoke.py"),
           "--outdir", os.path.join(ROOT, "serve_artifacts")]
    print(f"== [serve] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_gateway_smoke_stage() -> int:
    """The serving-gateway stage: a loopback HTTP/SSE gateway over two tiny
    replicas — one streamed request end-to-end (SSE grid rows, bitwise
    token-exact vs single-request generation), concurrent multi-tenant
    traffic, quota exhaustion → 429, and the AOT cold-start path serving
    with zero backend compiles (scripts/gateway_smoke.py; the workflow's
    matching step is skipped below). Artifacts land in ./gateway_artifacts
    — the dir ci.yml uploads alongside serve_artifacts."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "gateway_smoke.py"),
           "--outdir", os.path.join(ROOT, "gateway_artifacts")]
    print(f"== [gateway] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_fleet_smoke_stage() -> int:
    """The graftfleet stage: a real cross-process replica fleet on
    loopback (scripts/fleet_smoke.py; docs/SERVING.md "Deployment
    topology") — an overload burst breaches the burn-rate sentry and the
    controller attaches a warm AOT-prespawned replica process with ZERO
    backend compiles while goodput recovers; a health-page drain migrates
    a mid-stream request bitwise-invisibly; a chaos-SIGKILLed replica
    process fails over (reason-labeled) and is replaced off missed
    heartbeats; hysteresis/cooldown hold the fleet still under oscillating
    load; and the episode lands as fleet_action events + the obs_report
    FLEET verdict. Artifacts (controller decision log, metrics, flight
    bundles, replica logs) land in ./fleet_artifacts — the dir ci.yml
    uploads (the workflow's matching step is skipped below)."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "fleet_smoke.py"),
           "--outdir", os.path.join(ROOT, "fleet_artifacts")]
    print(f"== [fleet] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def run_chaos_smoke_stage() -> int:
    """The graftmend chaos stage: scripted fault scenarios over the real
    2-process gloo/DCN path (scripts/chaos_smoke.py; docs/RESILIENCE.md)
    — kill a worker mid-step and assert BITWISE-exact recovery vs an
    uninterrupted reference, SIGTERM graceful preemption, injected
    coordinator/checkpoint I/O faults absorbed by the retry layer (not
    crashes), corruption fallback, and an elastic shrink with resharding
    restore. Per-scenario verdicts + agent event logs + flight bundles
    land in ./chaos_artifacts — the dir ci.yml uploads (the workflow's
    matching step is skipped below). Heavy liveness-timeout scenarios stay
    behind --heavy / the slow test tier."""
    cmd = [sys.executable, os.path.join(ROOT, "scripts", "chaos_smoke.py"),
           "--outdir", os.path.join(ROOT, "chaos_artifacts")]
    print(f"== [chaos] {' '.join(cmd[1:])}")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(cmd, cwd=ROOT, env=env).returncode


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--changed-only", action="store_true",
                    help="git-diff-scope the lint stage (fast pre-push loop)")
    args = ap.parse_args()

    if run_lint_stage(args.changed_only) != 0:
        print("ci_local: FAILED (lint stage) — test tiers not run")
        return 1

    rc = run_ir_audit_stage()
    if rc == 3:
        # the audit's distinct missing-golden code: a NEW entry point
        # without a golden, not drift in any pinned program
        print("ci_local: FAILED (graftir goldens MISSING — new entry "
              "point? run scripts/ir_audit.py --update and commit) — "
              "test tiers not run")
        return 1
    if rc != 0:
        print("ci_local: FAILED (graftir contract drift) — test tiers not run")
        return 1

    if run_precision_audit_stage() != 0:
        print("ci_local: FAILED (graftnum precision findings) — test tiers "
              "not run")
        return 1

    rc = run_sync_audit_stage()
    if rc == 3:
        print("ci_local: FAILED (graftsync golden lock graph MISSING — "
              "run scripts/sync_audit.py --update and commit "
              "contracts/sync.json) — test tiers not run")
        return 1
    if rc != 0:
        print("ci_local: FAILED (graftsync concurrency findings / lock-"
              "graph drift) — test tiers not run")
        return 1

    rc = run_wire_audit_stage()
    if rc == 3:
        print("ci_local: FAILED (graftwire golden protocol contract "
              "MISSING — run scripts/wire_audit.py --update and commit "
              "contracts/wire.json) — test tiers not run")
        return 1
    if rc != 0:
        print("ci_local: FAILED (graftwire protocol findings / contract "
              "drift) — test tiers not run")
        return 1

    if run_obs_smoke_stage() != 0:
        print("ci_local: FAILED (observability smoke) — test tiers not run")
        return 1

    if run_serve_smoke_stage() != 0:
        print("ci_local: FAILED (serve smoke) — test tiers not run")
        return 1

    if run_gateway_smoke_stage() != 0:
        print("ci_local: FAILED (gateway smoke) — test tiers not run")
        return 1

    if run_fleet_smoke_stage() != 0:
        print("ci_local: FAILED (fleet smoke) — test tiers not run")
        return 1

    if run_chaos_smoke_stage() != 0:
        print("ci_local: FAILED (chaos smoke) — test tiers not run")
        return 1

    wf = yaml.safe_load(open(os.path.join(ROOT, ".github/workflows/ci.yml")))
    job = wf["jobs"]["test"]
    failures = 0
    for step in job["steps"]:
        name = step.get("name", step.get("uses", "<unnamed>"))
        if "run" not in step:
            print(f"-- [skip] {name}: action step (no local runner)")
            continue
        cmd = step["run"]
        if "scripts/lint.py" in cmd:
            print(f"-- [skip] {name}: already run in the lint stage")
            continue
        if "scripts/ir_audit.py" in cmd:
            print(f"-- [skip] {name}: already run in the graftir stage")
            continue
        if "scripts/precision_audit.py" in cmd:
            print(f"-- [skip] {name}: already run in the graftnum stage")
            continue
        if "scripts/sync_audit.py" in cmd:
            print(f"-- [skip] {name}: already run in the graftsync stage")
            continue
        if "scripts/wire_audit.py" in cmd:
            print(f"-- [skip] {name}: already run in the graftwire stage")
            continue
        if "scripts/obs_smoke.py" in cmd:
            print(f"-- [skip] {name}: already run in the obs smoke stage")
            continue
        if "scripts/serve_smoke.py" in cmd:
            print(f"-- [skip] {name}: already run in the serve smoke stage")
            continue
        if "scripts/gateway_smoke.py" in cmd:
            print(f"-- [skip] {name}: already run in the gateway smoke "
                  "stage")
            continue
        if "scripts/fleet_smoke.py" in cmd:
            print(f"-- [skip] {name}: already run in the fleet smoke stage")
            continue
        if "scripts/chaos_smoke.py" in cmd:
            print(f"-- [skip] {name}: already run in the chaos smoke stage")
            continue
        if any(m in cmd for m in NETWORK_MARKERS):
            # the editable-install smoke is half network, half local: keep
            # the local import check. Join backslash continuations first so
            # a continued pip line is dropped whole, and drop comments.
            joined = cmd.replace("\\\n", " ")
            local_lines = [ln for ln in joined.splitlines()
                           if ln.strip() and not ln.strip().startswith("#")
                           and not any(m in ln for m in NETWORK_MARKERS)]
            if not local_lines:
                print(f"-- [skip] {name}: needs network (pip)")
                continue
            cmd = "\n".join(local_lines)
            print(f"-- [trim] {name}: network lines skipped, running rest")
        env = dict(os.environ)
        env.update({k: str(v) for k, v in (step.get("env") or {}).items()})
        print(f"== [run] {name}: {cmd!r}")
        r = subprocess.run(cmd, shell=True, cwd=ROOT, env=env)
        if r.returncode != 0:
            # fail fast like the Actions job would: later steps never run
            # after a failing one, so executing them here would diverge
            # from the workflow being validated (and burn the 1-core box)
            print(f"!! step failed: {name} (exit {r.returncode}) — "
                  "remaining steps skipped (Actions fail-fast semantics)")
            failures += 1
            break
    print("ci_local:", "FAILED" if failures else "GREEN",
          f"({failures} failing steps)" if failures else "")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
