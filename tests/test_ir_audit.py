"""graftir contract toolchain: golden roundtrip stability, drift detection
on an injected upcast and an injected collective, the --update flow, waiver
handling, and the HLO parsers.

The expensive registry entries (trainers, serve engine) are exercised once
by the CI stage (scripts/ir_audit.py --check); these tests pin the
TOOLCHAIN's behavior on small synthetic programs so a parser or diff
regression fails in seconds, not minutes.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.analysis import ir_audit as A
from dalle_tpu.analysis.contracts import BuiltEntry, EntrySpec
from dalle_tpu.config import MeshConfig
from dalle_tpu.parallel.mesh import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.recompile_budget(64)


# ---------------------------------------------------------------------------
# synthetic programs
# ---------------------------------------------------------------------------

def _clean_fn(x):
    return jnp.sin(x) * 2.0 + 1.0


def _upcast_fn(x):
    # the hazard the audit exists to catch: a silent bf16->f32 widening
    y = x.astype(jnp.float32)
    return (jnp.sin(y) * 2.0 + 1.0).astype(x.dtype)


_X_BF16 = jnp.zeros((8, 16), jnp.bfloat16)


@functools.lru_cache(maxsize=None)
def _mesh8():
    return build_mesh(MeshConfig(dp=4, fsdp=2))


def _psum_fn(n_psums):
    from jax.sharding import PartitionSpec as P
    mesh = _mesh8()

    def body(x):
        for _ in range(n_psums):
            x = jax.lax.psum(x, "dp")
        return x

    return jax.shard_map(body, mesh=mesh, in_specs=P("dp"), out_specs=P())


# ---------------------------------------------------------------------------
# contract build: determinism + roundtrip
# ---------------------------------------------------------------------------

def test_contract_build_is_deterministic():
    built = BuiltEntry(fn=_clean_fn, args=(_X_BF16,))
    a = A.build_contract("t", built)
    b = A.build_contract("t", built)
    assert a == b


def test_contract_json_roundtrip_is_stable(tmp_path):
    built = BuiltEntry(fn=_upcast_fn, args=(_X_BF16,))
    live = A.build_contract("t", built)
    path = str(tmp_path / "t.json")
    A.save_contract(live, path)
    loaded = A.load_contract(path)
    assert loaded == json.loads(json.dumps(live))  # tuples etc. normalized
    assert A.diff_contracts(loaded, live) == {}
    # a second save of the loaded contract is byte-identical (sorted keys,
    # fixed indent) — goldens don't churn in git without a program change
    path2 = str(tmp_path / "t2.json")
    A.save_contract(loaded, path2)
    assert open(path).read() == open(path2).read()


def test_load_contract_missing_returns_none(tmp_path):
    assert A.load_contract(str(tmp_path / "nope.json")) is None


# ---------------------------------------------------------------------------
# drift detection: injected upcast, injected collective, memory tolerance
# ---------------------------------------------------------------------------

def test_injected_upcast_drifts_with_site_and_bytes():
    golden = A.build_contract("t", BuiltEntry(fn=_clean_fn, args=(_X_BF16,)))
    live = A.build_contract("t", BuiltEntry(fn=_upcast_fn, args=(_X_BF16,)))
    drift = A.diff_contracts(golden, live)
    assert "promotions" in drift
    (line,) = drift["promotions"]
    assert "bfloat16->float32" in line
    assert "_upcast_fn" in line                       # provenance site
    assert A._fmt_bytes(8 * 16 * 4) in line           # widened bytes
    # the histogram moves too: the two added convert_element_type eqns
    assert any("convert_element_type" in ln
               for ln in drift.get("primitives", []))


def test_clean_contract_does_not_drift_on_itself():
    golden = A.build_contract("t", BuiltEntry(fn=_upcast_fn, args=(_X_BF16,)))
    live = A.build_contract("t", BuiltEntry(fn=_upcast_fn, args=(_X_BF16,)))
    assert A.diff_contracts(golden, live) == {}


def test_injected_collective_drifts_with_kind_bytes_axes():
    mesh = _mesh8()
    x = jnp.zeros((8, 4), jnp.float32)

    def compiled(fn):
        jitted = jax.jit(fn)
        hlo = jitted.lower(x).compile().as_text()
        return A.collective_inventory(hlo, mesh)

    base = compiled(_psum_fn(1))
    more = compiled(_psum_fn(2))
    golden = {"primitives": {}, "collectives": base}
    live = {"primitives": {}, "collectives": more}
    drift = A.diff_contracts(golden, live)
    assert "collectives" in drift
    text = "\n".join(drift["collectives"])
    assert "all-reduce" in text
    assert "axis 'dp'" in text          # mesh-axis attribution, not raw ids
    assert "+1" in text                 # the injected extra collective


def test_count_stable_byte_drift_is_detected():
    # an upcast moved from a small tensor to a big one at the same site
    # keeps (src, dst, site, count) identical — the byte volume must drift
    ev = {"src": "bfloat16", "dst": "float32",
          "site": "dalle_tpu/m.py::f", "count": 1}
    golden = {"primitives": {}, "promotions": [dict(ev, bytes=16384)]}
    live = {"primitives": {}, "promotions": [dict(ev, bytes=4 << 20)]}
    drift = A.diff_contracts(golden, live)
    (line,) = drift["promotions"]
    assert "bytes 16.0 KB -> 4.0 MB" in line and line.startswith("~")
    # collectives key on bytes already — same-kind different-bytes shows as
    # a +1/-1 pair, not a byte mutation line
    g = {"primitives": {}, "collectives": [
        {"kind": "all-reduce", "bytes": 1024, "axes": "dp", "count": 1}]}
    l2 = {"primitives": {}, "collectives": [
        {"kind": "all-reduce", "bytes": 2048, "axes": "dp", "count": 1}]}
    assert len(A.diff_contracts(g, l2)["collectives"]) == 2


def test_memory_estimate_tolerance():
    golden = {"primitives": {}, "memory": {"peak_bytes_est": 1000}}
    within = {"primitives": {}, "memory": {"peak_bytes_est": 1040}}
    beyond = {"primitives": {}, "memory": {"peak_bytes_est": 1200}}
    assert "memory" not in A.diff_contracts(golden, within)
    drift = A.diff_contracts(golden, beyond)
    assert "memory" in drift and "+20.0%" in drift["memory"][0]


def test_peak_memory_estimate_scales_with_program():
    small = A.build_contract(
        "t", BuiltEntry(fn=_clean_fn, args=(jnp.zeros((8, 16), jnp.float32),)))
    big = A.build_contract(
        "t", BuiltEntry(fn=_clean_fn, args=(jnp.zeros((64, 16), jnp.float32),)))
    assert big["memory"]["peak_bytes_est"] > small["memory"]["peak_bytes_est"]
    assert small["memory"]["peak_bytes_est"] >= small["memory"]["arg_bytes"]


# ---------------------------------------------------------------------------
# HLO parsers
# ---------------------------------------------------------------------------

def test_parse_hlo_shapes():
    assert A._parse_hlo_shapes("f32[8,16]{1,0} %a, bf16[4] %b") == \
        8 * 16 * 4 + 4 * 2
    assert A._parse_hlo_shapes("f32[] %scalar") == 4   # rank-0: numel 1
    assert A._parse_hlo_shapes("token[] %tok") == 0    # unknown dtype skipped


def test_parse_replica_groups_both_forms():
    explicit = A.parse_replica_groups("{{0,1},{2,3}}")
    assert explicit == [frozenset({0, 1}), frozenset({2, 3})]
    iota = A.parse_replica_groups("[2,4]<=[8]")
    assert iota == [frozenset({0, 1, 2, 3}), frozenset({4, 5, 6, 7})]
    transposed = A.parse_replica_groups("[4,2]<=[2,4]T(1,0)")
    assert frozenset({0, 4}) in transposed and len(transposed) == 4


def test_axes_for_groups_names_mesh_axes():
    mesh = _mesh8()   # dp=4, fsdp=2
    assert A.axes_for_groups(mesh, A.mesh_axis_groups(mesh, ("dp",))) == "dp"
    assert A.axes_for_groups(mesh, A.mesh_axis_groups(mesh, ("fsdp",))) == \
        "fsdp"
    assert A.axes_for_groups(
        mesh, A.mesh_axis_groups(mesh, ("dp", "fsdp"))) == "dp,fsdp"
    assert A.axes_for_groups(mesh, [frozenset({0})]) == "none"
    assert A.axes_for_groups(mesh, [frozenset({0, 3})]) == "unmatched"


def test_axes_for_pairs_names_crossed_axes():
    mesh = _mesh8()   # dp=4, fsdp=2: ids laid out (dp, fsdp)
    # ring shift along fsdp: each pair flips only the fsdp coordinate
    shift = [(0, 1), (1, 0), (2, 3), (3, 2), (4, 5), (5, 4), (6, 7), (7, 6)]
    assert A.axes_for_pairs(mesh, shift) == "fsdp"
    # resharding permute crossing both axes (plus self-pairs, GSPMD-style)
    resh = [(0, 0), (1, 2), (3, 5), (7, 7)]
    assert A.axes_for_pairs(mesh, resh) == "dp,fsdp"
    assert A.axes_for_pairs(mesh, [(0, 0), (3, 3)]) == "none"
    assert A.axes_for_pairs(mesh, [(0, 99)]) == "unknown"


def test_collective_inventory_parses_and_aggregates():
    # operands print by name: their bytes come from the defining lines
    hlo = """
ENTRY %main (p0: f32[8,16], p1: f32[8,16], p2: f32[2]) -> f32[4] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %p1 = f32[8,16]{1,0} parameter(1)
  %p2 = f32[2]{0} parameter(2)
  %ar1 = f32[8,16]{1,0} all-reduce(%p0), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ar2 = f32[8,16]{1,0} all-reduce(%p1), replica_groups={{0,1,2,3}}, to_apply=%sum
  %ag = (f32[2]{0}, f32[4]{0}) all-gather-start(%p2), replica_groups=[2,2]<=[4]
  ROOT %agd = f32[4]{0} all-gather-done(%ag)
}
"""
    inv = A.collective_inventory(hlo)
    by_kind = {e["kind"]: e for e in inv}
    assert by_kind["all-reduce"]["count"] == 2          # aggregated
    assert by_kind["all-reduce"]["bytes"] == 8 * 16 * 4
    assert by_kind["all-gather"]["count"] == 1          # -done not recounted
    assert by_kind["all-gather"]["bytes"] == 2 * 4      # -start carries args


def test_donation_report_counts_balanced_alias_block():
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{1}: (1, {}, must-alias) }, entry_computation_layout=...")
    rep = A.donation_report(hlo, donated_leaves=3)
    assert rep == {"donated": 3, "aliased": 2}
    assert A.donation_report("HloModule m", 3) == {"donated": 3, "aliased": 0}


def test_donation_effectiveness_end_to_end():
    # same shape/dtype in->out: XLA aliases the donated buffer even on cpu
    fn = jax.jit(lambda x: x + 1.0, donate_argnums=(0,))
    x = jnp.zeros((16,), jnp.float32)
    hlo = fn.lower(x).compile().as_text()
    assert A.donation_report(hlo, 1) == {"donated": 1, "aliased": 1}


# ---------------------------------------------------------------------------
# waivers
# ---------------------------------------------------------------------------

def _write(tmp_path, rel, text):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(text)
    return rel


def test_collect_waivers_good_bare_and_unknown(tmp_path):
    rel = _write(tmp_path, "mod.py", (
        "x = 1  # graftir: allow=donation -- scan carry blocks aliasing\n"
        "y = 2  # graftir: allow=collectives\n"
        "z = 3  # graftir: allow=made-up-rule -- whatever\n"))
    waivers, problems = A.collect_waivers(rel, repo_root=str(tmp_path))
    assert set(waivers) == {"donation"}
    assert waivers["donation"].reason == "scan carry blocks aliasing"
    assert len(problems) == 2
    assert any("no reason" in p for p in problems)
    assert any("unknown graftir rule" in p for p in problems)


def test_waiver_in_string_literal_does_not_waive(tmp_path):
    rel = _write(tmp_path, "mod.py",
                 's = "# graftir: allow=donation -- fake"\n')
    waivers, problems = A.collect_waivers(rel, repo_root=str(tmp_path))
    assert waivers == {} and problems == []


def test_collect_waivers_missing_file_is_empty(tmp_path):
    assert A.collect_waivers("absent.py", repo_root=str(tmp_path)) == ({}, [])


# ---------------------------------------------------------------------------
# audit_entry orchestration + the CLI flows
# ---------------------------------------------------------------------------

def _spec(tmp_path, fn, source="src.py"):
    return EntrySpec("synth", source,
                     lambda: BuiltEntry(fn=fn, args=(_X_BF16,)))


def test_audit_entry_missing_golden_then_update_then_clean(tmp_path):
    cdir = str(tmp_path / "contracts")
    spec = _spec(tmp_path, _clean_fn)
    _write(tmp_path, "src.py", "x = 1\n")

    report, _ = A.audit_entry("synth", spec, cdir, repo_root=str(tmp_path))
    assert report.failed and "missing" in report.drift      # no golden yet

    report, _ = A.audit_entry("synth", spec, cdir, update=True,
                              repo_root=str(tmp_path))
    assert report.updated and not report.failed
    assert os.path.exists(A.contract_path(cdir, "synth"))

    report, _ = A.audit_entry("synth", spec, cdir, repo_root=str(tmp_path))
    assert not report.failed                                # clean roundtrip


def test_audit_entry_drift_report_names_entry_and_rule(tmp_path):
    cdir = str(tmp_path / "contracts")
    _write(tmp_path, "src.py", "x = 1\n")
    A.audit_entry("synth", _spec(tmp_path, _clean_fn), cdir, update=True,
                  repo_root=str(tmp_path))
    report, _ = A.audit_entry("synth", _spec(tmp_path, _upcast_fn), cdir,
                              repo_root=str(tmp_path))
    assert report.failed and "promotions" in report.drift
    text = A.render_report([report], {"synth": "src.py"}, "1 entry")
    assert "synth (src.py)" in text
    assert "bfloat16->float32" in text
    assert "contract drift in 1 entry" in text
    assert "--update" in text                # tells the reader the way out


def test_audit_entry_waiver_suppresses_drift(tmp_path):
    cdir = str(tmp_path / "contracts")
    src = _write(tmp_path, "src.py", "x = 1\n")
    A.audit_entry("synth", _spec(tmp_path, _clean_fn, src), cdir, update=True,
                  repo_root=str(tmp_path))
    _write(tmp_path, "src.py",
           "x = 1  # graftir: allow=promotions -- f32 logits on purpose\n"
           "# graftir: allow=primitives -- ditto\n"
           "# graftir: allow=memory -- ditto\n"
           "# graftir: allow=precision -- ditto (value classes move too)\n")
    report, _ = A.audit_entry("synth", _spec(tmp_path, _upcast_fn, src), cdir,
                              repo_root=str(tmp_path))
    assert not report.failed
    assert "promotions" in report.waived
    assert "f32 logits on purpose" in report.waived["promotions"][0]


def test_explain_renders_a_contract():
    live = A.build_contract("t", BuiltEntry(fn=_upcast_fn, args=(_X_BF16,)))
    text = A.explain(live)
    assert "entry: t" in text and "primitives:" in text
    assert "convert_element_type" in text
    assert "bfloat16->float32" in text
    assert "memory: peak est" in text


def test_cli_check_update_explain_flows(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import ir_audit as cli
    finally:
        sys.path.pop(0)
    from dalle_tpu.analysis import contracts as C
    _write(tmp_path, "src.py", "x = 1\n")
    monkeypatch.setattr(C, "ENTRIES", {
        "synth": EntrySpec("synth", "src.py",
                           lambda: BuiltEntry(fn=_clean_fn, args=(_X_BF16,)))})
    monkeypatch.setattr(A, "REPO_ROOT", str(tmp_path))
    cdir = str(tmp_path / "contracts")
    rdir = str(tmp_path / "report")

    assert cli.main(["--list-entries"]) == 0
    # no golden yet: --check fails with the DISTINCT missing-golden code
    # (3, not 1) so CI logs separate "new entry point needs --update" from
    # a real regression; the report artifact still names the gap
    assert cli.main(["--check", "--contracts-dir", cdir,
                     "--report", rdir]) == 3
    drift = json.load(open(os.path.join(rdir, "drift.json")))
    assert drift[0]["entry"] == "synth" and "missing" in drift[0]["drift"]
    assert cli.main(["--update", "--contracts-dir", cdir]) == 0
    assert cli.main(["--check", "--contracts-dir", cdir,
                     "--report", rdir]) == 0
    assert "contracts clean" in open(os.path.join(rdir, "report.txt")).read()
    assert cli.main(["--explain", "synth", "--contracts-dir", cdir]) == 0
    with pytest.raises(SystemExit, match="unknown entr"):
        cli.main(["--check", "--entries", "nope"])


def test_cli_exit_codes_distinguish_missing_from_drift(tmp_path,
                                                       monkeypatch, capsys):
    """Acceptance for the CI-log contract: only-missing goldens exit 3 and
    SAY so; any real drift exits 1 even when another entry is also
    missing (a regression must never be soft-pedaled as 'new entry')."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import ir_audit as cli
    finally:
        sys.path.pop(0)
    from dalle_tpu.analysis import contracts as C
    _write(tmp_path, "src.py", "x = 1\n")
    monkeypatch.setattr(A, "REPO_ROOT", str(tmp_path))
    cdir = str(tmp_path / "contracts")

    entries = {
        "pinned": EntrySpec("pinned", "src.py",
                            lambda: BuiltEntry(fn=_clean_fn,
                                               args=(_X_BF16,)))}
    monkeypatch.setattr(C, "ENTRIES", dict(entries))
    assert cli.main(["--update", "--contracts-dir", cdir]) == 0

    # add a second entry with no golden: exit 3, message names the way out
    entries["fresh"] = EntrySpec("fresh", "src.py",
                                 lambda: BuiltEntry(fn=_clean_fn,
                                                    args=(_X_BF16,)))
    monkeypatch.setattr(C, "ENTRIES", dict(entries))
    capsys.readouterr()
    assert cli.main(["--check", "--contracts-dir", cdir]) == 3
    out = capsys.readouterr().out
    assert "exit 3" in out and "MISSING" in out and "--update" in out

    # now ALSO drift the pinned entry: the regression code wins
    entries["pinned"] = EntrySpec("pinned", "src.py",
                                  lambda: BuiltEntry(fn=_upcast_fn,
                                                     args=(_X_BF16,)))
    monkeypatch.setattr(C, "ENTRIES", dict(entries))
    assert cli.main(["--check", "--contracts-dir", cdir]) == 1


# ---------------------------------------------------------------------------
# the repo's own goldens
# ---------------------------------------------------------------------------

def test_registry_entries_have_goldens_and_valid_schema():
    from dalle_tpu.analysis import contracts as C
    cdir = os.path.join(REPO, "contracts")
    for name in C.ENTRIES:
        golden = A.load_contract(A.contract_path(cdir, name))
        assert golden is not None, f"no golden for {name} — run --update"
        assert golden["schema"] == A.SCHEMA
        assert golden["entry"] == name
        assert golden["primitives"], name
    # and no orphaned goldens for entries that no longer exist (sync.json
    # and wire.json are the graftsync lock-graph and graftwire protocol
    # goldens, not graftir entry contracts — tests/test_sync_flow.py and
    # tests/test_wire_flow.py own their schemas)
    for fname in os.listdir(cdir):
        if fname in ("sync.json", "wire.json"):
            continue
        assert fname.removesuffix(".json") in C.ENTRIES, fname


def test_trainer_goldens_pin_donation_and_collectives():
    # the acceptance-criterion invariant, pinned at the golden level: every
    # donated leaf of all four trainer steps is aliased in the executable,
    # and the multi-axis entries actually contain collectives
    cdir = os.path.join(REPO, "contracts")
    for name in ("train_step_dalle", "train_step_vae", "train_step_clip",
                 "train_step_vqgan"):
        golden = A.load_contract(A.contract_path(cdir, name))
        don = golden["donation"]
        assert don["aliased"] == don["donated"] > 0, (name, don)
        assert golden["collectives"], name
        axes = {e["axes"] for e in golden["collectives"]}
        assert "unknown" not in axes and "unmatched" not in axes, (name, axes)
