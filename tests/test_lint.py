"""graftlint: positive/negative fixtures per rule, suppression semantics,
the estimator/ceiling contract, the repo-clean invariant, and the runtime
recompile counter.

Fixture sources are linted in-memory through FileContext — the rel_path
argument drives each rule's path scoping, so fixtures can pretend to live
anywhere in the tree.
"""

import ast
import os
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import pytest

from dalle_tpu.analysis import RULES, run_lint
from dalle_tpu.analysis.core import FileContext
from dalle_tpu.analysis.rules_coverage import untested_ops
from dalle_tpu.analysis.rules_vmem import check_estimator_contract

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_source(rule: str, src: str, rel_path: str = "dalle_tpu/_fixture.py"):
    return RULES[rule].run(FileContext(rel_path, textwrap.dedent(src)))


# ---------------------------------------------------------------------------
# prng-key-reuse
# ---------------------------------------------------------------------------

def test_prng_rule_flags_literal_key():
    src = """
    import jax
    def f(key=None):
        key = key if key is not None else jax.random.PRNGKey(0)
        return jax.random.uniform(key, (2,))
    """
    found = lint_source("prng-key-reuse", src)
    assert len(found) == 1 and "hard-coded" in found[0].message


def test_prng_rule_flags_key_consumed_twice():
    src = """
    import jax
    def f(key):
        a = jax.random.uniform(key, (2,))
        b = jax.random.normal(key, (2,))
        return a + b
    """
    found = lint_source("prng-key-reuse", src)
    assert len(found) == 1 and "already consumed" in found[0].message


def test_prng_rule_accepts_split_between_uses():
    src = """
    import jax
    def f(key):
        a = jax.random.uniform(key, (2,))
        key, sub = jax.random.split(key)
        b = jax.random.normal(key, (2,))
        return a + b + jax.random.gumbel(sub, (2,))
    """
    assert lint_source("prng-key-reuse", src) == []


def test_prng_rule_sees_from_jax_import_random_alias():
    src = """
    from jax import random
    def f(key):
        a = random.uniform(key, (2,))
        b = random.normal(key, (2,))
        return a + b
    """
    assert len(lint_source("prng-key-reuse", src)) == 1
    # stdlib `random` is NOT a key consumer
    stdlib = """
    import random
    def f(lines):
        a = random.choice(lines)
        b = random.choice(lines)
        return a + b
    """
    assert lint_source("prng-key-reuse", stdlib) == []


def test_prng_rule_if_else_branches_are_not_reuse():
    src = """
    import jax
    def f(key, training):
        if training:
            x = jax.random.bernoulli(key, 0.5)
        else:
            x = jax.random.normal(key, (2,))
        return x
    """
    assert lint_source("prng-key-reuse", src) == []
    # module-level reuse IS scanned
    top = """
    import jax
    def make():
        return None
    k = make()
    a = jax.random.uniform(k, (2,))
    b = jax.random.normal(k, (2,))
    """
    assert len(lint_source("prng-key-reuse", top)) == 1


def test_prng_rule_branch_uses_plus_later_use_single_finding():
    src = """
    import jax
    def f(key, t):
        if t:
            a = jax.random.uniform(key, (2,))
        else:
            a = jax.random.normal(key, (2,))
        return a + jax.random.gumbel(key, (2,))
    """
    found = lint_source("prng-key-reuse", src)
    assert len(found) == 1  # one reuse line → one finding, not one per branch


def test_suppression_inside_string_does_not_suppress():
    src = '''
    import jax
    DOC = "# graftlint: disable=prng-key-reuse"
    K = jax.random.PRNGKey(0)
    '''
    found = lint_source("prng-key-reuse", src)
    assert len(found) == 1  # the quoted directive is data, not a directive


def test_prng_rule_out_of_scope_for_tests_and_scripts():
    src = "import jax\nk = jax.random.PRNGKey(0)\n"
    assert lint_source("prng-key-reuse", src, "scripts/bench_x.py") == []


def test_suppression_comment_silences_a_line():
    src = """
    import jax
    def f():
        return jax.random.PRNGKey(0)  # graftlint: disable=prng-key-reuse
    """
    assert lint_source("prng-key-reuse", src) == []
    src_above = """
    import jax
    def f():
        # graftlint: disable=prng-key-reuse
        return jax.random.PRNGKey(0)
    """
    assert lint_source("prng-key-reuse", src_above) == []


# ---------------------------------------------------------------------------
# broad-except
# ---------------------------------------------------------------------------

def test_broad_except_positive_and_bare():
    src = """
    try:
        x = 1
    except Exception:
        pass
    """
    assert len(lint_source("broad-except", src)) == 1
    bare = """
    try:
        x = 1
    except:
        pass
    """
    found = lint_source("broad-except", bare)
    assert len(found) == 1 and "bare" in found[0].message


def test_broad_except_justified_or_narrow_is_clean():
    src = """
    try:
        x = 1
    except Exception as e:  # noqa: BLE001 - sample-level skip
        pass
    try:
        y = 2
    except (ValueError, KeyError):
        pass
    """
    assert lint_source("broad-except", src) == []


# ---------------------------------------------------------------------------
# jit-static-hazard
# ---------------------------------------------------------------------------

def test_static_hazard_flags_fresh_dict_at_call_site():
    src = """
    import functools
    import jax
    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, cfg):
        return x
    def g(x):
        return f(x, {"chunks": 4})
    def h(x):
        return f(x, cfg=dict(chunks=4))
    """
    found = lint_source("jit-static-hazard", src)
    assert len(found) == 2
    assert all("recompile" in f.message or "TypeError" in f.message
               for f in found)


def test_static_hazard_call_form_matches_jitted_binding_not_wrapped_fn():
    src = """
    import jax
    def f(x, cfg):
        return x
    g = jax.jit(f, static_argnums=(1,))
    def use(x):
        a = g(x, {"a": 1})      # the jitted call: hazard
        b = f(x, {"a": 1})      # plain python call: fine
        return a + b
    """
    found = lint_source("jit-static-hazard", src)
    assert len(found) == 1 and "'g'" in found[0].message


def test_static_hazard_accepts_hashable_name():
    src = """
    import functools
    import jax
    CFG = ("a", 4)
    @functools.partial(jax.jit, static_argnums=(1,))
    def f(x, cfg):
        return x
    def g(x):
        return f(x, CFG)
    """
    assert lint_source("jit-static-hazard", src) == []


# ---------------------------------------------------------------------------
# host-sync-in-jit
# ---------------------------------------------------------------------------

def test_host_sync_flags_item_float_asarray():
    src = """
    import jax
    import numpy as np
    @jax.jit
    def f(x):
        a = x.item()
        b = float(x) + 1
        c = np.asarray(x)
        return a + b
    """
    assert len(lint_source("host-sync-in-jit", src)) == 3


def test_host_sync_allows_float_on_static_params():
    src = """
    from functools import partial
    import jax
    @partial(jax.jit, static_argnames=("scale",))
    def f(x, scale):
        return x * float(scale)
    @partial(jax.jit, static_argnums=(1,))
    def h(x, n):
        return x * int(n)
    """
    assert lint_source("host-sync-in-jit", src) == []


def test_host_sync_ignores_nested_host_callback_body():
    # a nested plain def inside a jitted function may be a pure_callback
    # host body — host work there is the point, not a hazard
    src = """
    import jax
    import numpy as np
    @jax.jit
    def f(x):
        def host_fn(a):
            return np.asarray(a).sum()
        return jax.pure_callback(host_fn, x[0], x)
    """
    assert lint_source("host-sync-in-jit", src) == []


def test_host_sync_clean_outside_jit_and_on_statics():
    src = """
    import jax
    import numpy as np
    def plain(x):
        return float(x)
    @jax.jit
    def f(x):
        scale = float(1.0)
        return x * scale
    y = np.asarray([1.0])
    """
    assert lint_source("host-sync-in-jit", src) == []


# ---------------------------------------------------------------------------
# python-branch-on-tracer
# ---------------------------------------------------------------------------

def test_branch_on_tracer_flags_if_and_while():
    src = """
    import jax
    import jax.numpy as jnp
    @jax.jit
    def f(x):
        if jnp.any(x > 0):
            return x
        while jnp.max(x) > 1:
            x = x - 1
        return -x
    """
    assert len(lint_source("python-branch-on-tracer", src)) == 2


def test_branch_on_static_config_is_clean():
    src = """
    import jax
    @jax.jit
    def f(x, *, chunks=0):
        if chunks > 0:
            return x
        return -x
    """
    assert lint_source("python-branch-on-tracer", src) == []


# ---------------------------------------------------------------------------
# donate-missing
# ---------------------------------------------------------------------------

def test_donate_missing_flags_undonated_train_step():
    src = """
    import jax
    @jax.jit
    def train_step(state, batch):
        return state
    """
    found = lint_source("donate-missing", src, "dalle_tpu/train/_fixture.py")
    assert len(found) == 1 and "donate" in found[0].message


def test_donate_missing_clean_when_donating_or_not_a_step():
    src = """
    from functools import partial
    import jax
    @partial(jax.jit, donate_argnums=(0,))
    def train_step(state, batch):
        return state
    @jax.jit
    def sample(params, prompt):
        return prompt
    """
    assert lint_source("donate-missing", src,
                       "dalle_tpu/train/_fixture.py") == []
    # bench scripts are out of scope by design
    undonated = "import jax\n@jax.jit\ndef step(s, b):\n    return s\n"
    assert lint_source("donate-missing", undonated,
                       "scripts/serve_bench.py") == []


# ---------------------------------------------------------------------------
# vmem-ceiling
# ---------------------------------------------------------------------------

def _fake_fused(bwd_coeff_seq: int, limits, budget):
    """A module-shaped namespace replicating fused_attention's selection
    logic, with a tweakable estimator/tier table."""
    def _bwd_bytes(n, hd):
        return 34 * n * hd + bwd_coeff_seq * n * n

    def _compiler_params(est):
        if est <= 14 * 1024 * 1024:
            return None
        need = est + est // 4
        for _, limit in limits:
            if need <= limit:
                return types.SimpleNamespace(vmem_limit_bytes=limit)
        return types.SimpleNamespace(vmem_limit_bytes=limits[-1][1])

    return types.SimpleNamespace(
        _bwd_bytes=_bwd_bytes, _compiler_params=_compiler_params,
        _VMEM_RAISED_LIMITS=tuple(limits), _VMEM_RAISED_BUDGET=budget)


_M = 1024 * 1024
_REAL_LIMITS = ((30 * _M, 32 * _M), (44 * _M, 48 * _M))


def test_vmem_contract_holds_on_the_real_module():
    from dalle_tpu.ops import fused_attention
    assert check_estimator_contract(fused_attention) == []
    # and the faithful fake agrees (coeff 14 = 12 + 2 from _bwd_bytes)
    assert check_estimator_contract(_fake_fused(14, _REAL_LIMITS, 30 * _M)) == []


def test_vmem_contract_catches_estimator_drift():
    # estimator shrunk: headroom no longer covers the measured 25.68M point
    msgs = check_estimator_contract(_fake_fused(6, _REAL_LIMITS, 30 * _M))
    assert any("no longer covers" in m for m in msgs)


def test_vmem_contract_catches_tier_edits():
    # medium tier lowered 32M -> 24M: the calibration shape routes elsewhere
    msgs = check_estimator_contract(
        _fake_fused(14, ((30 * _M, 24 * _M), (44 * _M, 48 * _M)), 30 * _M))
    assert any("32M" in m or "Estimator and tier table" in m for m in msgs)
    # gate raised past the top ceiling's headroom
    msgs = check_estimator_contract(
        _fake_fused(14, ((30 * _M, 32 * _M), (44 * _M, 45 * _M)), 44 * _M))
    assert any("no dense fallback" in m for m in msgs)


def test_vmem_rule_flags_rogue_literal_ceiling():
    rogue = FileContext("dalle_tpu/ops/_fixture.py", textwrap.dedent("""
        import jax
        def call(k, pltpu, pl):
            return pl.pallas_call(
                k, compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=12345))
    """))
    found = RULES["vmem-ceiling"].run_project([rogue])
    assert any("12345" in f.message for f in found)


# ---------------------------------------------------------------------------
# scatter-minormost / scatter-missing-hints
# ---------------------------------------------------------------------------

def test_scatter_minormost_flags_trailing_array_index():
    src = """
    def append_scales(scale, sc, idx):
        return scale.at[:, :, idx].set(sc, unique_indices=True,
                                       indices_are_sorted=True)
    """
    found = lint_source("scatter-minormost", src,
                        rel_path="dalle_tpu/ops/_fixture.py")
    assert len(found) == 1 and "minormost" in found[0].message
    # a leading Ellipsis aligns the trailing element with the lane axis
    ell = """
    def poke(buf, idx):
        return buf.at[..., idx].set(1.0, unique_indices=True,
                                    indices_are_sorted=True)
    """
    assert len(lint_source("scatter-minormost", ell,
                           rel_path="dalle_tpu/ops/_fixture.py")) == 1


def test_scatter_minormost_clean_on_sequence_major_and_out_of_scope():
    # trailing full slice (the append_rows shape) is the blessed layout
    src = """
    def append_rows(kv, rows, ab, idx):
        return kv.at[ab, idx].set(rows, unique_indices=True,
                                  indices_are_sorted=True)
    def trailing_ellipsis(kv, idx):
        return kv.at[idx, ...].set(0.0, unique_indices=True,
                                   indices_are_sorted=True)
    """
    assert lint_source("scatter-minormost", src,
                       rel_path="dalle_tpu/ops/_fixture.py") == []
    # single index element: rank unknown, never flagged
    one = """
    def write(buf, idx, v):
        return buf.at[idx].set(v, unique_indices=True,
                               indices_are_sorted=True)
    """
    assert lint_source("scatter-minormost", one,
                       rel_path="dalle_tpu/ops/_fixture.py") == []
    # rule is scoped to ops code
    bad = """
    def f(scale, sc, idx):
        return scale.at[:, :, idx].set(sc)
    """
    assert lint_source("scatter-minormost", bad,
                       rel_path="dalle_tpu/train/_fixture.py") == []


def test_scatter_missing_hints_flags_bare_array_scatter():
    src = """
    def append(kv, rows, ab, idx):
        return kv.at[ab, idx].set(rows)
    """
    found = lint_source("scatter-missing-hints", src,
                        rel_path="dalle_tpu/ops/_fixture.py")
    assert len(found) == 1 and "unique_indices" in found[0].message
    # .add scatters too
    add = """
    def accumulate(buf, idx, v):
        return buf.at[:, idx].add(v)
    """
    assert len(lint_source("scatter-missing-hints", add,
                           rel_path="dalle_tpu/ops/_fixture.py")) == 1


def test_scatter_missing_hints_clean_cases():
    src = """
    def hinted(kv, rows, ab, idx):
        return kv.at[ab, idx].set(rows, unique_indices=True,
                                  indices_are_sorted=True)
    def one_hint(kv, rows, idx):
        return kv.at[idx].set(rows, unique_indices=True)
    def static_single(buf):
        return buf.at[0].set(1.0)
    def static_negative(buf):
        return buf.at[-1].set(1.0)
    def static_arith(buf, v):
        return buf.at[2 + 3, :].set(v)
    def slices_only(buf, v):
        return buf.at[:, 1:3].set(v)
    def suppressed(kv, rows, idx):
        # graftlint: disable=scatter-missing-hints
        return kv.at[idx].set(rows)
    """
    assert lint_source("scatter-missing-hints", src,
                       rel_path="dalle_tpu/ops/_fixture.py") == []


# ---------------------------------------------------------------------------
# untested-public-op
# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# weak-type-promotion
# ---------------------------------------------------------------------------

def test_weaktype_flags_weak_param_initializer():
    # the exact layerscale pattern: jnp.full of a Python float, no dtype —
    # the param flips weak→strong after one jitted step and every later
    # step call recompiles
    src = """
    import jax.numpy as jnp
    class Layer:
        def setup(self):
            self.scale = self.param("scale", lambda k: jnp.full((1, 4), 1e-5))
    """
    found = lint_source("weak-type-promotion", src)
    assert len(found) == 1 and "WEAK-typed" in found[0].message


def test_weaktype_flags_named_initializer_function():
    src = """
    import jax.numpy as jnp
    def init(key):
        return jnp.array(0.5)
    class Layer:
        def setup(self):
            self.gate = self.param("gate", init)
    """
    found = lint_source("weak-type-promotion", src)
    assert len(found) == 1 and "jnp.array" in found[0].message


def test_weaktype_flags_scalar_name_fill_in_full():
    # the layerscale shape: the fill rides a local scalar variable
    src = """
    import jax.numpy as jnp
    def init_eps(i):
        return 0.1
    class Layer:
        def setup(self):
            eps = init_eps(self.index)
            self.scale = self.param("scale",
                                    lambda k: jnp.full((1, 4), eps))
    """
    found = lint_source("weak-type-promotion", src)
    assert len(found) == 1 and "jnp.full" in found[0].message


def test_weaktype_param_initializer_clean_cases():
    # explicit dtype (kw or positional), list-literal fill (strong), a
    # strong numpy-scalar fill (Call), and asarray of a loaded ndarray
    # (Name: routinely strong-typed) must all stay silent
    src = """
    import numpy as np
    import jax.numpy as jnp
    pretrained = np.ones((4,), np.float32)
    class Layer:
        def setup(self):
            self.a = self.param("a", lambda k: jnp.full((4,), 1.0, jnp.float32))
            self.b = self.param("b", lambda k: jnp.full((4,), 2.0,
                                                        jnp.bfloat16))
            self.c = self.param("c", lambda k: jnp.array([1.0, 2.0]))
            self.d = self.param("d", lambda k: jnp.asarray(3.0,
                                                           dtype=jnp.float32))
            self.e = self.param("e", lambda k: jnp.asarray(pretrained))
            self.f = self.param("f", lambda k: jnp.full((4,),
                                                        np.float32(1.0)))
    """
    assert lint_source("weak-type-promotion", src) == []


def test_weaktype_flags_numpy_scalar_in_jitted_arithmetic():
    src = """
    import jax
    import numpy as np
    @jax.jit
    def f(x):
        return x * np.float32(0.5)
    """
    found = lint_source("weak-type-promotion", src)
    assert len(found) == 1 and "STRONG-typed" in found[0].message


def test_weaktype_numpy_scalar_clean_cases():
    # Python literal (weak), numpy scalar OUTSIDE jit, and np.float32 as a
    # dtype argument (not arithmetic) are all fine
    src = """
    import jax
    import numpy as np
    @jax.jit
    def f(x):
        return x * 0.5
    def g(x):
        return x * np.float32(0.5)
    @jax.jit
    def h(x):
        return x.astype(np.float32)
    """
    assert lint_source("weak-type-promotion", src) == []


# ---------------------------------------------------------------------------
# --changed-only rename following
# ---------------------------------------------------------------------------

def test_changed_files_follows_renames(tmp_path):
    import subprocess
    from dalle_tpu.analysis.core import changed_files

    def git(*args):
        subprocess.run(["git", *args], cwd=tmp_path, check=True,
                       capture_output=True)

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "old_name.py").write_text("x = 1\n" * 60)
    (tmp_path / "steady.py").write_text("y = 2\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # rename with a small edit: similarity stays high enough that
    # --name-status -M reports R<score>\told\tnew on one line
    (tmp_path / "old_name.py").rename(tmp_path / "new_name.py")
    text = (tmp_path / "new_name.py").read_text()
    (tmp_path / "new_name.py").write_text(text + "z = 3\n")
    (tmp_path / "steady.py").write_text("y = 4\n")
    git("add", "-A")
    changed = changed_files(repo_root=str(tmp_path))
    # BOTH sides of the rename: new path gets linted, old path fires
    # project-rule triggers like a deletion
    assert "new_name.py" in changed
    assert "old_name.py" in changed
    assert "steady.py" in changed


def test_changed_files_includes_untracked(tmp_path):
    import subprocess
    from dalle_tpu.analysis.core import changed_files

    def git(*args):
        subprocess.run(["git", *args], cwd=tmp_path, check=True,
                       capture_output=True)

    git("init", "-q")
    git("config", "user.email", "t@t")
    git("config", "user.name", "t")
    (tmp_path / "committed.py").write_text("x = 1\n")
    git("add", "-A")
    git("commit", "-qm", "seed")
    # a brand-new module with NO git add yet: `git diff HEAD` alone never
    # reports it, so a fresh file would sail through --changed-only unlinted
    (tmp_path / "brand_new.py").write_text("import jax\n")
    (tmp_path / "sub").mkdir()
    (tmp_path / "sub" / "nested_new.py").write_text("y = 2\n")
    changed = changed_files(repo_root=str(tmp_path))
    assert "brand_new.py" in changed
    assert "sub/nested_new.py" in changed
    # the committed, unmodified file stays out of the changed scope
    assert "committed.py" not in changed


# ---------------------------------------------------------------------------
# hardcoded-dtype
# ---------------------------------------------------------------------------

def test_hardcoded_dtype_flags_string_dtype_literal():
    src = """
    import jax.numpy as jnp
    def f(x):
        a = jnp.zeros((4,), dtype="bfloat16")
        b = jnp.zeros((4,), "float32")        # positional: same bypass
        return a + b
    """
    found = lint_source("hardcoded-dtype", src, "dalle_tpu/models/_f.py")
    assert len(found) == 2
    assert all("string literal" in f.message for f in found)


def test_hardcoded_dtype_flags_jnp_scalar_cast():
    src = """
    import jax.numpy as jnp
    def f(x):
        return x * jnp.float32(0.5)
    """
    found = lint_source("hardcoded-dtype", src, "dalle_tpu/ops/_f.py")
    assert len(found) == 1 and "STRONG-typed" in found[0].message


def test_hardcoded_dtype_module_array_creation_and_exemptions():
    src = """
    import jax.numpy as jnp
    import flax.linen as nn

    def helper():
        # float creation OUTSIDE an nn.Module: init-helper territory, exempt
        return jnp.zeros((2,), jnp.float32)

    class M(nn.Module):
        def setup(self):
            self.s = self.param("s", lambda k: jnp.full((1,), 0.1,
                                                        jnp.float32))

        def __call__(self, x, dtype=jnp.float32):
            # signature default IS the config surface, exempt
            ids = jnp.zeros((2,), jnp.int32)     # int dtype: not precision
            return x + ids.sum()
    """
    found = lint_source("hardcoded-dtype", src, "dalle_tpu/models/_f.py")
    assert len(found) == 1
    assert "jnp.full" in found[0].message and "nn.Module" in found[0].message


def test_hardcoded_dtype_suppression_and_scope():
    src = """
    import jax.numpy as jnp
    import flax.linen as nn

    class M(nn.Module):
        def setup(self):
            # deliberate f32 pin (weak-type retrace fix)
            self.s = self.param(  # graftlint: disable=hardcoded-dtype
                "s", lambda k: jnp.full((1,), 0.1, jnp.float32))
    """
    assert lint_source("hardcoded-dtype", src, "dalle_tpu/models/_f.py") == []
    # out of scope: train/ applies precision via cast_floating, not flagged
    src2 = """
    import jax.numpy as jnp
    def f(x):
        return x * jnp.float32(0.5)
    """
    assert lint_source("hardcoded-dtype", src2, "dalle_tpu/train/_f.py") == []


def test_project_rules_see_full_set_under_explicit_paths(tmp_path):
    # linting ONE file must not blind project rules to the rest of the tree
    (tmp_path / "dalle_tpu" / "ops").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (tmp_path / "dalle_tpu" / "other.py").write_text("x = 1\n")
    (tmp_path / "dalle_tpu" / "ops" / "mod.py").write_text(
        "def orphan_op():\n    pass\n")
    found = run_lint(paths=["dalle_tpu/other.py"], repo_root=str(tmp_path),
                     select=["untested-public-op"])
    assert any(f.path == "dalle_tpu/ops/mod.py" and "orphan_op" in f.message
               for f in found)


def test_untested_op_detection_on_fixtures():
    tree = ast.parse("def covered():\n    pass\n\ndef orphan():\n    pass\n"
                     "\ndef _private():\n    pass\n")
    hits = list(untested_ops({"dalle_tpu/ops/_fixture.py": tree},
                             "uses covered() somewhere"))
    assert [(h[1]) for h in hits] == ["orphan"]


# ---------------------------------------------------------------------------
# page-table-dynamic-shape
# ---------------------------------------------------------------------------

def test_paged_rule_flags_host_conversions():
    # int()/.item() on the table are a blocking sync one step away from a
    # shape or static arg — each block layout would trace its own program
    src = """
    def admit(state):
        first = int(state["pages"][0, 0])
        top = state["pages"].max().item()
        return first + top
    """
    found = lint_source("page-table-dynamic-shape", src,
                        rel_path="dalle_tpu/serve/_fixture.py")
    assert len(found) == 2
    assert all("device data" in f.message for f in found)


def test_paged_rule_flags_value_branch_and_shape_arg():
    src = """
    import jax.numpy as jnp
    def plan(pages, n):
        if pages[0, 0] >= 0:
            return jnp.zeros((pages[0, 1], n))
        while pages.min() < 0:
            n += 1
        return None
    """
    found = lint_source("page-table-dynamic-shape", src,
                        rel_path="dalle_tpu/serve/_fixture.py")
    msgs = [f.message for f in found]
    assert len(found) == 3
    assert any("`if` test" in m for m in msgs)
    assert any("`while` test" in m for m in msgs)
    assert any("shape argument" in m for m in msgs)


def test_paged_rule_clean_cases():
    # is-None engine probes, the table's OWN static shape, host mirrors
    # (_pages_host suffix), data-plane gathers, and out-of-scope paths
    # must all stay silent
    src = """
    import jax.numpy as jnp
    def bind(state, cache):
        pages = state.get("pages")
        if pages is None:
            return cache
        width = pages.shape[1]
        page = jnp.take_along_axis(pages, jnp.zeros((2, 1), jnp.int32), 1)
        return cache.replace(pages=pages), page, width
    def mirror(self, slot, blocks):
        self._pages_host[slot, :] = -1
        return int(self._pages_host[slot, 0])
    """
    assert lint_source("page-table-dynamic-shape", src,
                       rel_path="dalle_tpu/ops/_fixture.py") == []
    bad = """
    def f(pages):
        return int(pages[0, 0])
    """
    assert lint_source("page-table-dynamic-shape", bad,
                       rel_path="dalle_tpu/train/_fixture.py") == []


# ---------------------------------------------------------------------------
# the repo itself
# ---------------------------------------------------------------------------

def test_repo_is_lint_clean():
    findings = run_lint()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_codes_and_injected_positive(tmp_path, monkeypatch):
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import lint as lint_cli
    finally:
        sys.path.pop(0)
    assert lint_cli.main(["--list-rules"]) == 0
    assert lint_cli.main([os.path.join(REPO, "dalle_tpu/utils/misc.py")]) == 0
    with pytest.raises(SystemExit, match="unknown rule"):
        lint_cli.main(["--select", "broad_except"])  # typo'd name must error
    with pytest.raises(SystemExit, match="no such file"):
        lint_cli.main(["does_not_exist.py"])  # clean error, not a traceback
    # inject a positive fixture into a THROWAWAY repo root: exit flips to 1
    # without ever writing inside the real package tree. --select pins the
    # rule under test (the vmem-ceiling foreign-checkout guard would
    # otherwise make ANY foreign-root lint exit 1, proving nothing)
    (tmp_path / "dalle_tpu").mkdir()
    good = tmp_path / "dalle_tpu" / "good.py"
    good.write_text("x = 1\n")
    bad = tmp_path / "dalle_tpu" / "bad.py"
    bad.write_text("import jax\nK = jax.random.PRNGKey(0)\n")
    monkeypatch.setattr(lint_cli, "ROOT", str(tmp_path))
    assert lint_cli.main(["--select", "prng-key-reuse", str(good)]) == 0
    assert lint_cli.main(["--select", "prng-key-reuse", str(bad)]) == 1


# ---------------------------------------------------------------------------
# recompile guard (runtime half)
# ---------------------------------------------------------------------------

def test_compile_counter_counts_backend_compiles():
    from dalle_tpu.analysis.recompile_guard import install_compile_counter
    counter = install_compile_counter()
    assert counter is install_compile_counter()  # idempotent singleton
    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(37)           # unlikely shape → cold cache
    f(x)
    n1 = counter.count
    assert n1 > 0
    f(x)                         # cache hit: no new backend compiles
    assert counter.count == n1
    f(jnp.arange(38))            # new shape: recompiles
    assert counter.count > n1


@pytest.mark.recompile_budget(64)
def test_recompile_budget_marker_passes_under_budget():
    f = jax.jit(lambda x: x + 2)
    f(jnp.arange(39))


# ---------------------------------------------------------------------------
# unbounded-metric-label (rules_obs)
# ---------------------------------------------------------------------------

def test_unbounded_label_flags_trace_id_value():
    src = """
    from dalle_tpu.obs import counter_add, gauge_set
    def f(req):
        counter_add("serve.tokens_total", 1.0,
                    labels={"request": req.trace_id})
    """
    found = lint_source("unbounded-metric-label", src)
    assert len(found) == 1 and "trace_id" in found[0].message \
        and "cardinality" in found[0].message


def test_unbounded_label_sees_through_str_and_fstring():
    src = """
    from dalle_tpu.obs import gauge_set
    def f(request_id, text):
        gauge_set("a", 1.0, labels={"rid": str(request_id)})
        gauge_set("b", 2.0, labels={"t": f"p:{text}"})
    """
    found = lint_source("unbounded-metric-label", src)
    assert len(found) == 2


def test_unbounded_label_catches_positional_labels_dict():
    # labels is keyword-or-positional in counter_add/gauge_set — passing
    # the dict positionally must not evade the rule
    src = """
    from dalle_tpu.obs import counter_add
    def f(req):
        counter_add("serve.x_total", 1.0, {"rid": req.request_id})
    """
    found = lint_source("unbounded-metric-label", src)
    assert len(found) == 1 and "request_id" in found[0].message


def test_unbounded_label_clean_on_bounded_dimensions():
    # tenant / reason / window / layer_group are bounded dimensions — the
    # blessed label uses across gateway/slo/graftpulse stay legal, as does
    # a "trace_id" KEY whose value is bounded, and label-free calls
    src = """
    from dalle_tpu.obs import counter_add, gauge_set
    def f(tenant, reason, group):
        counter_add("gateway.rejected_by_total", 1.0,
                    labels={"tenant": tenant, "reason": reason})
        gauge_set("health.grad_norm", 1.0, labels={"layer_group": group})
        gauge_set("slo.burn_rate", 2.0, labels={"window": "5m"})
        gauge_set("x", 1.0, labels={"trace_id": "constant"})
        counter_add("y", 1.0)
    """
    assert lint_source("unbounded-metric-label", src) == []


def test_unbounded_label_suppression_and_scope():
    src = """
    from dalle_tpu.obs import gauge_set
    def f(trace_id):
        gauge_set("z", 1.0, labels={"rid": trace_id})  # graftlint: disable=unbounded-metric-label
    """
    assert lint_source("unbounded-metric-label", src) == []
    # tests/ are out of the lint surface entirely
    bare = """
    from dalle_tpu.obs import gauge_set
    def f(trace_id):
        gauge_set("z", 1.0, labels={"rid": trace_id})
    """
    assert lint_source("unbounded-metric-label", bare,
                       rel_path="tests/test_fixture.py") == []


# ---------------------------------------------------------------------------
# histogram-unbounded-buckets (rules_obs)
# ---------------------------------------------------------------------------

def test_histogram_buckets_flags_data_derived():
    # bounds computed at the call site: different code paths register the
    # family differently — trace.py only catches the mismatch at runtime
    src = """
    from dalle_tpu.obs import histogram_observe
    def f(latency, samples):
        histogram_observe("serve.lat_seconds", latency,
                          buckets=sorted(samples))
    """
    found = lint_source("histogram-unbounded-buckets", src)
    assert len(found) == 1 and "data-derived" in found[0].message


def test_histogram_buckets_flags_oversized_literal():
    bounds = ", ".join(str(i / 100) for i in range(1, 35))   # 34 > 32
    src = f"""
    from dalle_tpu.obs import histogram_observe
    def f(v):
        histogram_observe("serve.lat_seconds", v, buckets=({bounds}))
    """
    found = lint_source("histogram-unbounded-buckets", src)
    assert len(found) == 1 and "34 bucket bounds" in found[0].message


def test_histogram_buckets_catches_positional_arg():
    src = """
    from dalle_tpu.obs import histogram_observe
    def f(v, data):
        histogram_observe("serve.lat_seconds", v, [x for x in data])
    """
    assert len(lint_source("histogram-unbounded-buckets", src)) == 1


def test_histogram_buckets_clean_on_constants():
    # the sanctioned shapes: default bounds, explicit None, a small
    # literal, and an ALL_CAPS module constant (bare or dotted)
    src = """
    from dalle_tpu.obs import DEFAULT_BUCKETS, histogram_observe
    from dalle_tpu import obs
    MY_BOUNDS = (0.01, 0.1, 1.0)
    def f(v):
        histogram_observe("a_seconds", v)
        histogram_observe("b_seconds", v, buckets=None)
        histogram_observe("c_seconds", v, buckets=(0.01, 0.1, 1.0))
        histogram_observe("d_seconds", v, buckets=DEFAULT_BUCKETS)
        histogram_observe("e_seconds", v, buckets=MY_BOUNDS)
        histogram_observe("f_seconds", v, buckets=obs.DEFAULT_BUCKETS)
    """
    assert lint_source("histogram-unbounded-buckets", src) == []


def test_histogram_buckets_suppression():
    src = """
    from dalle_tpu.obs import histogram_observe
    def f(v, bounds):
        histogram_observe("a_seconds", v, buckets=tuple(bounds))  # graftlint: disable=histogram-unbounded-buckets
    """
    assert lint_source("histogram-unbounded-buckets", src) == []


# ---------------------------------------------------------------------------
# unguarded-distributed-io (rules_distributed)
# ---------------------------------------------------------------------------

def test_unguarded_io_flags_bare_distributed_initialize():
    src = """
    import jax
    def connect(coord, n, pid):
        jax.distributed.initialize(coordinator_address=coord,
                                   num_processes=n, process_id=pid)
    """
    found = lint_source("unguarded-distributed-io", src)
    assert len(found) == 1 and "jax.distributed.initialize" in found[0].message \
        and "retry layer" in found[0].message


def test_unguarded_io_flags_bare_orbax_mgr_calls():
    src = """
    class M:
        def save_it(self, step, args):
            self._mgr.save(step, args=args)
        def load_it(self, step, args):
            return self._mgr.restore(step, args=args)
    """
    found = lint_source("unguarded-distributed-io", src)
    assert len(found) == 2
    assert all("orbax manager" in f.message for f in found)


def test_unguarded_io_clean_when_routed_through_retry():
    # the two blessed shapes: a closure handed to with_retry (the
    # checkpoints.py/backend.py idiom) and an @retry-decorated function
    src = """
    import jax
    from dalle_tpu.utils.retry import retry, with_retry
    class M:
        def save_it(self, step, args):
            def _do_save():
                return self._mgr.save(step, args=args)
            with_retry("ckpt_save", _do_save)
    @retry("coordinator_connect", attempts=5)
    def connect(coord):
        jax.distributed.initialize(coordinator_address=coord)
    """
    assert lint_source("unguarded-distributed-io", src) == []


def test_unguarded_io_ignores_unrelated_save_restore():
    # .save()/.restore() on non-orbax receivers (figures, models) and the
    # guarded public CheckpointManager wrapper are not this rule's business
    src = """
    def f(fig, mgr, step, state):
        fig.save("out.png")
        mgr.save(step, state)       # the retried wrapper, not a raw _mgr
        mgr.restore(state)
    """
    assert lint_source("unguarded-distributed-io", src) == []


def test_unguarded_io_suppression():
    src = """
    import jax
    def once(coord):
        # preflight probe: a failure here must fail fast, not back off
        jax.distributed.initialize(coord)  # graftlint: disable=unguarded-distributed-io
    """
    assert lint_source("unguarded-distributed-io", src) == []


def test_unguarded_io_flags_bare_socket_dial():
    # the graftfleet transport edge: a raw TCP dial outside the retry
    # layer turns a replica mid-restart into a failed request
    src = """
    import socket
    def dial(host, port):
        return socket.create_connection((host, port), timeout=5.0)
    """
    found = lint_source("unguarded-distributed-io", src)
    assert len(found) == 1 \
        and "socket.create_connection" in found[0].message \
        and "retry layer" in found[0].message
    # the from-import spelling is the same dial
    bare = """
    from socket import create_connection
    def dial(host, port):
        return create_connection((host, port))
    """
    assert len(lint_source("unguarded-distributed-io", bare)) == 1


def test_unguarded_io_socket_dial_clean_when_guarded():
    # the fleet/transport.py idiom: ONE raw dial function wrapped by the
    # retry factory applied inline; everything else goes through it
    src = """
    import socket
    from dalle_tpu.utils.retry import retry
    def _connect_raw(addr, timeout=5.0):
        host, _, port = addr.rpartition(":")
        return socket.create_connection((host, int(port)), timeout=timeout)
    dial = retry("fleet_dial", attempts=4)(_connect_raw)
    """
    assert lint_source("unguarded-distributed-io", src) == []


def test_unbounded_blocking_flags_zero_arg_waits():
    # the graftward wedge lesson: a timeout-less cross-thread wait in the
    # serving control plane parks a thread a sick peer can wedge forever
    src = """
    def f(q, ev, t):
        item = q.get()
        ev.wait()
        t.join()
    """
    found = lint_source("unbounded-blocking-call", src,
                        rel_path="dalle_tpu/serve/_fixture.py")
    assert len(found) == 3
    assert all("timeout" in f.message for f in found)


def test_unbounded_blocking_clean_with_timeouts_and_dict_get():
    # bounded forms and dict lookups (positional args) are out of scope;
    # Event.wait(0.5) passes its timeout positionally — also bounded
    src = """
    def f(q, ev, t, d):
        a = q.get(timeout=1.0)
        b = ev.wait(0.5)
        t.join(timeout=2.0)
        c = d.get("key")
        e = d.get("key", None)
    """
    assert lint_source("unbounded-blocking-call", src,
                       rel_path="dalle_tpu/gateway/_fixture.py") == []


def test_unbounded_blocking_recv_needs_module_settimeout():
    bare = """
    def g(sock):
        return sock.recv(4096)
    """
    found = lint_source("unbounded-blocking-call", bare,
                        rel_path="dalle_tpu/fleet/_fixture.py")
    assert len(found) == 1 and "settimeout" in found[0].message
    # one settimeout anywhere in the module = the module manages socket
    # deadlines (the fleet/transport.py convention: the frame readers set
    # the timeout, helper recv loops inherit it)
    managed = """
    def prep(sock, timeout):
        sock.settimeout(timeout)
    def g(sock):
        return sock.recv(4096)
    """
    assert lint_source("unbounded-blocking-call", managed,
                       rel_path="dalle_tpu/fleet/_fixture.py") == []


def test_unbounded_blocking_scope_and_suppression():
    src = """
    def f(q):
        return q.get()
    """
    # only the fleet/gateway/serve control plane is in scope
    assert lint_source("unbounded-blocking-call", src,
                       rel_path="dalle_tpu/ops/_fixture.py") == []
    assert lint_source("unbounded-blocking-call", src,
                       rel_path="dalle_tpu/train/_fixture.py") == []
    suppressed = """
    def main(stop):
        # the main thread's shutdown park: waiting forever IS the intent
        stop.wait()  # graftlint: disable=unbounded-blocking-call
    """
    assert lint_source("unbounded-blocking-call", suppressed,
                       rel_path="dalle_tpu/gateway/_fixture.py") == []


def test_unguarded_io_socket_dial_suppression_and_unrelated():
    src = """
    import socket
    def probe(host, port):
        # liveness probe: one attempt IS the signal (a miss must not
        # hide behind backoff)
        return socket.create_connection((host, port))  # graftlint: disable=unguarded-distributed-io
    """
    assert lint_source("unguarded-distributed-io", src) == []
    # ONLY the stdlib socket dial spellings are the rule's business:
    # other APIs carrying the method name (asyncio, pools) manage their
    # own retries, and differently-named connection getters never matched
    clean = """
    async def g(loop, pool):
        await loop.create_connection(lambda: None, "h", 1)
        return pool.get_connection()
    """
    assert lint_source("unguarded-distributed-io", clean) == []
