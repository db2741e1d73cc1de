"""Device time by the program's own scopes, program side (obs/device.py
``capture_program`` / ``program_scopes`` / ``scope_layer``, ``fit()``'s
``warmup/scopes``, the default block's scopes): what the CPU can say. The
benchmark's readers are tested in tests/benchmarks/test_bench_scopes.py."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu import obs
from dalle_tpu.config import DalleConfig, MeshConfig, TrainConfig
from dalle_tpu.obs import device as obs_device
from dalle_tpu.obs.device import (ScopeTable, parse_scopes, program_scopes,
                                  scope_layer)
from dalle_tpu.parallel.mesh import build_mesh
from dalle_tpu.train.trainer_dalle import DalleTrainer

STEP = "jit(step)/"
FWD = STEP + "jvp(forward)/DALLE/transformer/transformer._block_body/"
BWD = (STEP + "transpose(jvp(forward))/DALLE/transformer/jvp(forward)/DALLE/"
       "transformer/checkpoint/transformer._block_body/")
REMAT = BWD.replace("checkpoint/", "checkpoint/rematted_computation/")


@pytest.mark.parametrize("path, expected", [
    # forward, backward, recompute, update
    (FWD + "layer_ff_0/ff/ff_0/w1/dot_general", ("ff", "fwd")),
    (BWD + "layer_ff_0/ff/ff_0/w1/dot_general", ("ff", "bwd")),
    (REMAT + "layer_ff_0/ff/ff_0/w1/dot_general", ("ff", "remat")),
    (STEP + "optimizer/mul", ("optimizer", "update")),
    (STEP + "clip/jit(_where)/select_n", ("optimizer", "update")),
    # two-part scopes are two whole components in a row
    (FWD + "layer_attn_1/attn_1/attn/kda_chunk/while/body/closed_call/"
     "checkpoint/dot_general", ("kda_chunk", "fwd")),
    (BWD + "layer_attn_1/attn_1/attn/kda_chunk/while/body/closed_call/"
     "checkpoint/rematted_computation/mul", ("kda_chunk", "remat")),
    (BWD + "layer_attn_1/attn_1/attn/kda_state/while/body/mul",
     ("kda_state", "bwd")),
    (FWD + "layer_attn_0/attn_0/attn/qkv/to_qkv/dot_general",
     ("attn_proj", "fwd")),
    (FWD + "layer_attn_0/attn_0/attn/mla_norm/mul", ("attn_proj", "fwd")),
    (FWD + "layer_attn_1/attn_1/checkpoint/attn_1._mixed/attn/kda_conv/"
     "jit(silu)/logistic", ("attn_proj", "fwd")),
    (FWD + "layer_attn_0/attn_0/attn_core/flash_attn_fwd",
     ("attn_core", "fwd")),
    (FWD + "layer_ff_1/ff_1/moe/experts/moe_gmm_fwd", ("moe", "fwd")),
    (STEP + "transpose(jvp(forward))/DALLE/embed/grad/dot_general",
     ("embed", "bwd")),
    (STEP + "jvp(forward)/DALLE/loss/DALLE._ce_segment/final_norm/mul",
     ("loss", "fwd")),
    (STEP + "jvp(forward)/DALLE/mtp/block/transformer._block_body/"
     "layer_attn_0/attn_0/attn_core/dot_general", ("attn_core", "fwd")),
    (STEP + "jvp(forward)/DALLE/loss/mtp/DALLE._ce_segment/dot_general",
     ("loss", "fwd")),
    # the innermost scope wins: a shared expert is the routed layer's, an
    # MLP of the dense layers' class or not
    (FWD + "layer_ff_1/ff_1/moe/shared/shared/w_gate/dot_general",
     ("moe", "fwd")),
    (FWD + "layer_ff_1/ff_1/moe/combine/while/body/scatter-add",
     ("moe", "fwd")),
    # under `forward` and no layer's scope: norms, residuals, the tables
    (FWD + "layer_ff_0/norm/mul", ("other", "fwd")),
    (STEP + "jvp(forward)/DALLE/mtp/merge/concatenate", ("other", "fwd")),
    (BWD + "add_any", ("other", "bwd")),
    # a flax module's name is not a scope: attn_2 is not attn, ff_0 not ff
    (FWD + "layer_attn_2/attn_2/kda_chunk/mul", ("other", "fwd")),
    (STEP + "layer_ff_0/ff_0/w1/dot_general", ("unscoped", "fwd")),
    (STEP + "jit(loss)/mul", ("unscoped", "fwd")),
    # no scope, no path
    (STEP + "convert_element_type", ("unscoped", "fwd")),
    ("reduce_sum", ("unscoped", "fwd")),
    ("", ("unscoped", "fwd")),
    (None, ("unscoped", "fwd")),
])
def test_scope_layer_reads_the_programs_vocabulary(path, expected):
    assert scope_layer(path) == expected


HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.4 (param_0.1: f32[8,4]) -> f32[8,4] {
  %param_0.1 = f32[8,4]{1,0} parameter(0)
  ROOT %mul.7 = f32[8,4]{1,0} multiply(%param_0.1, %param_0.1), metadata={op_name="jit(step)/jvp(forward)/DALLE/loss/mul" stack_frame_id=3}
}

%body.1 (arg: (s32[], f32[8,4])) -> (s32[], f32[8,4]) {
  %arg = (s32[], f32[8,4]{1,0}) parameter(0)
  %get-tuple-element.5 = f32[8,4]{1,0} get-tuple-element(%arg), index=1
  %copy.9 = f32[8,4]{1,0} copy(%get-tuple-element.5)
  %fusion.7 = f32[8,4]{1,0} fusion(%copy.9), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(step)/jvp(forward)/DALLE/loss/mul;jit(step)/optimizer/add" stack_frame_id=3}
  %copy.10 = f32[8,4]{1,0} copy(%fusion.7)
  %bitcast.2 = f32[4,8]{1,0} bitcast(f32[8,4]{1,0} %copy.10)
  ROOT %tuple.3 = (s32[], f32[8,4]{1,0}) tuple(%get-tuple-element.5, %copy.10)
}

ENTRY %main.686 (p: f32[8,4]) -> f32[8,4] {
  %p = f32[8,4]{1,0} parameter(0), metadata={op_name="state.params"}
  %while.54 = (s32[], /*index=1*/f32[8,4]{1,0}) while(%tuple.0), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/optimizer/while" stack_frame_id=9}
  %flash_attn_fwd.1 = bf16[8,4]{1,0} custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/jvp(forward)/DALLE/attn_core/flash_attn_fwd" stack_frame_id=4}
  ROOT %copy.11 = f32[8,4]{1,0} copy(%constant.1)
}
"""


def test_parse_scopes_reads_names_paths_and_inherits_along_a_chain():
    table = parse_scopes(HLO)
    assert table["fusion.7"] == "jit(step)/jvp(forward)/DALLE/loss/mul"
    assert table["while.54"] == "jit(step)/optimizer/while"
    assert table["flash_attn_fwd.1"].endswith("attn_core/flash_attn_fwd")
    assert table["mul.7"].endswith("loss/mul")
    # a copy of an operation takes its path, and a bitcast of that copy too
    # (an operand printed with its shape in front is still the operand);
    # a computation named by an attribute is never taken for an operand
    assert table["copy.10"] == table["bitcast.2"] == table["fusion.7"]
    assert table.inherited == {"copy.10", "bitcast.2"}
    # no path to inherit: a tuple element of a loop's state, a constant
    for bare in ("copy.9", "get-tuple-element.5", "copy.11", "tuple.3",
                 "param_0.1"):
        assert bare not in table
    assert isinstance(table, dict) and isinstance(table, ScopeTable)


def test_a_table_says_which_of_the_sources_layers_its_executable_lacks(
        monkeypatch):
    """A step loaded from a compile cache's entry that an older version of
    the program wrote carries that version's names (the key strips
    locations): the table holds the scopes of the traced source against the
    executable's."""
    source = {"jit(step)/jvp(forward)/DALLE/ff/ff_0/dot_general",
              "jit(step)/jvp(forward)/DALLE/loss/mul",
              "jit(step)/optimizer/add", "jit(step)/convert_element_type"}
    monkeypatch.setitem(obs_device._programs, "older/step", (HLO, source))
    table = program_scopes("older/step")
    assert table.unseen == {"ff"}
    assert program_scopes("older/step") is table


def _walk(jaxpr, prefix=""):
    """(primitive name, whole name stack) of every equation, through every
    nested jaxpr: an inner equation's stack is relative to its caller's."""
    for eqn in jaxpr.eqns:
        stack = "/".join(s for s in (prefix, str(eqn.source_info.name_stack))
                         if s)
        yield eqn.primitive.name, stack
        for value in eqn.params.values():
            for inner in (value if isinstance(value, (list, tuple))
                          else (value,)):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner, stack)


@pytest.mark.parametrize("tier, n", [("dense", 32), ("fused", 128),
                                     ("flash", 512)])
def test_every_tiers_core_runs_under_attn_core(tier, n):
    """By the jaxpr of ``Attention(tier=...)``: the projections are
    ``attn/qkv`` and ``attn/out``, and every product or kernel call besides
    them is the core's."""
    from dalle_tpu.models.transformer import Attention
    module = Attention(dim=64, heads=2, dim_head=64, tier=tier)
    x = jnp.zeros((1, n, 64), jnp.bfloat16)
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    jaxpr = jax.make_jaxpr(lambda p, x: module.apply(p, x))(params, x)
    found = {}
    for primitive, stack in _walk(jaxpr.jaxpr):
        if primitive in ("dot_general", "pallas_call"):
            found.setdefault(primitive, []).append(stack)
    kernels = found.get("pallas_call", [])
    assert bool(kernels) == (tier != "dense")
    assert all(scope_layer(s)[0] == "attn_core" for s in kernels), kernels
    products = found.get("dot_general", [])
    projections = [s for s in products if "/to_qkv" in s or "/to_out" in s]
    assert len(projections) == 2
    assert all(scope_layer(s)[0] == "attn_proj" for s in projections)
    assert any("attn/qkv" in s for s in projections)
    assert any("attn/out" in s for s in projections)
    # the dense core's own products, or those inside a kernel's body
    core = [s for s in products if s not in projections]
    assert core
    assert all(scope_layer(s)[0] == "attn_core" for s in core), core


def _solar_tiny() -> DalleConfig:
    path = os.path.join(os.path.dirname(__file__), "benchmarks", "data",
                        "tiny_solar2_config.json")
    with open(path) as f:
        return DalleConfig(**json.load(f)["model"])


TINY = DalleConfig(num_text_tokens=32, text_seq_len=8, dim=32, depth=2,
                   heads=2, dim_head=16, image_size=16, image_vocab_size=32,
                   image_fmap_size=4)


def _fit(model_cfg, tmp_path, steps=2, log=print):
    """A fresh trainer's fit() over ``steps`` batches; returns the backend
    compiles the fit() call itself made."""
    tc = TrainConfig(batch_size=2, checkpoint_dir=str(tmp_path),
                     preflight_checkpoint=False, save_every_steps=0,
                     mesh=MeshConfig())
    trainer = DalleTrainer(model_cfg, tc, mesh=build_mesh(
        MeshConfig(), devices=jax.devices()[:1]))
    rng = np.random.RandomState(0)
    batches = [(rng.randint(1, model_cfg.num_text_tokens,
                            (2, model_cfg.text_seq_len)),
                rng.randint(0, model_cfg.image_vocab_size,
                            (2, model_cfg.image_seq_len)))
               for _ in range(steps)]
    counter = obs_device.install_compile_counter()
    before = counter.count
    trainer.fit(iter(batches), log=log)
    assert trainer._host_step == steps
    return counter.count - before


@pytest.mark.parametrize("model_cfg, layers", [
    (TINY, {"optimizer", "loss", "attn_core", "attn_proj", "ff", "other"}),
    (_solar_tiny(), {"optimizer", "loss", "attn_core", "attn_proj",
                     "kda_chunk", "kda_state", "moe", "other"}),
], ids=["default_block", "solar_block"])
def test_fit_keeps_its_steps_table_and_compiles_nothing_for_it(
        model_cfg, layers, tmp_path, monkeypatch):
    obs_device._programs.pop("train/step", None)
    obs.reset_phase_totals()
    jax.clear_caches()
    with_capture = _fit(model_cfg, tmp_path / "a")
    assert obs.phase_totals()["warmup/scopes"][0] == 1
    assert isinstance(obs_device._programs["train/step"], tuple)  # not parsed
    table = program_scopes("train/step")
    assert table and program_scopes("train/step") is table       # text gone
    assert table.unseen == frozenset()    # the executable is this source's
    cells = {scope_layer(path) for path in table.values()}
    assert layers <= {layer for layer, _ in cells}
    assert {"fwd", "bwd", "update"} <= {phase for _, phase in cells}
    if model_cfg is not TINY:      # nn.remat per layer
        assert ("kda_chunk", "remat") in cells
    # the same fit() with the capture stubbed out compiles as many programs
    monkeypatch.setattr("dalle_tpu.train.base_trainer.capture_program",
                        lambda *a, **kw: False)
    jax.clear_caches()
    assert _fit(model_cfg, tmp_path / "b") == with_capture


def test_a_capture_that_fails_leaves_none_and_fit_runs_on(tmp_path,
                                                          monkeypatch):
    said = []
    obs_device._capture_failures_said.discard("train/step")

    def broken(self):
        raise RuntimeError("no text today")
    monkeypatch.setattr(jax.stages.Compiled, "runtime_executable", broken)
    _fit(TINY, tmp_path, log=said.append)
    assert program_scopes("train/step") is None
    assert sum("no scope table for 'train/step'" in m for m in said) == 1
    _fit(TINY, tmp_path, log=said.append)        # said once a process
    assert sum("no scope table" in m for m in said) == 1


def test_a_lowering_without_its_executable_is_skipped_not_compiled():
    """``capture_program`` on a function that never ran: its lowering holds
    no executable, and asking for one would compile."""
    def scoped(x):
        with jax.named_scope("loss"):
            return jnp.sum(jnp.tanh(x @ x.T)) * 3 + 1

    said = []
    fresh = jax.jit(scoped)
    x = jnp.ones((4, 4))
    counter = obs_device.install_compile_counter()
    before = counter.count
    assert not obs_device.capture_program("never/ran", fresh, x,
                                          log=said.append)
    assert counter.count == before
    assert program_scopes("never/ran") is None and len(said) == 1
    fresh(x)
    assert obs_device.capture_program("never/ran", fresh, x, log=said.append)
    table = program_scopes("never/ran")
    assert "loss" in {scope_layer(p)[0] for p in table.values()}
    # the trimmed print the capture takes reads as the default one does
    whole = parse_scopes(fresh.lower(x).compile().as_text())
    assert table == whole and table.inherited == whole.inherited


@pytest.mark.parametrize("held", [True, False], ids=["table", "no_table"])
def test_a_sigusr2_capture_gets_the_steps_table_beside_it(held, monkeypatch,
                                                          tmp_path):
    """``install_sigusr2_profiler`` writes ``program_scopes.json``
    (instruction -> [layer, phase, op_name]) into each capture's directory
    when the process holds a table of its step, and nothing without one."""
    import importlib.util
    import signal
    import time
    import types
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "_common.py")
    spec = importlib.util.spec_from_file_location("_common_scopes", path)
    common = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(common)
    stopped = []
    monkeypatch.setattr(jax.profiler, "start_trace", lambda path: None)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stopped.append(1))
    monkeypatch.setitem(obs_device._programs, "train/step",
                        parse_scopes(HLO) if held else None)
    prev = signal.getsignal(signal.SIGUSR2)
    try:
        args = types.SimpleNamespace(profiler_dir=None,
                                     profiler_capture_s=0.05)
        assert common.install_sigusr2_profiler(str(tmp_path), args)
        signal.getsignal(signal.SIGUSR2)(signal.SIGUSR2, None)
        (capture,) = os.listdir(tmp_path)
        written = os.path.join(tmp_path, capture, "program_scopes.json")
        # the timer thread stops the trace, then writes the file
        deadline = time.time() + 5.0
        while time.time() < deadline and not (
                stopped and (os.path.exists(written) or not held)):
            time.sleep(0.01)
        time.sleep(0.1)
    finally:
        signal.signal(signal.SIGUSR2, prev)
    assert stopped and os.path.exists(written) == held
    if held:
        with open(written) as f:
            table = json.load(f)
        assert table["fusion.7"] == [
            "loss", "fwd", "jit(step)/jvp(forward)/DALLE/loss/mul"]
        assert table["while.54"][:2] == ["optimizer", "update"]


def test_a_step_loaded_from_an_older_cache_entry_is_told_apart(tmp_path):
    """jax keys its persistent compile cache on a program stripped of its
    locations, so a change of scopes alone finds the older entry and the
    loaded executable carries the older names: the one cache file serves
    both versions, and ``ScopeTable.unseen`` names the layer the source
    scopes and the executable does not."""
    from jax.experimental.compilation_cache import compilation_cache

    def make(scope):
        def step(x):
            with jax.named_scope("forward"), jax.named_scope(scope):
                return jnp.tanh(x @ x.T).sum()
        return jax.jit(step)

    x = jnp.ones((64, 64))
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    try:
        for name, value in zip(names, (str(tmp_path), 0, 0)):
            jax.config.update(name, value)
        compilation_cache.reset_cache()
        make("attn_core")(x)                       # the older version writes
        entries = [f for f in os.listdir(tmp_path) if f.startswith("jit_step")]
        assert len(entries) == 1
        jax.clear_caches()
        newer = make("ff")
        newer(x)                                   # the newer one loads
        assert [f for f in os.listdir(tmp_path)
                if f.startswith("jit_step")] == entries
        assert obs_device.capture_program("cached/step", newer, x)
    finally:
        for name, value in before.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()
    table = program_scopes("cached/step")
    assert table.unseen == {"ff"}
    assert "attn_core" in {scope_layer(p)[0] for p in table.values()}
