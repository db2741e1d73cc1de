"""AOT-serialized engine executables: replica cold-start without retracing.

Autoscaling under a traffic spike is only real if a new replica reaches
"serving" in seconds. A fresh ``DecodeEngine`` pays trace + XLA compile for
its device programs (step scan, bulk refill window, per-row scatter-prefill,
shared-prefix refill, fixed-width prefill chunks, paged COW fork) on first
dispatch — minutes at flagship scale. This module
exports those programs ONCE (``jax.jit(...).lower(...).compile()`` +
``jax.experimental.serialize_executable``) and lets a cold replica load the
serialized executables straight into the engine
(``DecodeEngine.install_executables``): zero trace, zero compile, asserted
in CI via the backend-compile counter (scripts/gateway_smoke.py).

An executable is only valid for the exact program it was compiled from, so
the bundle carries a FINGERPRINT — model config, slot count, cache dtype,
sampling knobs, param avals, jax version, backend platform and device count
— and ``load_engine_aot`` refuses a mismatch (fall back to jit, never run a
wrong program). The fingerprinted step program is additionally pinned as
the ``serve_decode_aot`` graftir contract entry, so a refactor that changes
what the export lowers fails CI before it ships stale bundles.

Two layers of cold-start speedup compose here:

  * this module — skips trace AND compile for the engine's own programs;
  * the persistent XLA compilation cache (``enable_compilation_cache`` /
    ``scripts/_common.add_compile_cache_args``) — skips compile (not trace)
    for EVERYTHING else the process jits, across processes and restarts.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
from typing import Optional

# re-exported because the persistent cache is the second half of the
# cold-start story this module owns (docs/SERVING.md); the implementation
# is provider-neutral jax plumbing and lives with the other generic utils
# so train CLIs don't import the gateway package for it
from ..utils.misc import enable_compilation_cache  # noqa: F401

PROGRAMS = ("step", "refill", "refill_row", "refill_shared")
_BUNDLE = "programs.pkl"
_MANIFEST = "manifest.json"


def engine_programs(engine) -> tuple:
    """The full program list THIS engine configuration dispatches: the four
    base programs, plus one ``refill_chunk_w{w}`` per fixed chunk width
    (``DecodeEngine.chunk_widths`` — nonempty for chunk-on AND paged
    engines; the fixed-width set is what made chunked prefill AOT-
    exportable), plus the paged ``cow_copy`` fork program."""
    if engine.paged:
        # paged admission never dispatches the dense trickle/shared-prefix
        # programs (radix hits subsume shared prefills; staggered admission
        # goes through the fixed-width chunk programs), and their bodies
        # assume a dense slab — so paged bundles carry step + bulk refill
        # + the chunk widths + the COW fork, nothing else
        names = ["step", "refill"]
    else:
        names = list(PROGRAMS)
    names += [f"refill_chunk_w{w}" for w in engine.chunk_widths()]
    if engine.paged:
        names.append("cow_copy")
    return tuple(names)


def _aval_digest(tree) -> str:
    """Order-stable digest of a pytree's (path, shape, dtype) leaves — the
    part of the fingerprint that catches a changed param tree (different
    depth/width/quantization) without hashing gigabytes of weights."""
    import jax
    rows = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        rows.append((jax.tree_util.keystr(path), tuple(leaf.shape),
                     str(leaf.dtype)))
    return hashlib.sha256(repr(sorted(rows)).encode()).hexdigest()


def engine_fingerprint(engine) -> dict:
    """Everything that determines the engine's compiled programs. Two
    engines with equal fingerprints compile byte-identical programs; a
    bundle loads iff fingerprints match exactly."""
    import jax
    return {
        "jax_version": jax.__version__,
        "platform": jax.devices()[0].platform,
        "device_count": jax.device_count(),
        "model_cfg": engine.model.cfg.to_dict(),
        "slots": engine.slots,
        "cache_dtype": str(engine.cache_dtype.__name__
                           if hasattr(engine.cache_dtype, "__name__")
                           else engine.cache_dtype),
        "steps_per_sync": engine.steps_per_sync,
        "filter_thres": engine.filter_thres,
        "temperature": engine.temperature,
        "topk_approx": engine.topk_approx,
        "use_kernel": engine.use_kernel,
        # program-shaping: the graftpulse taps change the step program's
        # outputs, so a bundle exported without them must not load into an
        # engine expecting them (and vice versa). Pre-graftpulse bundles
        # lack the key entirely → mismatch → loud jit fallback.
        "decode_health": engine.decode_health,
        # graftloom/graftpage: chunked prefill decomposes into a FIXED
        # width set (``chunk_widths()``), one serialized program per width,
        # so chunk-on and paged engines export like any other — but the
        # width set (hence the bundle's program list) is shaped by these
        # knobs, and a bundle built for different ones must not load.
        # Pre-graftloom bundles lack refill_shared, pre-graftpage ones lack
        # the kv keys — both mismatch loudly rather than fail at dispatch.
        "prefill_chunk": engine.prefill_chunk,
        "kv_block_tokens": engine.kv_block_tokens,
        "kv_pool_blocks": engine.kv_pool_blocks,
        "param_avals": _aval_digest(engine.params),
    }


def _program_args(engine):
    """Abstract (ShapeDtypeStruct) call signatures for the engine programs —
    the avals the host loop passes at every dispatch. Built via
    ``jax.eval_shape`` so export never allocates a second KV cache."""
    import jax
    import jax.numpy as jnp
    params = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype), engine.params)
    state = jax.eval_shape(engine._init_state)
    B, T = engine.slots, engine.text_seq_len
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    boo = lambda *s: jax.ShapeDtypeStruct(s, jnp.bool_)  # noqa: E731
    args = {
        "step": (params, state),
        "refill": (params, state, i32(B, T), i32(B), i32(B), boo(B)),
        "refill_row": (params, state, i32(1, T), i32(), i32(), i32()),
        "refill_shared": (params, state, i32(1, T), i32(B), i32(B), boo(B)),
    }
    for w in engine.chunk_widths():
        # (params, state, ids_chunk, start, seeds, n_rows, mask, last) —
        # start/last are traced scalars so one program per WIDTH covers
        # every chunk position of that width
        args[f"refill_chunk_w{w}"] = (params, state, i32(B, w), i32(),
                                      i32(B), i32(B), boo(B), boo())
    if engine.paged:
        args["cow_copy"] = (state, i32(B), i32(B))
    return args


def step_lowering(engine):
    """The exact lowering the export serializes for the decode-step scan —
    exposed so the graftir ``serve_decode_aot`` entry pins the same program
    this module ships (analysis/contracts.py)."""
    return engine._step_fn.lower(*_program_args(engine)["step"])


def save_engine_aot(engine, out_dir: str) -> dict:
    """Compile and serialize the engine's three device programs into
    ``out_dir`` (``programs.pkl`` + ``manifest.json``). Returns the
    manifest. Run this on ANY machine with the target topology (the
    exporter pays the compile, cold replicas don't)."""
    from jax.experimental.serialize_executable import serialize
    if engine.aot_loaded:
        # a loaded executable can't be re-lowered; exporting must start
        # from a jit engine so the bundle is compiled fresh for this config
        raise ValueError("cannot export from an AOT-loaded engine; build a "
                         "fresh DecodeEngine and export that")
    os.makedirs(out_dir, exist_ok=True)
    args = _program_args(engine)
    programs = engine_programs(engine)
    fns = {"step": engine._step_fn, "refill": engine._refill_fn,
           "refill_row": engine._refill_row_fn,
           "refill_shared": engine._refill_shared_fn}
    for w in engine.chunk_widths():
        # the chunk program is ONE jit function; each fixed width lowers to
        # its own executable (graftloom's width-dynamic dispatch is exactly
        # the set chunk_widths() enumerates, so the bundle covers every
        # window the admission path can ever issue)
        fns[f"refill_chunk_w{w}"] = engine._refill_chunk_fn
    if engine.paged:
        fns["cow_copy"] = engine._cow_copy_fn
    bundle = {}
    for name in programs:
        # The export gets executables of its own. Compiled with no options,
        # jit's in-memory cache hands back the very executable this process
        # may already have run, and one that has run does not always
        # serialize (XLA:CPU swaps in sort comparators that cannot:
        # "UNIMPLEMENTED: `LessThan` is not serializable"). Naming an option
        # — here at its default value — keys a separate compile.
        compiled = fns[name].lower(*args[name]).compile(
            compiler_options={"xla_embed_ir_in_executable": False})
        payload, in_tree, out_tree = serialize(compiled)
        bundle[name] = (payload, in_tree, out_tree)
    manifest = {"fingerprint": engine_fingerprint(engine),
                "programs": list(programs),
                "payload_bytes": {n: len(bundle[n][0]) for n in programs}}
    with open(os.path.join(out_dir, _BUNDLE), "wb") as fh:
        pickle.dump(bundle, fh)
    tmp = os.path.join(out_dir, _MANIFEST + ".tmp")
    with open(tmp, "w") as fh:
        json.dump(manifest, fh, indent=2)
    os.replace(tmp, os.path.join(out_dir, _MANIFEST))
    return manifest


def fingerprint_mismatch(engine, aot_dir: str) -> Optional[str]:
    """None when the bundle under ``aot_dir`` matches ``engine``; otherwise
    a human-readable first-divergence description (missing bundle counts)."""
    path = os.path.join(aot_dir, _MANIFEST)
    if not os.path.exists(path):
        return f"no AOT manifest at {path}"
    with open(path) as fh:
        saved = json.load(fh).get("fingerprint", {})
    live = engine_fingerprint(engine)
    for key in sorted(set(saved) | set(live)):
        if saved.get(key) != live.get(key):
            return (f"fingerprint mismatch on {key!r}: "
                    f"bundle={saved.get(key)!r} engine={live.get(key)!r}")
    return None


def load_engine_aot(engine, aot_dir: str, *, strict: bool = False) -> bool:
    """Install the serialized executables from ``aot_dir`` into ``engine``.
    Returns True on success; on fingerprint mismatch returns False (the
    engine keeps its jit path — correct, just cold) or raises when
    ``strict``. Loading performs NO trace and NO backend compile — the
    gateway smoke pins that with a compile-counter delta of zero across a
    served request."""
    import jax
    from jax.experimental.serialize_executable import deserialize_and_load
    from ..obs import counter_add
    reason = fingerprint_mismatch(engine, aot_dir)
    if reason is not None:
        if strict:
            raise ValueError(f"refusing AOT bundle {aot_dir}: {reason}")
        # fall back to jit loudly: a silently-cold replica looks healthy
        # but pays the full retrace — the one thing the operator deployed
        # the bundle to avoid (classic cause: --aot_export run with
        # different fleet flags than serving, e.g. --slots)
        import warnings
        warnings.warn(f"AOT bundle {aot_dir} refused ({reason}); "
                      "falling back to jit (cold start pays full "
                      "trace+compile)", stacklevel=2)
        counter_add("gateway.aot_miss_total", 1.0)
        return False
    with open(os.path.join(aot_dir, _BUNDLE), "rb") as fh:
        bundle = pickle.load(fh)
    programs = engine_programs(engine)
    missing = [n for n in programs if n not in bundle]
    if missing:
        # a matching fingerprint with missing programs means a truncated or
        # hand-edited bundle — treat like a mismatch, never half-install
        if strict:
            raise ValueError(f"AOT bundle {aot_dir} lacks programs "
                             f"{missing}")
        import warnings
        warnings.warn(f"AOT bundle {aot_dir} lacks programs {missing}; "
                      "falling back to jit", stacklevel=2)
        counter_add("gateway.aot_miss_total", 1.0)
        return False
    # onto the engine's own device(s): left to its default the loader
    # spreads a one-device program over every local device, and the first
    # dispatch fails on the shard count
    leaf = jax.tree.leaves(engine.params)[0]
    devices = sorted(leaf.sharding.device_set, key=lambda d: d.id)
    loaded = {name: deserialize_and_load(*bundle[name],
                                         execution_devices=devices)
              for name in programs}
    chunks = {w: loaded[f"refill_chunk_w{w}"]
              for w in engine.chunk_widths()} or None
    engine.install_executables(step=loaded["step"], refill=loaded["refill"],
                               refill_row=loaded.get("refill_row"),
                               refill_shared=loaded.get("refill_shared"),
                               refill_chunks=chunks,
                               cow_copy=loaded.get("cow_copy"))
    counter_add("gateway.aot_load_total", 1.0)
    return True


