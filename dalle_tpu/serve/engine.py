"""Slot-based continuous-batching decode engine (Orca-style iteration-level
scheduling, Yu et al., OSDI '22 — adapted to static-shape TPU serving).

A fixed device batch of B decode slots shares one KV cache. Each slot
carries its own prompt, per-row cache offset, per-row length and RNG lane —
all (B,)-shaped device arrays, so rows at ragged positions ride one
compiled program and admission never recompiles. When a row emits its last
image token it is refilled from the host-side ``RequestQueue`` on the very
next iteration by prefilling the new prompt at that row's offset in one
multi-row window (``DALLE.serve_refill``); the other rows keep decoding —
no drain, no batch re-formation.

Two jitted device programs, compiled once per engine:

  * ``refill(params, state, texts, seeds, n_rows, mask)`` — admission
    prefill for the masked rows, with per-row decode lengths (parked rows'
    cache writes drop out of bounds).
  * ``step(params, state)`` — sample one token per slot under the per-row
    key discipline, then decode it at per-row offsets
    (``DALLE.serve_decode`` → ``transformer.decode_window`` →
    ``cached_attend_window``, which self-selects the windowed Pallas
    kernel on TPU).

Correctness bar (tests/test_serve.py, scripts/serve_smoke.py): each
request's tokens are BIT-EXACT against single-request
``generate_images_tokens(text[None], PRNGKey(seed))`` for any admission
order — the engine replicates the sequential path's split-chain key
discipline per row and keeps every reduction width identical (cache
max_seq == total_seq_len).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..chaos.faults import step_hook as chaos_step_hook
from ..models.dalle import DALLE
from ..obs import (counter_add, gauge_set, histogram_observe, record_event,
                   record_span, register_state_provider,
                   unregister_state_provider)
from ..ops.sampling import gumbel_sample_rows
from .paged import BlockPool, RadixCache
from .queue import CompletedRequest, Request, RequestQueue
from .scheduler import SlotScheduler


@dataclasses.dataclass
class EngineStats:
    steps: int = 0
    refills: int = 0
    # shared-prefix admissions (graftloom): cohorts of one group admitted
    # together pay ONE text prefill; ``shared_prefills_saved`` counts the
    # (N−1) per cohort the independent path would have paid — the
    # amortization ledger serve_bench reports against
    shared_refills: int = 0
    shared_prefills_saved: int = 0
    # chunked-prefill dispatches (prefill_chunk > 0): windows split into
    # bounded chunks interleaved with decode iterations
    prefill_chunks: int = 0
    # running mean of occupancy at iterations where the queue still held
    # work — the ≥90% serving bar only means something while there IS work.
    # Sum/count (not a sample list) so a long-lived serve loop stays O(1).
    occupancy_sum: float = 0.0
    occupancy_n: int = 0
    # paged-KV ledger (graftpage): radix prefix-cache outcomes, COW forks
    # and LRU evictions of the block pool. ``prefix_hit_tokens`` counts the
    # prompt positions admission mapped from resident blocks instead of
    # recomputing — the prefill compute the radix cache saved, in tokens.
    radix_full_hits: int = 0
    radix_partial_hits: int = 0
    radix_misses: int = 0
    prefix_hit_tokens: int = 0
    cow_forks: int = 0
    pages_evicted: int = 0
    # request ids still mid-decode when a max_steps bound tripped — they
    # were consumed from the queue and will never complete (empty on drain)
    aborted_in_flight: List[int] = dataclasses.field(default_factory=list)

    def sample_occupancy(self, value: float) -> None:
        self.occupancy_sum += float(value)
        self.occupancy_n += 1

    @property
    def progress(self) -> int:
        """Monotonic engine-iteration counter (graftward): every device
        dispatch the host loop completes — decode steps, refill windows,
        prefill chunks — advances it. A BUSY engine whose progress freezes
        is wedged; an idle one is just idle. Read cross-thread by the
        in-process :class:`~dalle_tpu.degrade.WedgeWatchdog`, the health
        verb, and (remotely) the fleet transport's frozen-progress
        check."""
        return self.steps + self.refills + self.prefill_chunks

    @property
    def occupancy_while_queued(self) -> float:
        if not self.occupancy_n:
            return 1.0
        return self.occupancy_sum / self.occupancy_n


# jitted program sharing across engines (the PR 5 jit_step precedent,
# serve-side): two engines over the SAME model object with equal program
# config compile byte-identical programs, so a replica fleet on one host —
# and every test building engines off one module fixture — should pay
# trace+compile ONCE, not once per engine. Keyed by id(model) + the
# program-shaping knobs; params stay CALL arguments, so f32/bf16/int8 param
# trees ride one cache entry via jax's own per-aval retrace. The cached
# closures bind a lightweight STAND-IN (the program-shaping attrs + model,
# nothing else) rather than the first engine — binding the engine would pin
# its whole param tree for the life of the process (GBs stranded on every
# checkpoint hot-swap). The stand-in pins the model, so id(model) keys
# never go stale; the cache is process-lifetime by design, bounded by
# distinct (model, config) pairs.
_PROGRAMS: Dict[int, Dict[tuple, tuple]] = {}

# every attribute the traced program bodies (_refill/_refill_row/_step/
# _multi_step) read off self — the stand-in carries exactly these
_PROGRAM_ATTRS = ("model", "use_kernel", "cache_dtype", "n_steps",
                  "filter_thres", "temperature", "topk_approx",
                  "num_text_tokens", "prefix_len", "park", "steps_per_sync",
                  "decode_health")


def _program_key(eng: "DecodeEngine") -> tuple:
    return (eng.slots, np.dtype(eng.cache_dtype).name, eng.filter_thres,
            eng.temperature, eng.topk_approx, eng.steps_per_sync,
            eng.use_kernel, eng.decode_health)


def _shared_programs(eng: "DecodeEngine") -> tuple:
    import types
    per_model = _PROGRAMS.setdefault(id(eng.model), {})
    key = _program_key(eng)
    fns = per_model.get(key)
    if fns is None:
        standin = types.SimpleNamespace(
            **{a: getattr(eng, a) for a in _PROGRAM_ATTRS})
        standin._step = DecodeEngine._step.__get__(standin)
        fns = (jax.jit(DecodeEngine._refill.__get__(standin),
                       donate_argnums=(1,)),
               jax.jit(DecodeEngine._refill_row.__get__(standin),
                       donate_argnums=(1,)),
               jax.jit(DecodeEngine._refill_shared.__get__(standin),
                       donate_argnums=(1,)),
               jax.jit(DecodeEngine._refill_chunk.__get__(standin),
                       donate_argnums=(1,)),
               jax.jit(DecodeEngine._multi_step.__get__(standin),
                       donate_argnums=(1,)),
               jax.jit(DecodeEngine._cow_copy.__get__(standin),
                       donate_argnums=(0,)))
        per_model[key] = fns
    return fns


# -- paged-state plumbing (graftpage) ---------------------------------------
# The page table is ONE state leaf (``state["pages"]``), bound into every
# layer's PagedKVCache inside the traced program bodies and stripped before
# the state is returned: a per-layer pages field would make donation alias
# the same buffer ``depth`` times, and the host would have to upload depth
# copies per admission instead of one. Dense engines have no "pages" key and
# both helpers are identity on their cache.

def _bind_cache(state):
    pages = state.get("pages")
    if pages is None:
        return state["cache"]
    return {name: c.replace(pages=pages)
            for name, c in state["cache"].items()}


def _unbind_cache(cache):
    return {name: (c.replace(pages=None) if hasattr(c, "pool") else c)
            for name, c in cache.items()}


def _carry(state, new):
    """Program-body return helper: the explicit per-program updates plus
    pass-through of the admission-data leaves (page table, CFG pairing) the
    host mutates between dispatches. Keeping them state leaves — data, not
    shape — is what lets admission, COW forks and radix hits happen with
    zero recompiles."""
    out = dict(new)
    for k in ("pages", "pair", "cfg", "uncond"):
        if k in state:
            out[k] = state[k]
    return out


@dataclasses.dataclass
class _ChunkJob:
    """One in-flight chunked-prefill admission (prefill_chunk > 0): the
    remapped prompt ids of the rows admitted together, dispatched one
    bounded window per engine iteration so neighbors' decode steps
    interleave — a fat admission can no longer stall every other row for
    its full prompt length."""
    ids: np.ndarray        # (B, prefix_len) remapped+bos'd full-vocab ids
    seeds: np.ndarray      # (B,)
    n_rows: np.ndarray     # (B,)
    mask: np.ndarray       # (B,) bool
    pairs: list            # [(slot, Request)]
    t0: float              # admission wall-clock (serve/prefill span start)
    start: int = 0         # next chunk's first position


class DecodeEngine:
    """Continuous-batching image-token decode over a DALLE model.

    ``slots``: device batch size B (every compiled program is shaped by it).
    ``cache_dtype``: KV storage dtype (f32 / bf16 / int8 — same knob as
    ``generate_images_tokens``). Sampling knobs mirror the sequential path
    so the exactness contract holds per request.

    ``use_kernel`` pins Pallas attend-kernel selection for the engine's
    decode and refill programs (None = shape-gated auto on TPU, dense
    elsewhere). Bitwise token parity with ``generate_images_tokens`` holds
    on the CPU mesh (CI enforces it there). On the TPU it does not — and
    pinning ``use_kernel=False`` here and on the reference does not restore
    it (chip run, PR 21, 1.4B int8w): the engine's B-row programs and the
    reference's 1-row program are different XLA programs and round
    differently. docs/SERVING.md "The exactness contract on the chip" says
    what the chip is held to instead.
    """

    def __init__(self, model: DALLE, params, *, slots: int,
                 cache_dtype=jnp.float32, filter_thres: float = 0.5,
                 temperature: float = 1.0, topk_approx: bool = False,
                 steps_per_sync: int = 1, use_kernel=None,
                 decode_health: bool = False, prefill_chunk: int = 0,
                 kv_block_tokens: int = 0,
                 kv_pool_blocks: Optional[int] = None,
                 radix_cache: bool = True):
        c = model.cfg
        if not c.block.is_default:
            # the engine's cache is multi-head keys and values in a KVCache
            # or PagedKVCache; a block without that layout is refused by
            # name, not run wrongly
            raise NotImplementedError(
                f"the serve engine decodes the default mha+geglu block only; "
                f"the {c.block.name} block has no cached decode path yet "
                f"(latent or grouped keys and values through KVCache / "
                f"PagedKVCache and the decode kernels; a recurrent state, "
                f"with a decay per key channel (kda) or per head (gdn), and "
                f"its convolution tail beside them; a block that norms its "
                f"layers' outputs)")
        attn_types = tuple(c.attn_types) or ("full",)
        if any(t != "full" for t in attn_types) or c.shift_tokens:
            # same constraint set as speculative decode: per-row windows
            # have no per-row sparse-mask gather and the shift ring buffers
            # are one-token-sequential by construction
            raise ValueError(
                "the serve engine requires full attention and "
                f"shift_tokens=False (got attn_types={attn_types}, "
                f"shift_tokens={c.shift_tokens})")
        self.model = model
        self.params = params
        self.slots = int(slots)
        self.cache_dtype = cache_dtype
        self.filter_thres = filter_thres
        self.temperature = temperature
        self.topk_approx = topk_approx
        self.use_kernel = use_kernel
        # graftpulse decode-quality taps (obs/health.py): per-row token
        # entropy + top-k mass computed IN the jitted step from the logits
        # already on device, fetched in the same host sync as the tokens —
        # zero added syncs, sampling untouched (no rng consumed), so the
        # per-request bit-exactness contract holds with the taps on.
        # Program-shaping (rides _program_key and the AOT fingerprint).
        self.decode_health = bool(decode_health)

        self.text_seq_len = c.text_seq_len
        self.prefix_len = c.text_seq_len + 1          # <bos> + text
        self.n_steps = c.image_seq_len
        self.park = c.total_seq_len                   # cache max_seq
        self.num_text_tokens = c.num_text_tokens + c.text_seq_len
        # multi-step scheduling: run K device steps per host sync
        # (lax.scan inside one program). K=1 is pure iteration-level
        # scheduling — a finished row refills on the very next token. K>1
        # amortizes per-dispatch host overhead (the serving lever when the
        # per-token program is small relative to dispatch cost — this
        # sandbox's CPU mesh) at the price of admission granularity: a
        # freed slot waits up to K-1 device steps for its refill. Token
        # exactness is unaffected — the device math is identical.
        assert steps_per_sync >= 1
        self.steps_per_sync = int(steps_per_sync)

        # grid-row granularity for streaming (on_rows): one committed row of
        # the image token grid = one fmap row
        self.row_len = c.image_fmap_size

        # chunked prefill (graftloom): window AND trickle admissions of
        # prompts longer than ``prefill_chunk`` positions dispatch as
        # bounded chunks with decode iterations interleaved — TTFT isolation
        # for the neighbors (a trickle admission becomes a one-row-masked
        # window job). Shared-prefix COHORT prefills stay one-shot: their
        # b=1 prefill is already 1/B of a window's compute, the bound
        # chunking enforces. 0 (the default) keeps the one-shot programs:
        # host loop and compiled programs are byte-identical to the
        # pre-chunking engine. Chunked tokens are bitwise ≡ unchunked
        # (tests/test_serve.py): each chunk token attends exactly the cache
        # prefix the full window would have shown it, at the same reduce
        # widths.
        assert prefill_chunk >= 0
        self.prefill_chunk = int(prefill_chunk)

        # paged KV (graftpage): kv_block_tokens > 0 swaps the dense per-slot
        # slab for a shared block pool + (B, max_blocks) page table. Pool
        # size is in BLOCKS (the HBM knob: blocks × block_tokens × 2hd ×
        # itemsize bytes per layer); the default gives every slot its full
        # private footprint — the interesting deployments size it SMALLER
        # and let the radix cache make up the difference. Admission walks
        # the radix tree per prompt, maps resident blocks, COW-forks the
        # divergent tail and prefills only the miss suffix; the admission
        # suffix rides _refill_chunk at the fixed width set {block_tokens,
        # prefix_len % block_tokens, 1}, so paged engines and the explicit
        # prefill_chunk knob are mutually exclusive (the block size IS the
        # chunk bound).
        assert kv_block_tokens >= 0
        self.kv_block_tokens = int(kv_block_tokens)
        self.paged = self.kv_block_tokens > 0
        self.radix_cache = bool(radix_cache)
        if self.paged:
            if self.prefill_chunk:
                raise ValueError(
                    "kv_block_tokens and prefill_chunk are mutually "
                    "exclusive: paged admission already dispatches prefill "
                    "in block-width chunks")
            bt = self.kv_block_tokens
            self.max_blocks = -(-self.park // bt)      # blocks per slot
            pool_blocks = (int(kv_pool_blocks) if kv_pool_blocks
                           else self.slots * self.max_blocks)
            # progress guarantee: the largest admission unit (a CFG pair =
            # two full rows) must fit the pool outright, else it can never
            # be admitted no matter what eviction frees
            min_need = self.max_blocks * (2 if self.slots >= 2 else 1)
            if pool_blocks < min_need:
                raise ValueError(
                    f"kv_pool_blocks={pool_blocks} cannot hold one "
                    f"admission unit ({min_need} blocks of "
                    f"{bt} tokens)")
            self.kv_pool_blocks = pool_blocks
        else:
            self.max_blocks = 0
            self.kv_pool_blocks = 0

        (self._refill_fn, self._refill_row_fn, self._refill_shared_fn,
         self._refill_chunk_fn, self._step_fn,
         self._cow_copy_fn) = _shared_programs(self)
        self.aot_loaded = False
        self.stats = EngineStats()
        # host-side paged control plane — (re)built per run()
        self.block_pool: Optional[BlockPool] = None
        self.radix: Optional[RadixCache] = None

    def install_executables(self, *, step=None, refill=None,
                            refill_row=None, refill_shared=None,
                            refill_chunks=None, cow_copy=None) -> None:
        """Swap the engine's jitted programs for AOT-compiled executables
        (gateway/aot.py): a cold replica then serves without retracing or
        recompiling any device program. Executables must have been lowered
        from THIS engine configuration — the aot module's fingerprint check
        enforces that; calling one with mismatched shapes/dtypes fails loudly
        at dispatch, never silently.

        ``refill_chunks`` maps chunk WIDTH → executable for every width the
        engine's admission path can dispatch (the fixed set
        ``chunk_widths()``); ``cow_copy`` is the paged fork program. Both
        are required exactly when the engine's configuration uses them —
        the aot_loaded flag must mean the WHOLE cold-start path is
        executable-backed."""
        if step is None or refill is None:
            raise ValueError("install_executables requires the step and "
                             "refill programs")
        if not self.paged and (refill_row is None or refill_shared is None):
            # dense engines dispatch the trickle and shared-prefix programs;
            # paged ones never do (radix hits subsume shared prefills,
            # staggered admission goes through the chunk programs), and
            # their bodies assume a dense slab — so paged bundles omit them
            raise ValueError("install_executables requires refill_row and "
                             "refill_shared for dense engines")
        widths = self.chunk_widths()
        if widths:
            missing = [w for w in widths if w not in (refill_chunks or {})]
            if missing:
                raise ValueError(
                    f"install_executables: refill_chunk widths {missing} "
                    f"required by this engine (chunk_widths={widths})")
            exes = dict(refill_chunks)

            def _chunk_dispatch(params, state, ids_chunk, start, seeds,
                                n_rows, mask, last, _exes=exes):
                return _exes[int(ids_chunk.shape[1])](
                    params, state, ids_chunk, start, seeds, n_rows, mask,
                    last)

            self._refill_chunk_fn = _chunk_dispatch
        if self.paged:
            if cow_copy is None:
                raise ValueError("install_executables: paged engines "
                                 "require the cow_copy program")
            self._cow_copy_fn = cow_copy
        self._step_fn = step
        self._refill_fn = refill
        if refill_row is not None:
            self._refill_row_fn = refill_row
        if refill_shared is not None:
            self._refill_shared_fn = refill_shared
        self.aot_loaded = True

    def chunk_widths(self) -> tuple:
        """The FIXED set of prefill-chunk widths this engine can dispatch —
        what makes chunk-on and paged engines AOT-serializable: every
        admission decomposes into windows from this set, so the aot bundle
        carries one executable per width and a cold replica never compiles.
        Dense chunk-off engines return () (the one-shot programs cover
        admission)."""
        if self.paged:
            bt = self.kv_block_tokens
            widths = {1}                        # full-hit logits recompute
            if bt < self.prefix_len:
                widths.add(bt)                  # miss-suffix body chunks
                if self.prefix_len % bt:
                    widths.add(self.prefix_len % bt)   # suffix tail
            return tuple(sorted(widths))
        if 0 < self.prefill_chunk < self.prefix_len:
            widths = {self.prefill_chunk}
            if self.prefix_len % self.prefill_chunk:
                widths.add(self.prefix_len % self.prefill_chunk)
            return tuple(sorted(widths))
        return ()

    # -- device programs ---------------------------------------------------
    def _init_state(self) -> Dict:
        B = self.slots
        if self.paged:
            cache = self.model.apply(
                self.params, self.kv_pool_blocks, self.kv_block_tokens,
                self.cache_dtype, method=DALLE.serve_init_cache_paged)
            pages = jnp.full((B, self.max_blocks), -1, jnp.int32)
            probe_cache = {n: c.replace(pages=pages)
                           for n, c in cache.items()}
        else:
            cache = self.model.apply(self.params, self.slots,
                                     self.cache_dtype,
                                     method=DALLE.serve_init_cache)
            pages = None
            probe_cache = cache
        texts = jax.ShapeDtypeStruct((B, self.text_seq_len), jnp.int32)
        mask = jax.ShapeDtypeStruct((B,), jnp.bool_)
        # logits dtype must match what the model emits (bf16 params emit
        # bf16 logits): a f32 placeholder would silently promote the
        # jnp.where merge and break bitwise exactness vs the sequential path
        out_shape = jax.eval_shape(
            lambda p, t, cc, m: self.model.apply(
                p, t, cc, m, method=DALLE.serve_refill),
            self.params, texts, probe_cache, mask)
        logits_dtype = out_shape[0].dtype
        state = {
            "cache": cache,
            "logits": jnp.zeros((B, out_shape[0].shape[-1]), logits_dtype),
            "cur_key": jnp.zeros((B, 2), jnp.uint32),
            "orig_key": jnp.zeros((B, 2), jnp.uint32),
            # parked until admitted: j clamps to the final step, active=False
            "t_idx": jnp.full((B,), self.n_steps, jnp.int32),
            # per-row decode length (ragged service demand — partial-grid
            # requests): tokens for a row with n < image_seq_len equal the
            # first n of the full single-request generation
            "n_row": jnp.full((B,), self.n_steps, jnp.int32),
            "active": jnp.zeros((B,), jnp.bool_),
            # CFG pairing (graftpage satellite): per-row partner index, cond
            # scale and uncond flag — DATA leaves the host rewrites at
            # admission. pair[i] == i / cfg == 1.0 rows sample their raw
            # logits bitwise unchanged, so non-CFG traffic is untouched.
            "pair": jnp.arange(B, dtype=jnp.int32),
            "cfg": jnp.ones((B,), jnp.float32),
            "uncond": jnp.zeros((B,), jnp.bool_),
        }
        if pages is not None:
            state["pages"] = pages
        return state

    def _refill(self, params, state, texts, seeds, n_rows, mask):
        new_keys = jax.vmap(jax.random.PRNGKey)(seeds)       # (B, 2) u32
        logits_r, cache = self.model.apply(
            params, texts, _bind_cache(state), mask, self.use_kernel,
            method=DALLE.serve_refill)
        m1 = mask[:, None]
        return _carry(state, {
            "cache": _unbind_cache(cache),
            "logits": jnp.where(m1, logits_r, state["logits"]),
            "cur_key": jnp.where(m1, new_keys, state["cur_key"]),
            "orig_key": jnp.where(m1, new_keys, state["orig_key"]),
            "t_idx": jnp.where(mask, 0, state["t_idx"]),
            "n_row": jnp.where(mask, n_rows, state["n_row"]),
            "active": state["active"] | mask,
        })

    def _refill_row(self, params, state, text1, seed, n_tok, row):
        """Admit ONE request into slot ``row`` (traced scalar — one
        compiled program serves every slot): a b=1 prefill (bitwise the
        sequential ``_prefill``) scattered into the shared cache. Under
        staggered completions admissions arrive one or two rows at a time;
        this costs 1/B of the multi-row refill window, which stays the
        bulk-admission path (cold start, bursts)."""
        logits1, cache1 = self.model.apply(
            params, text1, self.cache_dtype, method=DALLE.serve_prefill_row)
        cache = dict(state["cache"])
        for name, small in cache1.items():
            big = cache[name]
            kv = jax.lax.dynamic_update_slice(big.kv, small.kv, (row, 0, 0))
            if big.scale is not None:
                sc = jax.lax.dynamic_update_slice(big.scale, small.scale,
                                                  (row, 0, 0))
                cache[name] = big.replace(kv=kv, scale=sc)
            else:
                cache[name] = big.replace(kv=kv)
        key1 = jax.random.PRNGKey(seed)
        return _carry(state, {
            "cache": cache,
            "logits": jax.lax.dynamic_update_slice(
                state["logits"], logits1.astype(state["logits"].dtype),
                (row, 0)),
            "cur_key": jax.lax.dynamic_update_slice(
                state["cur_key"], key1[None], (row, 0)),
            "orig_key": jax.lax.dynamic_update_slice(
                state["orig_key"], key1[None], (row, 0)),
            "t_idx": state["t_idx"].at[row].set(0),
            "n_row": state["n_row"].at[row].set(n_tok),
            "active": state["active"].at[row].set(True),
        })

    # graftir: allow=precision -- the shared-prefix refill and the paged
    # COW fork are admission-only programs: they WRITE (or block-move) KV
    # into the int8 cache but never attend over it, so the rows' quant
    # scales legitimately pass through as moved data without a
    # dequantizing multiply (graftnum orphaned-scale); the scales are
    # consumed by the very next serve_decode step, whose entry pins the
    # dequant sites.
    def _refill_shared(self, params, state, text1, seeds, n_rows, mask):
        """Shared-prefix admission (graftloom): N candidates of ONE prompt
        (masked rows) pay a single b=1 text prefill, broadcast into every
        sibling row (``DALLE.serve_refill_shared``), with per-candidate RNG
        lanes seeded independently — each candidate's tokens stay BITWISE
        identical to an independent single-candidate request, (N−1) prompt
        prefills cheaper."""
        new_keys = jax.vmap(jax.random.PRNGKey)(seeds)       # (B, 2) u32
        logits1, cache = self.model.apply(
            params, text1, state["cache"], mask, self.cache_dtype,
            method=DALLE.serve_refill_shared)
        m1 = mask[:, None]
        return _carry(state, {
            "cache": cache,
            "logits": jnp.where(m1, logits1.astype(state["logits"].dtype),
                                state["logits"]),
            "cur_key": jnp.where(m1, new_keys, state["cur_key"]),
            "orig_key": jnp.where(m1, new_keys, state["orig_key"]),
            "t_idx": jnp.where(mask, 0, state["t_idx"]),
            "n_row": jnp.where(mask, n_rows, state["n_row"]),
            "active": state["active"] | mask,
        })

    def _refill_chunk(self, params, state, ids_chunk, start, seeds, n_rows,
                      mask, last):
        """One bounded window of a chunked prefill: ``ids_chunk`` (B, w)
        already remapped+bos'd prompt ids written at positions
        [start, start+w) of the masked rows. Rows only turn active — and
        only then consume keys/logits — on the FINAL chunk (``last``, a
        traced scalar so one program serves every chunk of a given
        width)."""
        logits_r, cache = self.model.apply(
            params, ids_chunk, _bind_cache(state), mask, start,
            self.use_kernel, method=DALLE.serve_refill_window)
        new_keys = jax.vmap(jax.random.PRNGKey)(seeds)
        lm = mask & last
        m1 = lm[:, None]
        return _carry(state, {
            "cache": _unbind_cache(cache),
            "logits": jnp.where(m1, logits_r.astype(state["logits"].dtype),
                                state["logits"]),
            "cur_key": jnp.where(m1, new_keys, state["cur_key"]),
            "orig_key": jnp.where(m1, new_keys, state["orig_key"]),
            "t_idx": jnp.where(lm, 0, state["t_idx"]),
            "n_row": jnp.where(lm, n_rows, state["n_row"]),
            "active": state["active"] | lm,
        })

    def _cow_copy(self, state, src, dst):
        """Copy-on-write fork (graftpage): duplicate shared blocks into
        fresh ones in every layer's pool — ``pool[dst[i]] = pool[src[i]]``,
        fixed lane count B with inactive lanes' dst out of bounds (scatter
        drop). Runs BEFORE the forked row's first write, so radix-resident
        blocks are never mutated; int8 scale planes ride with their
        blocks."""
        cache = {name: (c.copy_blocks(src, dst) if hasattr(c, "pool")
                        else c)
                 for name, c in state["cache"].items()}
        out = dict(state)
        out["cache"] = cache
        return out

    def _step(self, params, state):
        n_steps = self.n_steps
        logits, t_idx, active = (state["logits"], state["t_idx"],
                                 state["active"])
        n_row = state["n_row"]
        j = jnp.minimum(t_idx, n_row - 1)
        final = j == n_row - 1

        # per-row key discipline == the sequential split chain: tokens
        # 0..image_seq_len-2 consume one split each; only the FULL
        # sequence's last token uses fold_in(orig_key, n_steps) without
        # consuming a split. A partial-length row's final token therefore
        # still comes from the split chain — its tokens are exactly the
        # first n of the full generation.
        sp = jax.vmap(jax.random.split)(state["cur_key"])    # (B, 2, 2)
        new_key, sub = sp[:, 0], sp[:, 1]
        fin_key = jax.vmap(
            lambda k: jax.random.fold_in(k, n_steps))(state["orig_key"])
        uses_fold = final & (n_row == n_steps)
        sample_key = jnp.where(uses_fold[:, None], fin_key, sub)

        img_logits = logits[:, self.num_text_tokens:]
        # classifier-free guidance on paired rows: the stored per-row logits
        # stay RAW (cond rows hold conditioned logits, their partners hold
        # null-text logits); the merge is recomputed at every sample site —
        # exactly the sequential ``null + (cond − null) * cond_scale`` on
        # the image band (slicing commutes with the elementwise merge).
        # Both rows of a pair sample from the COND row's merged logits with
        # the same key chain (same seed), so they emit identical tokens in
        # lockstep and free together. The scale is cast to the logits dtype
        # first: a strong f32 scalar would promote bf16 logits and break
        # bitwise parity with the weak-typed sequential constant. cfg==1.0
        # rows keep their raw logits bitwise untouched (x + 0*s is NOT a
        # bitwise identity for -0.0 — hence the where, not the arithmetic).
        pair, cfg, uncond = state["pair"], state["cfg"], state["uncond"]
        s = cfg.astype(img_logits.dtype)[:, None]
        partner = img_logits[pair]
        merged = partner + (img_logits - partner) * s
        merged = jnp.where(uncond[:, None], merged[pair], merged)
        img_logits = jnp.where((cfg == 1.0)[:, None], img_logits, merged)
        stats = {}
        if self.decode_health:
            # per-row quality of the distribution being sampled FROM (the
            # pre-gumbel logits): entropy + top-k mass, (B,) f32 each —
            # fetched with the tokens at the same sync
            from ..obs.health import decode_quality
            stats = decode_quality(img_logits)
        tok = gumbel_sample_rows(sample_key, img_logits,
                                 thres=self.filter_thres,
                                 temperature=self.temperature,
                                 approx=self.topk_approx)

        decode_rows = active & ~final
        offsets = jnp.where(decode_rows, self.prefix_len + j, self.park)
        new_logits, cache = self.model.apply(
            params, tok, j, offsets, _bind_cache(state), self.use_kernel,
            method=DALLE.serve_decode)
        finished = active & final
        state = _carry(state, {
            "cache": _unbind_cache(cache),
            "logits": jnp.where(decode_rows[:, None], new_logits, logits),
            "cur_key": jnp.where(uses_fold[:, None], state["cur_key"],
                                 new_key),
            "orig_key": state["orig_key"],
            "t_idx": jnp.where(active, t_idx + 1, t_idx),
            "n_row": n_row,
            "active": decode_rows,
        })
        return tok, finished, stats, state

    def _multi_step(self, params, state):
        """steps_per_sync × _step in one program; (K, B) tokens/finished
        (+ (K, B) decode-quality stats when ``decode_health`` — an empty
        dict otherwise, so the program signature is stable)."""
        if self.steps_per_sync == 1:
            tok, finished, stats, state = self._step(params, state)
            return (tok[None], finished[None],
                    jax.tree.map(lambda x: x[None], stats), state)

        def body(carry, _):
            tok, finished, stats, carry = self._step(params, carry)
            return carry, (tok, finished, stats)

        state, (toks, fins, stats) = jax.lax.scan(body, state, None,
                                                  length=self.steps_per_sync)
        return toks, fins, stats, state

    # -- host loop ---------------------------------------------------------
    def _pad_text(self, text: np.ndarray) -> np.ndarray:
        out = np.zeros((self.text_seq_len,), np.int32)
        n = min(len(text), self.text_seq_len)
        out[:n] = text[:n]
        return out

    def _n_tokens(self, req: Request) -> int:
        if req.max_tokens is None:
            return self.n_steps
        return int(np.clip(req.max_tokens, 1, self.n_steps))

    def _remap_bos_host(self, texts: np.ndarray) -> np.ndarray:
        """Host-side ``remap_and_bos`` for the chunked-prefill path: 0-pads
        → unique per-position pad ids, <bos>=0 prepended. Integer-exact vs
        the device remap, so every chunk gathers the same embedding rows the
        one-shot window would."""
        B, T = texts.shape
        pad_ids = (np.arange(T, dtype=np.int32)
                   + np.int32(self.num_text_tokens - self.text_seq_len))
        out = np.where(texts == 0, pad_ids[None, :], texts).astype(np.int32)
        return np.concatenate([np.zeros((B, 1), np.int32), out], axis=1)

    # -- admission units (CFG pairing + paged planning) --------------------
    def _expand_unit(self, req: Request) -> List[Request]:
        """A request is admitted as a UNIT of slots that must activate in
        lockstep: one row normally, two for cond_scale != 1.0 — the request
        itself plus a synthetic null-text partner (negative request_id,
        never surfaced to callers) whose logits feed the per-step CFG
        merge. The null row shares the seed so both rows' key chains — and
        therefore their sampled tokens — stay bitwise identical."""
        if req.cond_scale == 1.0:
            return [req]
        if self.slots < 2:
            raise ValueError(
                "cond_scale != 1.0 needs an engine with slots >= 2 (the "
                "CFG pair occupies two decode slots)")
        null = dataclasses.replace(
            req, request_id=-req.request_id - 1,
            text=np.zeros_like(np.asarray(req.text)),
            group_id=None, group_size=1, group_index=0)
        return [req, null]

    def _take_units(self, queue, n_free: int):
        """Deferred units first (strict FIFO — a deferred CFG pair or
        pool-starved unit is never overtaken), then fresh queue takes,
        expanded into units. Units that don't fit ``n_free`` rows go back
        to the overflow deque intact. Returns (placeable units, number of
        requests newly taken from the queue)."""
        units = self._overflow
        self._overflow = []
        taken = 0
        have = sum(len(u) for u in units)
        if have < n_free:
            for req in queue.take(n_free - have):
                taken += 1
                units.append(self._expand_unit(req))
        placed, rows = [], 0
        for i, u in enumerate(units):
            if rows + len(u) > n_free:
                self._overflow = units[i:]
                break
            placed.append(u)
            rows += len(u)
        return placed, taken

    def _set_pair_state(self, pairs_u) -> None:
        """Write the CFG pairing mirrors for one admitted unit; dirty only
        when something actually changes, so non-CFG workloads never upload
        (their admission path is byte-identical to the pre-CFG engine)."""
        if len(pairs_u) == 2:
            (cs, creq), (ns, _) = pairs_u
            self._pair_host[cs], self._pair_host[ns] = ns, cs
            self._cfg_host[cs] = self._cfg_host[ns] = creq.cond_scale
            self._uncond_host[cs], self._uncond_host[ns] = False, True
            self._cfg_dirty = True
        else:
            slot = pairs_u[0][0]
            if (self._pair_host[slot] != slot
                    or self._cfg_host[slot] != 1.0
                    or self._uncond_host[slot]):
                self._pair_host[slot] = slot
                self._cfg_host[slot] = 1.0
                self._uncond_host[slot] = False
                self._cfg_dirty = True

    def _upload_cfg(self, state):
        if self._cfg_dirty:
            state["pair"] = jnp.asarray(self._pair_host)
            state["cfg"] = jnp.asarray(self._cfg_host)
            state["uncond"] = jnp.asarray(self._uncond_host)
            self._cfg_dirty = False
        return state

    # -- paged admission (graftpage) ---------------------------------------
    def _plan_row(self, req: Request) -> dict:
        """Radix-match one row's prompt and size its block demand: the
        blocks it can MAP from resident KV (read-only shares), the block it
        must COW-fork (full hit), and the fresh blocks it needs for the
        unmatched prompt suffix plus its decode tokens. Written positions
        span [0, prefix_len + n_tok - 1) — the final token's KV is never
        written (the dense engine's decode_rows contract), so a full-length
        row needs ceil((total_seq_len - 1) / bt) blocks."""
        bt = self.kv_block_tokens
        ids = self._remap_bos_host(self._pad_text(req.text)[None])[0]
        key = tuple(int(x) for x in ids)
        n_tok = self._n_tokens(req)
        total = -(-(self.prefix_len + n_tok - 1) // bt)
        pr = {"req": req, "key": key, "ids": ids, "n_tok": n_tok,
              "shared": [], "fork_src": None, "fresh_n": total,
              "full": False, "hit_tok": 0, "match": None}
        if not self.radix_cache:
            return pr
        # record=False: a unit the pool defers is re-planned every retry
        # iteration (its matched blocks are unprotected while it waits, so
        # the match CANNOT be cached across evictions) — the ledger commits
        # once, in _plan_unit, when the unit actually admits
        m = self.radix.match(key, record=False)
        pr["match"] = m
        if m.full:
            # the block holding position prefix_len-1 must be forked before
            # the width-1 logits recompute rewrites it: with a partial tail
            # that's the tail block, with a block-aligned prompt it's the
            # LAST full block — either way the fork dst is the row's first
            # fresh block and the remaining matched blocks stay shared
            shared = list(m.blocks) if self.prefix_len % bt else \
                list(m.blocks[:-1])
            pr.update(shared=shared, fork_src=m.tail_block,
                      fresh_n=total - len(shared), full=True,
                      hit_tok=m.hit_tokens)
        elif m.blocks:
            pr.update(shared=list(m.blocks),
                      fresh_n=total - len(m.blocks), hit_tok=m.hit_tokens)
        return pr

    def _plan_unit(self, unit) -> Optional[dict]:
        """Block-feasibility for one admission unit, atomically: retain
        every block the unit reads FIRST (matched shares and fork sources
        — protecting them from the eviction this very pass may run), evict
        radix-only leaves for the remainder, then allocate every fresh
        block the unit's rows will ever write (prompt suffix AND decode) up
        front — a row that starts decoding can never run out mid-stream.
        Returns None (with retains rolled back) when the pool can't cover
        the unit; the caller defers the whole unit FIFO-fairly."""
        pool = self.block_pool
        rows = [self._plan_row(r) for r in unit]
        retained = []
        for pr in rows:
            for bid in pr["shared"]:
                pool.retain(bid)
                retained.append(bid)
            if pr["fork_src"] is not None:
                pool.retain(pr["fork_src"])
                retained.append(pr["fork_src"])
        need = sum(pr["fresh_n"] for pr in rows)
        if pool.free_count < need and self.radix_cache:
            self.stats.pages_evicted += self.radix.evict(
                need - pool.free_count)
        if pool.free_count < need:
            for bid in retained:
                pool.release(bid)
            return None
        bt = self.kv_block_tokens
        n_full = self.prefix_len // bt
        t = self.prefix_len % bt
        tmp = []
        for pr in rows:
            pr["fresh"] = [pool.alloc() for _ in range(pr["fresh_n"])]
            if pr["fork_src"] is not None:
                pr["fork_dst"] = pr["fresh"][0]
                tmp.append(pr["fork_src"])   # held only until the copy runs
            elif self.radix_cache:
                # register the prompt's blocks NOW (content is prompt-
                # deterministic; this pass's dispatches write it), so
                # same-pass siblings — candidate fan-outs, repeated
                # templates — already hit; insert() retains one tree ref
                # per NEW node and keeps incumbents for already-resident
                # prefixes
                combined = pr["shared"] + pr["fresh"]
                self.radix.insert(pr["key"], combined[:n_full],
                                  combined[n_full] if t else None)
        # the unit is definitely admitting: commit its matches to the hit
        # ledgers exactly once (planning retries of deferred units don't
        # count — see _plan_row)
        for pr in rows:
            if pr["match"] is not None:
                self.radix.record(pr["match"])
            if pr["full"]:
                self.stats.radix_full_hits += 1
                self.stats.shared_prefills_saved += 1
                self.stats.prefix_hit_tokens += pr["hit_tok"]
            elif pr["shared"]:
                self.stats.radix_partial_hits += 1
                self.stats.prefix_hit_tokens += pr["hit_tok"]
            else:
                self.stats.radix_misses += 1
        return {"rows": rows, "tmp": tmp}

    def _admit_paged(self, state, placed, row_t0):
        """Dispatch one paged admission pass. Order is load-bearing:
        page-table upload → full-miss windows → partial-hit suffix chunks →
        COW forks → full-hit width-1 recomputes. Forks must follow every
        prefill that WRITES a block being forked (same-pass siblings fork
        blocks the pass itself fills) and precede the full-hit write into
        the fork; device dispatch order makes each step see the previous
        one's pool."""
        B = self.slots
        bt = self.kv_block_tokens
        pool = self.block_pool
        tmp = []
        miss_mask = np.zeros((B,), bool)
        texts = np.zeros((B, self.text_seq_len), np.int32)
        seeds = np.zeros((B,), np.int32)
        n_rows_arr = np.full((B,), self.n_steps, np.int32)
        suffix: Dict[int, list] = {}
        forks = []
        hit_rows = []
        all_rows = []
        for pairs_u, plan in placed:
            tmp.extend(plan["tmp"])
            for (slot, req), pr in zip(pairs_u, plan["rows"]):
                blocks = pr["shared"] + pr["fresh"]
                self._pages_host[slot, :] = -1
                self._pages_host[slot, :len(blocks)] = blocks
                self._slot_blocks[slot] = blocks
                seeds[slot] = req.seed
                n_rows_arr[slot] = pr["n_tok"]
                all_rows.append((slot, req, pr))
                if pr["full"]:
                    forks.append((pr["fork_src"], pr["fork_dst"]))
                    hit_rows.append((slot, pr))
                elif pr["shared"]:
                    suffix.setdefault(len(pr["shared"]) * bt,
                                      []).append((slot, pr))
                else:
                    miss_mask[slot] = True
                    texts[slot] = self._pad_text(req.text)
        # one upload covers every layer and every dispatch below — the
        # page table is device DATA, so nothing here can recompile
        state["pages"] = jnp.asarray(self._pages_host)
        state = self._upload_cfg(state)
        t0 = time.perf_counter()
        if miss_mask.any():
            state = self._refill_fn(self.params, state, texts, seeds,
                                    n_rows_arr, miss_mask)
            self.stats.refills += 1
        for start in sorted(suffix):
            mask = np.zeros((B,), bool)
            ids = np.zeros((B, self.prefix_len), np.int32)
            for slot, pr in suffix[start]:
                mask[slot] = True
                ids[slot] = pr["ids"]
            pos = start
            while pos < self.prefix_len:
                w = min(bt, self.prefix_len - pos)
                last = pos + w >= self.prefix_len
                state = self._refill_chunk_fn(
                    self.params, state, ids[:, pos:pos + w], np.int32(pos),
                    seeds, n_rows_arr, mask, np.bool_(last))
                self.stats.prefill_chunks += 1
                pos += w
            self.stats.refills += 1
        if forks:
            src = np.zeros((B,), np.int32)
            # unused lanes get UNIQUE out-of-range dst (scatter drop)
            dst = pool.num_blocks + np.arange(B, dtype=np.int32)
            for i, (s, d) in enumerate(forks):
                src[i] = s
                dst[i] = d
            state = self._cow_copy_fn(state, src, dst)
            self.stats.cow_forks += len(forks)
            pool.cow_copies += len(forks)
        if hit_rows:
            # full-prefix hits recompute ONLY position prefix_len-1 — a
            # width-1 window whose logits are bitwise the one-shot window's
            # last position (same gathered prefix, same reduce widths); its
            # KV write is an idempotent rewrite into the row's private fork
            mask = np.zeros((B,), bool)
            ids = np.zeros((B, self.prefix_len), np.int32)
            for slot, pr in hit_rows:
                mask[slot] = True
                ids[slot] = pr["ids"]
            state = self._refill_chunk_fn(
                self.params, state, ids[:, self.prefix_len - 1:],
                np.int32(self.prefix_len - 1), seeds, n_rows_arr, mask,
                np.bool_(True))
            self.stats.refills += 1
        t1 = time.perf_counter()
        for bid in tmp:
            pool.release(bid)
        for slot, req, pr in all_rows:
            if req.request_id >= 0:
                mode = ("paged-hit" if pr["full"] else
                        "paged-partial" if pr["shared"] else "paged")
                record_span("serve/prefill", t0, t1 - t0,
                            request_id=req.request_id,
                            trace_id=req.trace_id, mode=mode)
            row_t0[slot] = t1
        gauge_set("kv.pages_free", float(pool.free_count))
        gauge_set("kv.pages_used", float(pool.used_count))
        gauge_set("kv.pages_shared", float(pool.shared_count))
        gauge_set("kv.pages_cow_copies", float(pool.cow_copies))
        counter_add("kv.prefix_hit_tokens_total",
                    float(sum(pr["hit_tok"] for _, _, pr in all_rows)))
        return state

    def _release_slot_blocks(self, slot: int) -> None:
        """Completion: drop the row's refs on every block it mapped —
        shared blocks fall back to tree-only (evictable), private blocks
        free outright unless the radix tree adopted them at admission. The
        device page table keeps its stale row until the slot's next
        admission overwrites it: an inactive row's writes drop at the park
        offset and its outputs are discarded, so stale mappings are
        unreachable."""
        for bid in self._slot_blocks.pop(slot, ()):
            self.block_pool.release(bid)
        self._pages_host[slot, :] = -1

    def kv_stats(self) -> dict:
        """Page-pool + radix counters for the health verb and obs_report."""
        if not self.paged:
            return {"paged": False}
        out = {"paged": True, "block_tokens": self.kv_block_tokens,
               "pool_blocks": self.kv_pool_blocks,
               "blocks_per_slot": self.max_blocks,
               "radix_cache": self.radix_cache}
        pool, rx = self.block_pool, self.radix
        if pool is not None:
            out.update(pages_free=pool.free_count,
                       pages_used=pool.used_count,
                       pages_shared=pool.shared_count,
                       cow_copies=pool.cow_copies)
        if rx is not None:
            out.update(radix_nodes=rx.resident_nodes,
                       radix_lookups=rx.lookups,
                       radix_full_hits=rx.full_hits,
                       radix_partial_hits=rx.partial_hits,
                       prefix_hit_tokens=rx.hit_tokens_total,
                       radix_evictions=rx.evictions)
        return out

    @staticmethod
    def _split_cohorts(pairs):
        """Partition one admission pass into shared-prefix cohorts (≥2
        members of one group with identical text — the /v1/images fan-out)
        and singles. A group split across admission passes still shares
        within each pass; a lone straggler rides the single paths. Group
        members with mismatched text (a misuse the gateway never produces)
        are demoted to singles rather than silently prefilled with the
        first member's prompt."""
        by_gid: Dict[int, list] = {}
        singles = []
        for slot, req in pairs:
            # CFG members (cond_scale != 1.0) ride the single paths: the
            # broadcast-prefill cohort would activate a cond row in one
            # dispatch and its synthetic null partner in another, breaking
            # the pair's lockstep key chain
            if req.group_id is not None and req.cond_scale == 1.0:
                by_gid.setdefault(req.group_id, []).append((slot, req))
            else:
                singles.append((slot, req))
        cohorts = []
        for members in by_gid.values():
            text0 = members[0][1].text
            if len(members) >= 2 and all(
                    np.array_equal(r.text, text0) for _, r in members[1:]):
                cohorts.append(members)
            else:
                singles.extend(members)
        singles.sort(key=lambda p: p[0])
        return cohorts, singles

    def run(self, queue: RequestQueue, *, max_steps: Optional[int] = None,
            poll_s: float = 0.02,
            on_complete=None, on_rows=None) -> List[CompletedRequest]:
        """Serve until the queue is drained (closed + empty + nothing in
        flight). Producers may keep submitting from other threads while
        this runs. Returns completions in completion order.

        A long-lived deployment (queue held open indefinitely) should pass
        ``on_complete``: each CompletedRequest is handed to it the moment
        its last token lands and is NOT accumulated — the return value is
        then an empty list and memory stays O(slots) for the life of the
        loop. Without it, every completion (including its full token array)
        is retained until drain.

        ``on_rows(request, row_idx, row_tokens)`` streams partial results:
        called the moment a committed GRID ROW of the image token field
        finishes (``row_len == image_fmap_size`` tokens — the slot state's
        per-row offset crossing a row boundary), plus once for a trailing
        partial row of a ``max_tokens`` request just before its completion.
        Concatenating a request's row_tokens in row_idx order reproduces its
        final token sequence exactly, so a streaming consumer (the
        gateway's SSE writer, which dVAE-decodes committed rows into
        preview pixels) needs no end-of-stream reconciliation. Callbacks
        run on the engine thread — keep them O(row) and non-blocking.

        ``max_steps`` is a harness bound (bench/smoke), not a graceful
        drain: requests still mid-decode when it trips are abandoned —
        already consumed from the queue, never completed. Their ids are
        recorded in ``stats.aborted_in_flight`` so the loss is visible."""
        B = self.slots
        sched = SlotScheduler(B)
        # paged control plane + CFG mirrors, fresh per serve loop (the
        # device cache below starts empty, so host residency must too)
        if self.paged:
            self.block_pool = BlockPool(self.kv_pool_blocks)
            self.radix = RadixCache(self.kv_block_tokens, self.block_pool)
            self._pages_host = np.full((B, self.max_blocks), -1, np.int32)
            self._slot_blocks: Dict[int, List[int]] = {}
        self._pair_host = np.arange(B, dtype=np.int32)
        self._cfg_host = np.ones((B,), np.float32)
        self._uncond_host = np.zeros((B,), bool)
        self._cfg_dirty = False
        self._overflow: List[List[Request]] = []
        state = self._init_state()
        buffers: Dict[int, List[int]] = {}
        row_t0: Dict[int, float] = {}      # per-slot start of the open row
        # per-slot decode-quality accumulators [Σentropy, Σtopk_mass, n]
        # (decode_health only; reset at admission, reduced at completion)
        qual: Dict[int, List[float]] = {}
        completed: List[CompletedRequest] = []
        self.stats = EngineStats()

        # flight-recorder / watchdog state provider: while this loop is
        # live, a stall report or post-mortem bundle carries the queue
        # depth, slot occupancy and in-flight request ids — the serve-side
        # "where was everyone" snapshot. Read from other threads; every
        # value is a point-in-time copy and the collector tolerates races.
        def _engine_state() -> dict:
            inflight = []
            for s in sched.active_slots():
                r = sched.request_at(s)
                if r is not None:
                    inflight.append({
                        "slot": s, "request_id": r.request_id,
                        "trace_id": r.trace_id,
                        "tokens_done": len(buffers.get(s, ()))})
            return {"queue_depth": queue.qsize(),
                    "slot_occupancy": sched.occupancy,
                    "steps": self.stats.steps, "inflight": inflight}

        provider = register_state_provider(
            f"serve.engine[{threading.current_thread().name}]",
            _engine_state)
        try:
            return self._run(queue, sched, state, buffers, row_t0, qual,
                             completed, max_steps=max_steps, poll_s=poll_s,
                             on_complete=on_complete, on_rows=on_rows)
        finally:
            unregister_state_provider(provider)

    def _admit_shared(self, state, members, row_t0):
        """One shared-prefix cohort: a single b=1 prefill broadcast into
        every member's slot, per-candidate RNG lanes from each member's own
        seed."""
        B = self.slots
        seeds = np.zeros((B,), np.int32)
        n_rows = np.full((B,), self.n_steps, np.int32)
        mask = np.zeros((B,), bool)
        for slot, req in members:
            seeds[slot] = req.seed
            n_rows[slot] = self._n_tokens(req)
            mask[slot] = True
        text1 = self._pad_text(members[0][1].text)[None]
        t0 = time.perf_counter()
        state = self._refill_shared_fn(self.params, state, text1, seeds,
                                       n_rows, mask)
        t1 = time.perf_counter()
        self.stats.refills += 1
        self.stats.shared_refills += 1
        self.stats.shared_prefills_saved += len(members) - 1
        record_span("pipeline/prefill_shared", t0, t1 - t0,
                    group_id=members[0][1].group_id,
                    candidates=len(members),
                    trace_id=members[0][1].trace_id)
        for slot, req in members:
            record_span("serve/prefill", t0, t1 - t0,
                        request_id=req.request_id, trace_id=req.trace_id,
                        mode="shared")
            row_t0[slot] = t1
        return state

    def _dispatch_chunk(self, state, chunk_jobs, pending, row_t0):
        """Advance the oldest pending chunked prefill by ONE bounded window
        (the per-iteration budget that keeps neighbors' decode interleaved);
        on the final chunk the rows turn active and their prefill spans
        close."""
        job = chunk_jobs[0]
        prefix = job.ids.shape[1]
        w = min(self.prefill_chunk, prefix - job.start)
        last = job.start + w >= prefix
        t0 = time.perf_counter()
        state = self._refill_chunk_fn(
            self.params, state, job.ids[:, job.start:job.start + w],
            np.int32(job.start), job.seeds, job.n_rows, job.mask,
            np.bool_(last))
        t1 = time.perf_counter()
        self.stats.prefill_chunks += 1
        record_span("serve/prefill_chunk", t0, t1 - t0,
                    start=job.start, width=w,
                    step=self.stats.steps,
                    trace_id=job.pairs[0][1].trace_id)
        histogram_observe("serve.prefill_chunk_seconds", t1 - t0,
                          trace_id=job.pairs[0][1].trace_id)
        job.start += w
        if last:
            chunk_jobs.pop(0)
            self.stats.refills += 1
            for slot, req in job.pairs:
                pending.discard(slot)
                record_span("serve/prefill", job.t0, t1 - job.t0,
                            request_id=req.request_id,
                            trace_id=req.trace_id, mode="chunked")
                row_t0[slot] = t1
        return state

    def _run(self, queue, sched, state, buffers, row_t0, qual, completed, *,
             max_steps, poll_s, on_complete, on_rows):
        B = self.slots
        chunk_jobs: List[_ChunkJob] = []
        pending: set = set()       # slots admitted but mid-chunked-prefill
        # drain also requires the overflow deque empty: units deferred for
        # slots (a CFG pair against one free slot) or for pool pressure were
        # already consumed from the queue and still owe completions
        while not (queue.drained and not sched.any_active
                   and not self._overflow):
            if max_steps is not None and self.stats.steps >= max_steps:
                break

            # admission: fill every free slot the queue can cover, FIFO,
            # in lockstep UNITS (single rows, or cond+null CFG pairs)
            pre_q = queue.qsize()
            free = sched.free_slots()
            admitted = 0
            if free:
                units, admitted = self._take_units(queue, len(free))
                placed = []
                for i, unit in enumerate(units):
                    plan = None
                    if self.paged:
                        plan = self._plan_unit(unit)
                        if plan is None:
                            # pool can't cover the unit even after
                            # eviction: defer it AND everything behind it
                            # (FIFO — no overtaking), retry when
                            # completions release blocks
                            self._overflow = units[i:] + self._overflow
                            break
                    placed.append((sched.admit(unit), plan))
                if placed:
                    pairs = []
                    now = time.perf_counter()
                    for pairs_u, _ in placed:
                        self._set_pair_state(pairs_u)
                        for slot, req in pairs_u:
                            req.admitted_at = now
                            buffers[slot] = []
                            qual[slot] = [0.0, 0.0, 0]
                            pairs.append((slot, req))
                            if req.request_id < 0:
                                continue   # synthetic CFG-null row
                            # queue wait as its own span (admission SLO
                            # input: TTFT = queue wait + prefill + first
                            # step) + gauge
                            record_span("serve/request_queue_wait",
                                        req.submitted_at,
                                        now - req.submitted_at,
                                        request_id=req.request_id,
                                        trace_id=req.trace_id)
                            gauge_set("serve.queue_wait_s",
                                      now - req.submitted_at)
                            histogram_observe("serve.queue_wait_seconds",
                                              now - req.submitted_at,
                                              trace_id=req.trace_id)
                            record_event("request_admitted", slot=slot,
                                         request_id=req.request_id,
                                         trace_id=req.trace_id)
                if placed and self.paged:
                    state = self._admit_paged(state, placed, row_t0)
                elif placed:
                    state = self._upload_cfg(state)
                    # shared-prefix cohorts first (one prefill per group),
                    # then singles through the classic window/trickle split
                    cohorts, singles = self._split_cohorts(pairs)
                    for members in cohorts:
                        state = self._admit_shared(state, members, row_t0)
                    chunk_on = 0 < self.prefill_chunk < self.prefix_len
                    if singles and (2 * len(singles) >= B or chunk_on):
                        # bulk admission: one multi-row refill window —
                        # chunked into bounded, decode-interleaved pieces
                        # when prefill_chunk caps the per-dispatch width.
                        # chunk-on also routes TRICKLE-size admissions here
                        # (a one-row-masked window): a fat single admission
                        # must obey the same per-dispatch bound, else the
                        # staggered-completion steady state reintroduces
                        # exactly the TTFT stall the knob exists to cap
                        texts = np.zeros((B, self.text_seq_len), np.int32)
                        seeds = np.zeros((B,), np.int32)
                        n_rows = np.full((B,), self.n_steps, np.int32)
                        mask = np.zeros((B,), bool)
                        for slot, req in singles:
                            texts[slot] = self._pad_text(req.text)
                            seeds[slot] = req.seed
                            n_rows[slot] = self._n_tokens(req)
                            mask[slot] = True
                        if 0 < self.prefill_chunk < self.prefix_len:
                            chunk_jobs.append(_ChunkJob(
                                ids=self._remap_bos_host(texts),
                                seeds=seeds, n_rows=n_rows, mask=mask,
                                pairs=list(singles),
                                t0=time.perf_counter()))
                            pending.update(s for s, _ in singles)
                        else:
                            t0 = time.perf_counter()
                            state = self._refill_fn(self.params, state,
                                                    texts, seeds, n_rows,
                                                    mask)
                            t1 = time.perf_counter()
                            self.stats.refills += 1
                            # one shared prefill window, one span per
                            # admitted request (each request's timeline owns
                            # its prefill segment; dur is the host dispatch
                            # wall)
                            for slot, req in singles:
                                record_span("serve/prefill", t0, t1 - t0,
                                            request_id=req.request_id,
                                            trace_id=req.trace_id,
                                            mode="window")
                                row_t0[slot] = t1
                    elif singles:
                        # trickle admission (staggered completions, chunking
                        # off): per-row scatter-prefill, 1/B the window's
                        # compute
                        for slot, req in singles:
                            t0 = time.perf_counter()
                            state = self._refill_row_fn(
                                self.params, state,
                                self._pad_text(req.text)[None],
                                np.int32(req.seed),
                                np.int32(self._n_tokens(req)),
                                np.int32(slot))
                            t1 = time.perf_counter()
                            self.stats.refills += 1
                            record_span("serve/prefill", t0, t1 - t0,
                                        request_id=req.request_id,
                                        trace_id=req.trace_id, mode="row")
                            row_t0[slot] = t1
            # work-conservation sample: requests that were already queued
            # at the take instant and still went unplaced must leave every
            # slot busy, so occupancy is sampled exactly then (an idle slot
            # here is a real violation, not tautologically 1.0). A request
            # landing after the take is admitted next iteration and is
            # deliberately excluded — arrival-bound, not an idle-slot bug.
            backlog = (pre_q - admitted) > 0
            gauge_set("serve.queue_depth", float(queue.qsize()))
            gauge_set("serve.slot_occupancy", sched.occupancy)

            if chunk_jobs:
                # one bounded prefill window per iteration, so the decode
                # step below keeps interleaving — the TTFT-isolation bar
                state = self._dispatch_chunk(state, chunk_jobs, pending,
                                             row_t0)

            if not any(s not in pending for s in sched.active_slots()):
                if chunk_jobs:
                    continue          # keep driving the pending prefill
                if self._overflow:
                    continue          # free slots admit the deferred units
                if queue.drained:
                    break
                queue.wait_nonempty(timeout=poll_s)
                continue

            if backlog:
                self.stats.sample_occupancy(sched.occupancy)

            # chaos hook (graftfleet): an env-installed FaultPlan can
            # kill/hang/slow a REPLICA PROCESS at decode-iteration
            # granularity — mid-stream, between row commits — which is
            # what the fleet smoke's drain/kill scenarios script. One
            # module-global None check when chaos is off (the
            # BaseTrainer.fit precedent, serve-side).
            chaos_step_hook(self.stats.steps)

            toks, fins, qstats, state = self._step_fn(self.params, state)
            toks = np.asarray(toks)               # (K, B)
            fins = np.asarray(fins)
            # decode-quality stats ride the SAME host sync as the tokens
            # (empty dict when decode_health is off)
            q_ent = np.asarray(qstats["entropy"]) if qstats else None
            q_mass = np.asarray(qstats["topk_mass"]) if qstats else None
            now = time.perf_counter()
            for k in range(toks.shape[0]):
                active = [s for s in sched.active_slots()
                          if s not in pending]
                if not active:
                    break
                for slot in active:
                    req = sched.request_at(slot)
                    if req.first_token_at is None:
                        req.first_token_at = now
                    buf = buffers[slot]
                    buf.append(int(toks[k, slot]))
                    if q_ent is not None:
                        acc = qual.setdefault(slot, [0.0, 0.0, 0])
                        acc[0] += float(q_ent[k, slot])
                        acc[1] += float(q_mass[k, slot])
                        acc[2] += 1
                    if (len(buf) % self.row_len == 0
                            and req.request_id >= 0):
                        row = len(buf) // self.row_len - 1
                        # one committed grid row = one timeline segment
                        # (host-sync granularity: rows finishing inside one
                        # multi-step dispatch share its sync timestamp)
                        t0r = row_t0.get(slot, now)
                        record_span("serve/decode_row", t0r, now - t0r,
                                    request_id=req.request_id,
                                    trace_id=req.trace_id, row=row)
                        histogram_observe("serve.decode_row_seconds",
                                          now - t0r,
                                          trace_id=req.trace_id)
                        row_t0[slot] = now
                        if on_rows is not None:
                            on_rows(req, row, buf[row * self.row_len:])
                # synthetic CFG-null rows burn device work but emit no
                # caller-visible tokens — keep the throughput counter an
                # honest goodput number
                counter_add("serve.tokens_emitted_total",
                            float(sum(1 for s in active
                                      if sched.request_at(s).request_id
                                      >= 0)))
                for slot in active:
                    if not fins[k, slot]:
                        continue
                    req = sched.complete(slot)
                    if self.paged:
                        self._release_slot_blocks(slot)
                    if req.request_id < 0:
                        # synthetic CFG-null row: its tokens are bitwise
                        # duplicates of the cond partner's — nothing to
                        # surface, just free the slot
                        buffers.pop(slot, None)
                        qual.pop(slot, None)
                        row_t0.pop(slot, None)
                        continue
                    tail = len(buffers[slot]) % self.row_len
                    if tail:
                        # trailing partial row of a max_tokens request
                        t0r = row_t0.get(slot, now)
                        record_span("serve/decode_row", t0r, now - t0r,
                                    request_id=req.request_id,
                                    trace_id=req.trace_id,
                                    row=len(buffers[slot]) // self.row_len,
                                    partial=True)
                        if on_rows is not None:
                            on_rows(req, len(buffers[slot]) // self.row_len,
                                    buffers[slot][-tail:])
                    row_t0.pop(slot, None)
                    cr = CompletedRequest(
                        request_id=req.request_id,
                        tokens=np.asarray(buffers.pop(slot), np.int32),
                        seed=req.seed,
                        submitted_at=req.submitted_at,
                        admitted_at=req.admitted_at,
                        first_token_at=req.first_token_at,
                        completed_at=now)
                    if on_complete is not None:
                        on_complete(cr)
                    else:
                        completed.append(cr)
                    # per-request decode quality (graftpulse): means of the
                    # in-jit entropy/top-k taps plus the host-side
                    # repeated-token ratio. Per-request values travel as
                    # SPAN ARGS tagged with the trace_id (bounded ring) and
                    # as unlabeled aggregate gauges — never as metric
                    # labels, which would be unbounded Prometheus
                    # cardinality (graftlint: unbounded-metric-label)
                    q_args = {}
                    acc = qual.pop(slot, None)
                    if acc is not None and acc[2] > 0:
                        t = cr.tokens
                        rep = (float(np.mean(t[1:] == t[:-1]))
                               if t.shape[0] > 1 else 0.0)
                        q_args = {"entropy": round(acc[0] / acc[2], 4),
                                  "topk_mass": round(acc[1] / acc[2], 4),
                                  "repeat_ratio": round(rep, 4)}
                        gauge_set("health.decode_entropy", acc[0] / acc[2])
                        gauge_set("health.decode_topk_mass", acc[1] / acc[2])
                        gauge_set("health.decode_repeat_ratio", rep)
                        record_event("decode_quality",
                                     request_id=req.request_id,
                                     trace_id=req.trace_id, **q_args)
                    # retrospective spans: requests overlap, so the
                    # stack-based span() contract cannot hold — see
                    # obs.record_span
                    record_span("serve/request", req.admitted_at,
                                now - req.admitted_at,
                                request_id=req.request_id,
                                trace_id=req.trace_id,
                                tokens=int(cr.tokens.shape[0]), **q_args)
                    record_span("serve/request_ttft", req.submitted_at,
                                cr.ttft_s, request_id=req.request_id,
                                trace_id=req.trace_id)
                    # native histogram (graftlens): the latency SHAPE a
                    # single gauge cannot carry — p50/p95 render from the
                    # cumulative buckets (obs_report), fleet-wide because
                    # the collector sums buckets across processes
                    histogram_observe("serve.ttft_seconds", cr.ttft_s,
                                      trace_id=req.trace_id)
                    record_event("request_completed",
                                 request_id=req.request_id,
                                 trace_id=req.trace_id,
                                 latency_s=cr.latency_s)
                    counter_add("serve.requests_completed_total", 1.0)
                    gauge_set("serve.request_latency_s", cr.latency_s)
                self.stats.steps += 1
        self.stats.aborted_in_flight = [
            sched.request_at(s).request_id for s in sched.active_slots()
            if sched.request_at(s).request_id >= 0]
        return completed
