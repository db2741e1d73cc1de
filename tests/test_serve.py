"""Continuous-batching serve engine (dalle_tpu/serve): scheduling
invariants (work-conserving slots, FIFO fairness, drain semantics) and the
correctness bar speculative decode set — per-request outputs TOKEN-EXACT
against single-request ``generate_images_tokens`` under the same per-request
key, for any admission order."""

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dalle_tpu.config import DalleConfig
from dalle_tpu.models.dalle import DALLE, init_dalle
from dalle_tpu.serve import DecodeEngine, RequestQueue, SlotScheduler

# ceiling = the module's cold full-run total (re-measured 745 with the
# graftloom shared-prefix + chunked-prefill matrix; was 722 pre-graftloom)
# + ~15% slack for cross-jax-version compile-count variance (the
# test_speculative convention). Since PR 7 engines over the same model
# object share compiled programs per config key (serve/engine.py
# _shared_programs), so same-config tests stopped paying repeat compiles;
# an engine change that recompiles per admission, per slot count or per
# engine INSTANCE would blow straight through this.
pytestmark = pytest.mark.recompile_budget(860)

CFG = dict(num_text_tokens=32, text_seq_len=6, dim=32, depth=2, heads=2,
           dim_head=16, image_size=16, image_vocab_size=24, image_fmap_size=4)

TEXTS = [np.array([3, 4, 5, 0, 0, 0], np.int32),
         np.array([7, 8, 0, 0, 0, 0], np.int32),
         np.array([9, 1, 2, 3, 0, 0], np.int32),
         np.array([5, 5, 0, 0, 0, 0], np.int32),
         np.array([1, 2, 3, 4, 5, 6], np.int32)]


@pytest.fixture(scope="module")
def model_params():
    cfg = DalleConfig(**CFG)
    return init_dalle(cfg, jax.random.PRNGKey(0), batch=2)


@pytest.fixture(scope="module")
def refs100(model_params):
    """Sequential single-request references, seed 100+i per TEXTS[i] —
    shared by every f32 default-mode exactness test (eager references are
    the expensive half of these tests on the 1-core CI box)."""
    model, params = model_params
    return {i: _reference(model, params, t, 100 + i)
            for i, t in enumerate(TEXTS)}


def _reference(model, params, text, seed, **kw):
    ids = model.apply(params, jnp.asarray(text[None]),
                      jax.random.PRNGKey(seed),
                      method=DALLE.generate_images_tokens, **kw)
    return np.asarray(ids[0])


# ---------------------------------------------------------------------------
# host-side pieces (no jax)
# ---------------------------------------------------------------------------

def test_queue_fifo_close_drain():
    q = RequestQueue()
    r1 = q.submit(np.zeros(6, np.int32), seed=1)
    r2 = q.submit(np.zeros(6, np.int32), seed=2)
    assert q.qsize() == 2 and not q.drained
    taken = q.take(1)
    assert [r.request_id for r in taken] == [r1.request_id]
    q.close()
    with pytest.raises(RuntimeError):
        q.submit(np.zeros(6, np.int32), seed=3)
    assert not q.drained                      # r2 still queued
    assert q.take(5) == [r2]
    assert q.drained
    assert q.wait_nonempty(timeout=0.01) is False   # closed+empty: no block


def test_queue_rejects_stale_explicit_ids():
    """A duplicate explicit id would silently alias two requests' results
    everywhere completions are keyed by id — the queue rejects any id at or
    below the issued high-water mark instead of tracking ids forever."""
    q = RequestQueue()
    q.submit(np.zeros(6, np.int32), seed=1)            # auto id 0
    with pytest.raises(ValueError):
        q.submit(np.zeros(6, np.int32), seed=2, request_id=0)
    q.submit(np.zeros(6, np.int32), seed=3, request_id=7)
    with pytest.raises(ValueError):
        q.submit(np.zeros(6, np.int32), seed=4, request_id=5)
    nxt = q.submit(np.zeros(6, np.int32), seed=5)      # auto resumes past 7
    assert nxt.request_id == 8
    with pytest.raises(ValueError):
        q.submit(np.zeros(6, np.int32), seed=6, max_tokens=0)


def test_queue_bounded_rejects_on_full():
    """maxsize bounds the BACKLOG: submit on a full queue raises QueueFull
    (the gateway's 429) instead of growing without bound, FIFO order is
    untouched, and taking frees capacity."""
    from dalle_tpu.serve import QueueFull
    q = RequestQueue(maxsize=2)
    r1 = q.submit(np.zeros(6, np.int32), seed=1)
    q.submit(np.zeros(6, np.int32), seed=2)
    with pytest.raises(QueueFull):
        q.submit(np.zeros(6, np.int32), seed=3)
    assert q.qsize() == 2                      # rejected, not enqueued
    assert q.take(1) == [r1]                   # FIFO across the rejection
    r4 = q.submit(np.zeros(6, np.int32), seed=4)   # take freed capacity
    assert [r.request_id for r in q.take(5)][-1] == r4.request_id
    with pytest.raises(ValueError):
        RequestQueue(maxsize=0)


def test_policy_queue_fifo_default_matches_base():
    """A PolicyQueue without an explicit policy is bit-identical to the
    FIFO base: same take order, nothing shed — the pinned default."""
    from dalle_tpu.serve import PolicyQueue
    pq = PolicyQueue(maxsize=3)
    ids = [pq.submit(np.zeros(6, np.int32), seed=i,
                     priority=i, deadline_at=None).request_id
           for i in range(3)]
    assert [r.request_id for r in pq.take(2)] == ids[:2]
    assert [r.request_id for r in pq.take(2)] == ids[2:]
    assert pq.shed_total == 0


def test_policy_queue_priority_deadline_order_and_shed():
    """PriorityDeadlinePolicy: priority tiers first, then earliest
    deadline, then FIFO; an already-expired request is shed at take time
    and handed to on_shed, never to a slot."""
    from dalle_tpu.serve import PolicyQueue, PriorityDeadlinePolicy
    shed = []
    pq = PolicyQueue(policy=PriorityDeadlinePolicy(),
                     on_shed=shed.append)
    now = time.perf_counter()
    lo = pq.submit(np.zeros(6, np.int32), seed=1)               # prio 0
    hi_late = pq.submit(np.zeros(6, np.int32), seed=2, priority=5)
    hi_soon = pq.submit(np.zeros(6, np.int32), seed=3, priority=5,
                        deadline_at=now + 100)
    expired = pq.submit(np.zeros(6, np.int32), seed=4,
                        deadline_at=now - 0.1)
    got = pq.take(2)
    # same tier: the deadlined request precedes the open-ended one
    assert [r.request_id for r in got] == [hi_soon.request_id,
                                           hi_late.request_id]
    assert [r.request_id for r in shed] == [expired.request_id]
    assert pq.shed_total == 1
    assert [r.request_id for r in pq.take(5)] == [lo.request_id]


def test_scheduler_invariants():
    from dalle_tpu.serve.queue import Request
    s = SlotScheduler(3)
    reqs = [Request(request_id=i, text=np.zeros(4, np.int32), seed=i)
            for i in range(5)]
    pairs = s.admit(reqs[:2])
    assert [p[0] for p in pairs] == [0, 1] and s.occupancy == 2 / 3
    # FIFO pairing: next admission lands in the remaining slot, in order
    s.admit(reqs[2:3])
    assert s.occupancy == 1.0 and s.free_slots() == []
    with pytest.raises(ValueError):
        s.admit(reqs[3:5])                    # over-admission must raise
    done = s.complete(1)
    assert done.request_id == 1 and s.free_slots() == [1]
    with pytest.raises(ValueError):
        s.complete(1)                         # double-complete must raise
    s.admit(reqs[3:4])
    assert s.admission_order == [0, 1, 2, 3]  # strict submission order


# ---------------------------------------------------------------------------
# engine: token-exactness for ragged admission orders
# ---------------------------------------------------------------------------

def test_engine_token_exact_ragged_admission(model_params, refs100):
    """5 requests through 2 shared-cache slots: admissions interleave with
    mid-flight decode (3 refill waves), yet every request's tokens equal
    single-request generation under its own key — the refill window and
    per-row decode change nothing another row can observe."""
    model, params = model_params
    refs = refs100
    q = RequestQueue()
    for i, t in enumerate(TEXTS):
        q.submit(t, seed=100 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, params, slots=2)
    done = eng.run(q)
    assert sorted(c.request_id for c in done) == list(range(5))
    for c in done:
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])
        assert c.admitted_at >= c.submitted_at
        assert c.first_token_at >= c.admitted_at
        assert c.completed_at >= c.first_token_at
    # work-conserving: while the queue held requests, both slots were busy —
    # and the bar is non-vacuous (backlogged iterations really were sampled)
    assert eng.stats.occupancy_while_queued == 1.0
    assert eng.stats.occupancy_n > 0
    assert eng.stats.refills == 3             # [0,1], [2], then [3,4]


def test_engine_on_complete_streams_without_accumulating(model_params,
                                                         refs100):
    """Long-lived serving memory contract: with ``on_complete`` every
    completion is delivered as its last token lands and run() accumulates
    nothing — results are identical to the drain-and-return mode."""
    model, params = model_params
    refs = refs100
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:3]):
        q.submit(t, seed=100 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, params, slots=2)
    streamed = []
    returned = eng.run(q, on_complete=streamed.append)
    assert returned == []
    assert sorted(c.request_id for c in streamed) == [0, 1, 2]
    for c in streamed:
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])


def test_engine_use_kernel_pin_plumbs_and_stays_exact(model_params):
    """use_kernel=False pins dense attends through every serve layer (and
    generate_images_tokens accepts the same pin for the reference side) —
    on the CPU mesh auto already resolves dense, so this checks the plumb
    and that the pinned engine keeps the exactness contract."""
    model, params = model_params
    refs = {i: _reference(model, params, t, 100 + i, use_kernel=False)
            for i, t in enumerate(TEXTS[:3])}
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:3]):
        q.submit(t, seed=100 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, params, slots=2, use_kernel=False)
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])


@pytest.mark.slow  # ~13s; int8w (the engine DEFAULT since graftnum) covers
# the int8-KV machinery fast-tier below — the standalone bf16+int8KV+approx
# top-k mode keeps its exactness check in the slow tier
def test_engine_int8_cache_exact(model_params):
    """bf16 params + int8 KV + approximate top-k — the pre-graftnum serving
    fast path — stays token-exact vs the same-mode sequential reference."""
    from dalle_tpu.train.train_state import cast_floating
    model, params = model_params
    bf16 = cast_floating(params, jnp.bfloat16)
    refs = {i: _reference(model, bf16, t, 7 + i, cache_dtype=jnp.int8,
                          topk_approx=True, temperature=0.5)
            for i, t in enumerate(TEXTS[:3])}
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:3]):
        q.submit(t, seed=7 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, bf16, slots=2, cache_dtype=jnp.int8,
                       topk_approx=True, temperature=0.5)
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])


def test_engine_int8w_default_exact_bulk_and_trickle(model_params):
    """int8 weights + int8 KV — the serve-engine DEFAULT since the
    precision-flow audit certified it: tokens stay bit-exact vs same-mode
    single-request generation through BOTH admission paths. slots=2 with a
    closed 4-deep queue forces bulk refill windows; slots=3 with ragged
    per-request lengths staggers completions through the per-row trickle
    scatter-prefill."""
    from dalle_tpu.ops.quantize_weights import quantize_params_int8
    model, params = model_params
    qv = quantize_params_int8(params)
    refs = {i: _reference(model, qv, t, 300 + i, cache_dtype=jnp.int8)
            for i, t in enumerate(TEXTS)}

    # bulk: every admission covers >= half the slots -> refill window
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:4]):
        q.submit(t, seed=300 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, qv, slots=2, cache_dtype=jnp.int8)
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])

    # trickle: ragged lengths free slots one at a time mid-flight
    lens = [16, 3, 9, 1, 12]
    q = RequestQueue()
    for i, t in enumerate(TEXTS):
        q.submit(t, seed=300 + i, request_id=i, max_tokens=lens[i])
    q.close()
    eng = DecodeEngine(model, qv, slots=3, cache_dtype=jnp.int8)
    done = eng.run(q)
    assert sorted(c.request_id for c in done) == list(range(5))
    for c in done:
        assert c.tokens.shape == (lens[c.request_id],)
        np.testing.assert_array_equal(c.tokens,
                                      refs[c.request_id][:lens[c.request_id]])


def test_wrapper_serve_engine_defaults_to_int8w(model_params):
    """DalleWithVae.serve_engine() with no precision argument builds the
    int8-weights + int8-KV engine from the wrapper's cached derived tree,
    and its requests match the wrapper-mode sequential reference exactly."""
    from dalle_tpu.models.wrapper import DalleWithVae
    model, params = model_params
    dv = DalleWithVae(model, params, None)   # vae unused on the token path
    eng = dv.serve_engine(slots=2)
    assert eng.cache_dtype == jnp.int8
    assert "quant" in eng.params             # per-channel scales present
    int8_leaves = [l for l in jax.tree_util.tree_leaves(eng.params["params"])
                   if hasattr(l, "dtype") and l.dtype == jnp.int8]
    assert int8_leaves
    # the derived tree is the wrapper's cached int8w mode — a second engine
    # must reuse it, not re-quantize
    assert dv.serve_engine(slots=2).params is eng.params

    refs = {i: _reference(model, eng.params, t, 500 + i,
                          cache_dtype=jnp.int8)
            for i, t in enumerate(TEXTS[:2])}
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:2]):
        q.submit(t, seed=500 + i, request_id=i)
    q.close()
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])


def test_engine_axial_posemb_exact():
    """rotary off → the per-row axial positional-embedding gather path."""
    cfg = DalleConfig(**{**CFG, "rotary_emb": False})
    model, params = init_dalle(cfg, jax.random.PRNGKey(0), batch=2)
    refs = {i: _reference(model, params, t, 40 + i)
            for i, t in enumerate(TEXTS[:3])}
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:3]):
        q.submit(t, seed=40 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, params, slots=2)
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])


def test_engine_streaming_submissions(model_params):
    """Producer submits from another thread while the engine runs: no
    drain-the-batch wait — late requests slot into freed rows, all complete
    exactly, in FIFO admission order."""
    model, params = model_params
    refs = {i: _reference(model, params, t, 60 + i)
            for i, t in enumerate(TEXTS)}
    q = RequestQueue()
    q.submit(TEXTS[0], seed=60, request_id=0)

    def producer():
        for i in range(1, 5):
            time.sleep(0.01)
            q.submit(TEXTS[i], seed=60 + i, request_id=i)
        q.close()

    t = threading.Thread(target=producer)
    t.start()
    eng = DecodeEngine(model, params, slots=2)
    done = eng.run(q)
    t.join()
    assert sorted(c.request_id for c in done) == list(range(5))
    for c in done:
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])


# ---------------------------------------------------------------------------
# engine: gates, exhaustion, observability
# ---------------------------------------------------------------------------

def test_engine_ragged_lengths_trickle_admission(model_params):
    """Per-request max_tokens (ragged service demand) + slots=3 so
    staggered completions admit through the per-row scatter-prefill path
    AND the bulk refill window: each request's tokens equal the FIRST n of
    its full single-request generation, and short rows free their slot
    early (multi-step sync > 1 exercises the K-granular refill too)."""
    model, params = model_params
    full = {i: _reference(model, params, t, 80 + i)
            for i, t in enumerate(TEXTS)}
    lens = [16, 3, 9, 1, 12]
    q = RequestQueue()
    for i, t in enumerate(TEXTS):
        q.submit(t, seed=80 + i, request_id=i, max_tokens=lens[i])
    q.close()
    eng = DecodeEngine(model, params, slots=3, steps_per_sync=2)
    done = eng.run(q)
    assert sorted(c.request_id for c in done) == list(range(5))
    for c in done:
        assert c.tokens.shape == (lens[c.request_id],)
        np.testing.assert_array_equal(c.tokens,
                                      full[c.request_id][:lens[c.request_id]])


def test_engine_rejects_sparse_config():
    cfg = DalleConfig(**{**CFG, "attn_types": ("full", "axial_row")})
    model, params = init_dalle(cfg, jax.random.PRNGKey(0), batch=2)
    with pytest.raises(ValueError, match="full attention"):
        DecodeEngine(model, params, slots=2)


def test_engine_max_steps_cutoff(model_params):
    """max_steps bounds the loop (bench/smoke harness knob): the engine
    returns only fully completed requests, never a truncated token list."""
    model, params = model_params
    q = RequestQueue()
    for i, t in enumerate(TEXTS[:2]):
        q.submit(t, seed=i, request_id=i)
    q.close()
    eng = DecodeEngine(model, params, slots=2)
    done = eng.run(q, max_steps=5)
    assert done == [] and eng.stats.steps == 5
    # the cutoff is not a graceful drain: consumed-but-unfinished requests
    # are reported, never silently dropped
    assert sorted(eng.stats.aborted_in_flight) == [0, 1]


def test_engine_spans_and_gauges(model_params):
    """Tracing on: every completed request leaves a serve/request +
    serve/request_ttft span (request_id arg, sane durations) and the
    queue-depth / slot-occupancy gauges and token counters are live."""
    from dalle_tpu import obs
    model, params = model_params
    tracer = obs.configure()
    try:
        q = RequestQueue()
        for i, t in enumerate(TEXTS[:3]):
            q.submit(t, seed=20 + i, request_id=i)
        q.close()
        eng = DecodeEngine(model, params, slots=2)
        done = eng.run(q)
        spans = tracer.snapshot_spans()
        by_name = {}
        for name, rel, dur, tid, depth, args, *_ in spans:
            by_name.setdefault(name, []).append((dur, args))
        for want in ("serve/request", "serve/request_ttft",
                     "serve/request_queue_wait"):
            got = by_name.get(want, [])
            assert len(got) == 3, f"missing {want} spans: {by_name.keys()}"
            ids = sorted(a["request_id"] for _, a in got)
            assert ids == [0, 1, 2]
            assert all(d >= 0 for d, _ in got)
        # queue wait ≤ TTFT per request: the wait span measures exactly the
        # submission→admission segment of the TTFT span
        qw = {a["request_id"]: d
              for d, a in by_name["serve/request_queue_wait"]}
        tt = {a["request_id"]: d for d, a in by_name["serve/request_ttft"]}
        assert all(qw[i] <= tt[i] for i in qw)
        m = obs.metrics_snapshot()
        assert m["serve.requests_completed_total"] == 3
        assert m["serve.tokens_emitted_total"] == sum(
            c.tokens.shape[0] for c in done)
        assert m["serve.slot_occupancy"] >= 0
        assert m["serve.queue_depth"] == 0
        assert m["serve.queue_wait_s"] >= 0
    finally:
        obs.disable()


# ---------------------------------------------------------------------------
# shared-prefix candidate groups + chunked prefill (graftloom)
# ---------------------------------------------------------------------------

def _submit_group(q, text, base_seed, n, *, gid, start_id, max_tokens=None):
    """The /v1/images fan-out shape: candidate i samples under
    base_seed + i, all members carry one group_id and identical text."""
    for i in range(n):
        q.submit(text, seed=base_seed + i, request_id=start_id + i,
                 max_tokens=max_tokens, group_id=gid, group_size=n,
                 group_index=i)


@pytest.fixture(scope="module")
def int8w_params(model_params):
    """One int8-quantized tree shared by every int8w graftloom test (the
    eager quantize pass is not free on the 1-core CI box)."""
    from dalle_tpu.ops.quantize_weights import quantize_params_int8
    return quantize_params_int8(model_params[1])


@pytest.fixture(scope="module")
def group_refs(model_params):
    """Sequential single-request references for the f32 group tests:
    TEXTS[0] under seeds 700..702 — computed once, sliced per test."""
    model, params = model_params
    return [_reference(model, params, TEXTS[0], 700 + i) for i in range(3)]


def test_engine_shared_prefix_group_exact_and_split_demotes(model_params,
                                                            group_refs):
    """Shared-prefix admission holds the PR4 bar, both when a group fits
    one pass and when it splits. (a) Both candidates of ONE prompt
    admitted together pay a single shared b=1 prefill (1 refill total, 1
    prefill saved), yet each candidate's tokens are bitwise its
    INDEPENDENT single-request generation under its own seed. (b) A
    3-candidate group through the same 2 slots: the first pass admits two
    members (cohort, shared prefill), the straggler lands alone in a later
    pass and demotes to the single trickle path — sharing degrades to
    fewer saved prefills, never to different bits."""
    model, params = model_params
    refs = group_refs

    q = RequestQueue()
    _submit_group(q, TEXTS[0], 700, 2, gid=1, start_id=0)
    q.close()
    eng = DecodeEngine(model, params, slots=2)
    done = eng.run(q)
    assert sorted(c.request_id for c in done) == [0, 1]
    for c in done:
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])
    assert eng.stats.shared_refills == 1
    assert eng.stats.shared_prefills_saved == 1
    assert eng.stats.refills == 1             # ONE admission dispatch total

    q = RequestQueue()
    _submit_group(q, TEXTS[0], 700, 3, gid=2, start_id=0)
    q.close()
    eng = DecodeEngine(model, params, slots=2)
    done = eng.run(q)
    assert sorted(c.request_id for c in done) == [0, 1, 2]
    for c in done:
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])
    assert eng.stats.shared_refills == 1      # the pass-1 pair
    assert eng.stats.shared_prefills_saved == 1


def test_engine_shared_prefix_cohort_beside_trickle_single(model_params,
                                                           group_refs):
    """One admission pass holding a cohort AND a lone single: the cohort
    rides the shared prefill, the single rides the per-row trickle path,
    and a partial-grid group (max_tokens) gets the exact reference prefix.
    slots=3 + steps_per_sync=2 reuses the ragged-admission programs."""
    model, params = model_params
    sref = _reference(model, params, TEXTS[1], 720)
    q = RequestQueue()
    q.submit(TEXTS[1], seed=720, request_id=0, max_tokens=9)
    _submit_group(q, TEXTS[0], 700, 2, gid=2, start_id=1, max_tokens=6)
    q.close()
    eng = DecodeEngine(model, params, slots=3, steps_per_sync=2)
    done = {c.request_id: c for c in eng.run(q)}
    assert sorted(done) == [0, 1, 2]
    np.testing.assert_array_equal(done[0].tokens, sref[:9])
    for i in range(2):
        np.testing.assert_array_equal(done[1 + i].tokens,
                                      group_refs[i][:6])
    assert eng.stats.shared_refills == 1
    assert eng.stats.shared_prefills_saved == 1


def test_engine_group_mismatched_text_demoted_not_shared(model_params):
    """Members claiming one group_id but carrying DIFFERENT texts (a misuse
    the gateway never produces) must not be prefilled with the first
    member's prompt: they demote to singles and produce exactly what the
    same two UNGROUPED requests produce (both demote to the identical
    window-admission program, so the comparison is bitwise by
    construction — and shared_refills stays 0)."""
    model, params = model_params

    def run(gid):
        q = RequestQueue()
        q.submit(TEXTS[0], seed=740, request_id=0, group_id=gid,
                 group_size=2, group_index=0)
        q.submit(TEXTS[1], seed=741, request_id=1, group_id=gid,
                 group_size=2, group_index=1)
        q.close()
        eng = DecodeEngine(model, params, slots=2)
        return {c.request_id: c.tokens for c in eng.run(q)}, eng.stats

    grouped, gstats = run(9)
    plain, _ = run(None)
    assert gstats.shared_refills == 0
    for i in (0, 1):
        np.testing.assert_array_equal(grouped[i], plain[i])


def test_engine_shared_prefix_int8w_and_int8kv_exact(model_params,
                                                     int8w_params):
    """The shared prefill holds the PR4 bar in the quantized serving modes:
    int8 weights + int8 KV (the audited default) and bf16 + int8 KV with
    approximate top-k — candidate tokens bitwise the same-mode independent
    references. The prefix KV depends only on the text, so broadcasting
    quantized kv AND scale rows is exact by construction.

    (Why the bf16 mode is pinned at the STATE level instead of via token
    references: the bf16 fast path has a PRE-existing, graftloom-
    independent low-bit wobble — the b=1 JITTED prefill can differ from
    the EAGER sequential reference in last-place bf16 bits on the CPU
    backend, flipping a rare near-tie sample. The per-row trickle path
    shows the identical flip with no groups involved (e.g. a lone seed-760
    request on this text through slots=3), so a bf16 token-vs-reference
    check here would test that wobble, not sharing. The sharing claim —
    shared admission ≡ per-row admission, every cache/scale/logits/key
    bit, for BOTH jitted programs — is seed-independent and pinned in
    test_engine_shared_refill_state_bitwise_eq_row_path on the int8w
    default, whose activations are the same bf16.)"""
    model, params = model_params

    qv = int8w_params
    refs = {i: _reference(model, qv, TEXTS[2], 750 + i,
                          cache_dtype=jnp.int8) for i in range(2)}
    q = RequestQueue()
    _submit_group(q, TEXTS[2], 750, 2, gid=4, start_id=0)
    q.close()
    eng = DecodeEngine(model, qv, slots=2, cache_dtype=jnp.int8)
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])
    assert eng.stats.shared_refills == 1

    # chunked prefill through the same quantized mode (reusing the refs):
    # every chunk writes the same int8 cache rows + scale planes the
    # one-shot window would, so tokens stay bit-exact — 7 positions in 2s
    # dispatch as 2,2,2,1
    q = RequestQueue()
    for i in range(2):
        q.submit(TEXTS[2], seed=750 + i, request_id=i)
    q.close()
    eng = DecodeEngine(model, qv, slots=2, cache_dtype=jnp.int8,
                       prefill_chunk=2)
    for c in eng.run(q):
        np.testing.assert_array_equal(c.tokens, refs[c.request_id])
    assert eng.stats.prefill_chunks == 4


def test_engine_shared_refill_state_bitwise_eq_row_path(model_params,
                                                        int8w_params):
    """The seed-independent sharing invariant, on the REAL jitted serving
    programs: ONE shared b=1 prefill broadcast into N sibling rows
    produces EXACTLY the engine state N per-row scatter-prefills produce —
    every KV byte, every int8 scale plane, the first-token logits and both
    RNG lanes. Decode is the same program either way, so a candidate
    stream cannot diverge from ungrouped admission no matter the seed.
    Checked in the int8w+int8kv serve DEFAULT — bf16 activations, so the
    fragile-tie mode's logits dtype merge is covered, with quantized kv
    AND scale planes to broadcast (and the engine config shares its
    compiled programs with the token test above)."""
    model, params = model_params
    eng = DecodeEngine(model, int8w_params, slots=2, cache_dtype=jnp.int8)
    text1 = jnp.asarray(eng._pad_text(TEXTS[2])[None])
    seeds = jnp.asarray(np.array([760, 761], np.int32))
    n_rows = jnp.asarray(np.full((2,), eng.n_steps, np.int32))
    mask = jnp.asarray(np.ones((2,), bool))
    st_sh = eng._refill_shared_fn(eng.params, eng._init_state(), text1,
                                  seeds, n_rows, mask)
    st_row = eng._init_state()
    for row, s in enumerate((760, 761)):
        st_row = eng._refill_row_fn(eng.params, st_row, text1,
                                    jnp.int32(s), jnp.int32(eng.n_steps),
                                    jnp.int32(row))
    for name in st_sh["cache"]:
        a, b = st_sh["cache"][name], st_row["cache"][name]
        np.testing.assert_array_equal(np.asarray(a.kv), np.asarray(b.kv))
        if a.scale is not None:
            np.testing.assert_array_equal(np.asarray(a.scale),
                                          np.asarray(b.scale))
    for k in ("logits", "cur_key", "orig_key", "t_idx", "n_row", "active"):
        np.testing.assert_array_equal(np.asarray(st_sh[k]),
                                      np.asarray(st_row[k]))


def test_engine_chunked_prefill_exact_and_interleaves(model_params):
    """prefill_chunk=3 splits the 7-position window prefill (<bos> + 6
    text) into 3+3+1 chunks: (a) chunked tokens are BITWISE the unchunked
    engine's for the same workload (the satellite's chunked ≡ unchunked
    claim; the unchunked engine is itself pinned ≡ sequential generation
    by the admission tests above); (b) the TTFT-isolation property — a
    chunked admission arriving beside a still-decoding row dispatches its
    chunks interleaved with that row's decode steps (the step counter
    strictly advances between chunks), so a fat admission can't stall a
    neighbor for its whole prompt length. (prefill_chunk=0 engines never
    build chunk jobs — their host loop and pinned programs are the
    pre-graftloom ones, which the serve_refill/serve_decode graftir
    goldens hold byte-identical.)"""
    from dalle_tpu import obs
    model, params = model_params

    # r0 decodes the full grid; r1 frees its slot after 2 tokens so the
    # queued r2 admits (chunked) while r0 still has ~14 steps to go
    def run(prefill_chunk):
        q = RequestQueue()
        q.submit(TEXTS[0], seed=770, request_id=0)
        q.submit(TEXTS[1], seed=771, request_id=1, max_tokens=2)
        q.submit(TEXTS[2], seed=772, request_id=2)
        q.close()
        eng = DecodeEngine(model, params, slots=2,
                           prefill_chunk=prefill_chunk)
        return {c.request_id: c for c in eng.run(q)}, eng

    plain, _ = run(0)
    tracer = obs.configure()
    try:
        done, eng = run(3)
        chunk_spans = [args for name, _r, _d, _t, _dep, args, *_
                       in tracer.snapshot_spans()
                       if name == "serve/prefill_chunk"]
    finally:
        obs.disable()
    assert sorted(done) == [0, 1, 2]
    for i in range(3):
        np.testing.assert_array_equal(done[i].tokens, plain[i].tokens)
    assert done[1].tokens.shape == (2,)
    # two chunked admissions ([r0,r1] window, then [r2]) of 3 chunks each
    assert eng.stats.prefill_chunks == 6
    assert [s["start"] for s in chunk_spans] == [0, 3, 6, 0, 3, 6]
    assert [s["width"] for s in chunk_spans] == [3, 3, 1, 3, 3, 1]
    # isolation: r2's chunks (the last 3) dispatched with r0 mid-decode —
    # decode steps landed between every pair of consecutive chunks
    steps = [s["step"] for s in chunk_spans[3:]]
    assert steps[0] < steps[1] < steps[2]

    # TRICKLE regime (slots=3): a later single admission below the window
    # threshold (2*1 < 3) must ALSO chunk — it becomes a one-row-masked
    # window job, not an unbounded one-shot row prefill — and its tokens
    # stay bitwise the chunk-off engine's (whose trickle path is pinned ≡
    # sequential generation by the ragged-admission test above)
    def run3(prefill_chunk):
        q = RequestQueue()
        q.submit(TEXTS[0], seed=780, request_id=0)
        q.submit(TEXTS[1], seed=781, request_id=1, max_tokens=2)
        q.submit(TEXTS[2], seed=782, request_id=2, max_tokens=2)
        q.submit(TEXTS[3], seed=783, request_id=3)
        q.close()
        eng = DecodeEngine(model, params, slots=3,
                           prefill_chunk=prefill_chunk)
        return {c.request_id: c for c in eng.run(q)}, eng

    plain3, off_eng = run3(0)
    assert off_eng.stats.prefill_chunks == 0
    done3, on_eng = run3(3)
    assert sorted(done3) == [0, 1, 2, 3]
    for i in range(4):
        np.testing.assert_array_equal(done3[i].tokens, plain3[i].tokens)
    # [r0,r1,r2] window (3 chunks) + r3's one-row trickle job (3 chunks)
    assert on_eng.stats.prefill_chunks == 6


def test_engine_decode_health_exact_with_quality_telemetry(model_params):
    """graftpulse decode-quality taps (engine decode_health=True): tokens
    stay BIT-exact vs the untapped engine and the single-request reference
    (the taps read the logits, consume no rng), and each completed request's
    serve/request span carries entropy / topk_mass / repeat_ratio args while
    the aggregate dalle_health_decode_* gauges go live."""
    import math
    from dalle_tpu import obs
    model, params = model_params
    refs = {i: _reference(model, params, t, 40 + i)
            for i, t in enumerate(TEXTS[:3])}

    def run(decode_health):
        q = RequestQueue()
        for i, t in enumerate(TEXTS[:3]):
            q.submit(t, seed=40 + i, request_id=i)
        q.close()
        eng = DecodeEngine(model, params, slots=2,
                           decode_health=decode_health)
        return eng.run(q)

    plain = {c.request_id: c.tokens for c in run(False)}
    tracer = obs.configure()
    try:
        tapped = run(True)
        for c in tapped:
            np.testing.assert_array_equal(c.tokens, refs[c.request_id])
            np.testing.assert_array_equal(c.tokens, plain[c.request_id])
        qspans = [args for name, _r, _d, _t, _dep, args, *_
                  in tracer.snapshot_spans() if name == "serve/request"]
        assert len(qspans) == 3
        for args in qspans:
            assert math.isfinite(args["entropy"]) and args["entropy"] >= 0
            assert 0.0 <= args["topk_mass"] <= 1.0 + 1e-6
            assert 0.0 <= args["repeat_ratio"] <= 1.0
            assert "trace_id" in args   # per-request values ride span args,
            # never metric labels (graftlint: unbounded-metric-label)
        m = obs.metrics_snapshot()
        for g in ("health.decode_entropy", "health.decode_topk_mass",
                  "health.decode_repeat_ratio"):
            assert g in m, g
        assert not any("{" in k and "trace_id" in k for k in m)
    finally:
        obs.disable()
