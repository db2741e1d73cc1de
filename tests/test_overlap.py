"""PR3 host-overlap machinery on the CPU mesh: device prefetch semantics,
on-device rollback snapshots (donation-safe, bit-exact), async checkpointing
(drain-on-close, rotation with in-flight writes, incomplete-step hygiene),
and fit()'s late metrics fetch. See docs/PERFORMANCE.md."""

import os
import time

import jax
import numpy as np
import pytest

from dalle_tpu.config import DalleConfig, MeshConfig, ObsConfig, TrainConfig
from dalle_tpu.data.device_prefetch import DevicePrefetcher, prefetch_to_device
from dalle_tpu.parallel.mesh import build_mesh
from dalle_tpu.train.checkpoints import CheckpointManager
from dalle_tpu.train.trainer_dalle import DalleTrainer

# recompilation budget (conftest guard): the trainer tests reuse the shared
# TINY program (compiled by earlier modules when run as a suite) plus the
# tree-copy/rollback programs; standalone cold total measured ~140
pytestmark = pytest.mark.recompile_budget(200)

TINY = DalleConfig(num_text_tokens=32, text_seq_len=8, dim=32, depth=2,
                   heads=2, dim_head=16, image_size=16, image_vocab_size=32,
                   image_fmap_size=4)


def _tc(tmp_path, **kw):
    kw.setdefault("batch_size", 8)
    kw.setdefault("preflight_checkpoint", False)
    kw.setdefault("mesh", MeshConfig(dp=4, fsdp=2))
    return TrainConfig(checkpoint_dir=str(tmp_path), **kw)


def _batch(rng, n=8):
    return (rng.randint(1, TINY.num_text_tokens, (n, TINY.text_seq_len)),
            rng.randint(0, TINY.image_vocab_size, (n, TINY.image_seq_len)))


# -- device prefetch semantics ------------------------------------------------

def test_prefetch_ordering_and_put_application():
    log = []

    def put(x):
        log.append(("put", x))
        return x * 10

    pf = DevicePrefetcher(iter(range(6)), put, depth=2)
    assert list(pf) == [0, 10, 20, 30, 40, 50]
    assert [x for _, x in log] == list(range(6))


def test_prefetch_runs_ahead_by_depth():
    """Pulls from the source lead the consumer by `depth` items — the
    double-buffering contract (batch N+1..N+depth are placed while N runs)."""
    events = []

    def src():
        for i in range(5):
            events.append(("pull", i))
            yield i

    pf = DevicePrefetcher(src(), lambda x: x, depth=2)
    out0 = next(pf)
    assert out0 == 0
    # first consume forced pulls of items 0 AND 1 (depth=2 in flight)
    assert events == [("pull", 0), ("pull", 1)]
    next(pf)
    assert events[-1] == ("pull", 2)


def test_prefetch_exhaustion_drains_buffer():
    pf = DevicePrefetcher(iter([1, 2, 3]), lambda x: x, depth=8)
    assert list(pf) == [1, 2, 3]
    with pytest.raises(StopIteration):
        next(pf)


def test_prefetch_source_error_after_buffered_items():
    """An iterator error is held until the good (already-put) items drain."""
    def src():
        yield 1
        yield 2
        raise RuntimeError("boom")

    pf = DevicePrefetcher(src(), lambda x: x, depth=4)
    assert next(pf) == 1
    assert next(pf) == 2
    with pytest.raises(RuntimeError, match="boom"):
        next(pf)


def test_prefetch_put_error_propagates_in_order():
    def put(x):
        if x == 2:
            raise ValueError("bad put")
        return x

    pf = DevicePrefetcher(iter([0, 1, 2, 3]), put, depth=2)
    assert next(pf) == 0
    assert next(pf) == 1
    with pytest.raises(ValueError, match="bad put"):
        list(pf)


def test_prefetch_to_device_places_on_mesh(mesh8):
    batches = [np.ones((8, 4), np.float32) * i for i in range(3)]
    out = list(prefetch_to_device(iter(batches), mesh8, depth=2))
    assert len(out) == 3
    assert all(isinstance(x, jax.Array) for x in out)
    from jax.sharding import PartitionSpec as P
    assert out[0].sharding.spec == P(("dp", "fsdp"), None)
    np.testing.assert_array_equal(np.asarray(out[2]), batches[2])


def test_prefetch_to_device_requires_mesh_or_put():
    with pytest.raises(ValueError):
        prefetch_to_device(iter([1]))


@pytest.mark.slow
def test_fit_with_prefetch_matches_no_prefetch(tmp_path, rng):
    """Prefetch is a scheduling change, not a math change: same batches,
    same final params either way (int conversion + sharding go through the
    same _put_batch). Slow tier: two full trainer compiles (~54s on the
    1-core CPU box) for a parity re-proof — the fast tier keeps the
    mechanism itself covered (ordering/placement + the fit NaN test run
    with prefetch on by default)."""
    batches = [_batch(rng) for _ in range(4)]
    params = {}
    for name, depth in (("off", 0), ("on", 2)):
        tc = _tc(tmp_path / name, device_prefetch=depth, save_every_steps=0)
        tr = DalleTrainer(TINY, tc, mesh=build_mesh(tc.mesh))
        tr.fit(iter(batches), log=lambda *a: None)
        params[name] = jax.device_get(tr.state.params)
    for a, b in zip(jax.tree.leaves(params["off"]),
                    jax.tree.leaves(params["on"])):
        np.testing.assert_array_equal(a, b)


# -- on-device rollback snapshots --------------------------------------------

def test_snapshot_modes_survive_donation_and_restore_bit_exact(tmp_path, rng):
    """Device mode: the jnp.copy snapshot survives repeated donations of the
    live state and restores bit-exact (twice — rollback installs a copy, so
    the snapshot outlives its own use). Host mode (same trainer, config
    swapped — one compile pays for both): the legacy device_get path still
    restores bit-exact."""
    tc = _tc(tmp_path, rollback_snapshot="device")
    tr = DalleTrainer(TINY, tc, mesh=build_mesh(tc.mesh))
    text, ids = _batch(rng)
    tr.train_step(text, ids)
    tr._snapshot_good()
    assert tr._last_good_device is not None and tr._last_good is None
    good = jax.device_get((tr.state.params, tr.state.opt_state))
    for _ in range(3):
        tr.train_step(text, ids)   # donates the live state each step
    tr._rollback()
    now = jax.device_get((tr.state.params, tr.state.opt_state))
    for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(now)):
        np.testing.assert_array_equal(a, b)   # bit-exact, not allclose
    # the snapshot survives its own rollback (rollback installs a copy):
    # poison again, roll back again
    tr.train_step(text, ids)
    tr._rollback()
    again = jax.device_get((tr.state.params, tr.state.opt_state))
    for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(again)):
        np.testing.assert_array_equal(a, b)
    # -- host mode on the same (already-compiled) trainer ------------------
    tr.train_cfg = tc.replace(rollback_snapshot="host")
    tr._snapshot_good()
    assert tr._last_good is not None and tr._last_good_device is None
    good = jax.device_get((tr.state.params, tr.state.opt_state))
    tr.train_step(text, ids)
    tr._rollback()
    now = jax.device_get((tr.state.params, tr.state.opt_state))
    for a, b in zip(jax.tree.leaves(good), jax.tree.leaves(now)):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("stepped,stats,want", [
    # no allocator limit (CPU): the copy is host RAM either way
    (False, {"hbm_bytes_in_use": 1}, "device"),
    # before the first step the step's footprint is unknown: host
    (False, {"hbm_bytes_in_use": 1, "hbm_bytes_limit": 1 << 40,
             "hbm_peak_bytes": 1, "hbm_peak_reserved_bytes": 1}, "host"),
    # a limit but no reservation peak: the step cannot be sized, host
    (True, {"hbm_bytes_in_use": 1, "hbm_bytes_limit": 1 << 40,
            "hbm_peak_bytes": 1}, "host"),
    # stepped, and buffers + program reservations leave room: device
    (True, {"hbm_bytes_in_use": 1, "hbm_bytes_limit": 1 << 40,
            "hbm_peak_bytes": 1 << 20, "hbm_peak_reserved_bytes": 1 << 20},
     "device"),
    # stepped, but the program's reservation fills the chip: host
    (True, {"hbm_bytes_in_use": 1, "hbm_bytes_limit": 1 << 30,
            "hbm_peak_bytes": 1 << 20,
            "hbm_peak_reserved_bytes": (1 << 30) - (1 << 20)}, "host"),
], ids=["no_limit", "before_first_step", "no_reservation_peak",
        "fits_beside_step", "step_fills_chip"])
def test_auto_snapshot_gate_sizes_the_step(tmp_path, monkeypatch, stepped,
                                           stats, want):
    """rollback_snapshot="auto" takes the device copy only when it fits
    beside a step that has already run — buffers plus what the step's
    program reserves (invisible to bytes_in_use on the TPU)."""
    from dalle_tpu import obs
    tr = DalleTrainer(TINY, _tc(tmp_path, rollback_snapshot="auto"))
    tr._stepped = stepped
    monkeypatch.setattr(obs, "device_memory_stats", lambda d=None: stats)
    live = (tr.state.params, tr.state.opt_state)
    assert tr._snapshot_mode(live) == want


def test_fit_nan_rollback_from_device_snapshot(tmp_path, rng):
    """End-to-end: a NaN loss mid-fit rolls the live state back to the last
    device snapshot bit-exact (inject by corrupting params so the real loss
    goes NaN — the guard path, not a mocked metrics dict)."""
    tc = _tc(tmp_path, rollback_snapshot="device", save_every_steps=0,
             device_prefetch=0)
    tr = DalleTrainer(TINY, tc, mesh=build_mesh(tc.mesh))
    batches = [_batch(rng) for _ in range(5)]
    poisoned = {"at": 2, "good": None}

    orig_step = tr.train_step

    def stepper(text, ids):
        if tr._host_step == poisoned["at"]:
            # corrupt one leaf → loss NaN on this step
            bad = jax.tree.map(lambda x: x * np.nan, tr.state.params)
            tr.state = tr.state.replace(params=bad)
        return orig_step(text, ids)

    tr.train_step = stepper
    logs = []
    tr.fit(iter(batches), log=logs.append)
    assert any("rolling back" in l for l in logs)
    # the post-fit params are finite again (rolled back, then retrained)
    assert all(np.isfinite(x).all()
               for x in jax.tree.leaves(jax.device_get(tr.state.params)))


# -- async checkpointing ------------------------------------------------------

def _state(val=1.0):
    import jax.numpy as jnp
    return {"w": jnp.full((1024,), val, jnp.float32),
            "step": jnp.int32(7)}


def test_async_save_close_drains_and_step_is_durable(tmp_path):
    """A save racing manager shutdown never leaves a truncated/unlisted
    step: close() drains, and a FRESH manager over the same directory lists
    and restores the step."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(3, _state(3.0), {"k": "v"})
    mgr.close()
    mgr2 = CheckpointManager(str(tmp_path), async_save=True)
    assert mgr2.latest_step() == 3
    restored, meta = mgr2.restore(_state(0.0))
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.full((1024,), 3.0, np.float32))
    assert meta == {"k": "v"}
    mgr2.close()
    mgr.close()   # idempotent


def test_async_save_is_donation_safe(tmp_path):
    """After save() returns, mutating/deleting the saved buffers must not
    corrupt the checkpoint (orbax snapshots before returning)."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    s = _state(5.0)
    mgr.save(1, s)
    s["w"].delete()           # the donation analogue
    mgr.wait_until_finished()
    restored, _ = mgr.restore(_state(0.0))
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.full((1024,), 5.0, np.float32))
    mgr.close()


def test_rotation_keep_n_with_inflight_saves(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep_n=2, async_save=True)
    for step in (1, 2, 3, 4):
        mgr.save(step, _state(float(step)))
    mgr.wait_until_finished()
    steps = sorted(int(d) for d in os.listdir(tmp_path) if d.isdigit())
    assert steps == [3, 4]
    assert mgr.latest_step() == 4
    mgr.close()


def test_restore_ignores_incomplete_tmp_step(tmp_path):
    """An interrupted write leaves a *.orbax-checkpoint-tmp-* directory —
    it must be invisible to latest_step()/restore() on a fresh manager."""
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(2, _state(2.0))
    mgr.close()
    os.makedirs(os.path.join(str(tmp_path), "9.orbax-checkpoint-tmp-123"))
    mgr2 = CheckpointManager(str(tmp_path), async_save=True)
    assert mgr2.latest_step() == 2
    restored, _ = mgr2.restore(_state(0.0))
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.full((1024,), 2.0, np.float32))
    mgr2.close()


def test_in_flight_gauge_lifecycle(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    assert mgr.in_flight_step is None
    mgr.save(5, _state())
    assert mgr.in_flight_step == 5
    mgr.wait_until_finished()
    assert mgr.in_flight_step is None
    mgr.close()


def test_sync_manager_unchanged(tmp_path):
    """async_save=False keeps the pre-PR3 contract: save() returns durable."""
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _state(1.0))
    assert mgr.in_flight_step is None
    mgr2 = CheckpointManager(str(tmp_path))
    assert mgr2.latest_step() == 1
    mgr2.close()
    mgr.close()


def test_signal_save_drains_inflight_write(tmp_path, rng):
    """The SIGUSR1 latch means "durable now": the boundary save forced by
    the latch drains the async writer before fit continues."""
    tc = _tc(tmp_path, save_every_steps=0, async_checkpointing=True)
    tr = DalleTrainer(TINY, tc, mesh=build_mesh(tc.mesh))
    tr.install_signal_checkpoint(log=lambda *a: None)
    tr._signal_save = True     # what the SIGUSR1 handler sets
    batches = [_batch(rng) for _ in range(2)]
    tr.fit(iter(batches), log=lambda *a: None)
    assert tr.ckpt.in_flight_step is None       # drained at the latch save
    assert tr.ckpt.latest_step() == 1           # first boundary
    assert tr._signal_save is False


# -- the late metrics fetch ---------------------------------------------------

class _Writer:
    def __init__(self):
        self.records = []

    def log(self, step, metrics):
        self.records.append((step, dict(metrics)))


def test_late_fetch_true_steps_and_save_boundary_fetch(tmp_path, rng):
    """One fit covers the late fetch's contract on a real trainer: records
    carry their TRUE steps in order with no step lost (the late record
    written before a save boundary's own; the final parked boundary flushed
    at fit exit), and save boundaries (2, 4) get an in-band record of their
    OWN step — nothing is checkpointed without a NaN check of the current
    state. The losses are those of the same steps run bare (in band)."""
    tc = _tc(tmp_path, save_every_steps=2, log_every=1, metrics_every=1,
             device_prefetch=0)
    batches = [_batch(rng) for _ in range(5)]
    tr = DalleTrainer(TINY, tc, mesh=build_mesh(tc.mesh))
    w = _Writer()
    tr.fit(iter(batches), metrics_writer=w, log=lambda *a: None)
    assert [s for s, _ in w.records] == [1, 2, 3, 4, 5]
    assert tr.ckpt.latest_step() == 4
    assert (tr._fetched_late, tr._fetched_in_band) == (2, 3)   # 1, 3 | 2, 4, 5
    bare = DalleTrainer(TINY, tc, mesh=tr.mesh)
    assert [m["loss"] for _, m in w.records] == [
        bare.train_step(*b)["loss"] for b in batches]
