"""A train cell end to end on the CPU at a tiny width, through the same code
as on the chip (Pallas in interpret mode), and the faults a train cell can
have planted underneath the timed path: each has to come out not correct."""

import jax
import jax.numpy as jnp
import pytest

from _drive import run

SEED = 2 ** 31 + 4242


def state_left_unchanged(trainer):
    real = trainer.step_fn

    def step(state, text, ids, key):
        kept = jax.tree.map(jnp.copy, state)     # the real step donates
        _, metrics = real(state, text, ids, key)
        return kept, metrics
    trainer.step_fn = step


def half_of_the_batch_left_out(trainer):
    real = trainer.step_fn
    trainer.step_fn = lambda state, text, ids, key: real(
        state, text[:len(text) // 2], ids[:len(ids) // 2], key)


def test_a_sound_run_is_correct_and_prints_the_contracts_line():
    line, earlier = run("tiny_train_adam", seed=SEED, seconds=1.0)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 3
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert line["metrics"]["setup_s"]["unit"] == "s"
    assert line["device"]["platform"] == "cpu"        # named, never hidden
    assert set(line["compared"]) == {"loss_gap", "grad_norm_gap",
                                     "leaf_change_gap"}
    for value, limit in line["compared"].values():
        assert 0 <= value <= limit
    text = "\n".join(earlier)
    assert "compiles inside the window: 0" in text
    assert "peak bytes" in text and "cache" in text and "device: cpu" in text


@pytest.mark.parametrize("fault,number", [
    (state_left_unchanged, "leaf_change_gap"),
    (half_of_the_batch_left_out, "grad_norm_gap")],
    ids=["state_left_unchanged", "half_of_the_batch_left_out"])
def test_a_fault_under_the_timed_path_is_not_correct(fault, number):
    line, _ = run("tiny_train_adam", seed=SEED + 1, seconds=0.5, fault=fault)
    assert line["correct"] is False
    value, limit = line["compared"][number]
    assert value > limit
