"""The rest of a run with the timed path broken underneath: `correct` has to
come out false for each fault a training cell can have."""
import jax
import jax.numpy as jnp
import pytest
from bench_testlib import needs_devices, tiny_manifest, tiny_run


def state_unchanged(t):
    step = jax.jit(t.step_fn)
    t.step = lambda p, o, b, k: (p, o, step(p, o, b, k)[2])


def half_batch_left_out(t):
    def step(p, o, b, k):
        half = {n: v[:v.shape[0] // 2] for n, v in b.items()}
        return t.step_fn(p, o, half, k)      # the mean over the rest
    t.step = jax.jit(step)


def exchange_left_out(t):
    """What chip 0 computes when the gradients are not exchanged: its own
    quarter of the rows stands for the whole batch."""
    def step(p, o, b, k):
        mine = {n: jnp.concatenate([v[:v.shape[0] // 4]] * 4)
                for n, v in b.items()}
        return t.step_fn(p, o, mine, k)
    t.step = jax.jit(step)


def broken(monkeypatch, fault):
    entry = tiny_manifest().module("entries", "fleet_pretrainer")
    build = entry.build

    def build_broken(*a, **kw):
        t = build(*a, **kw)
        fault(t)
        return t

    monkeypatch.setattr(entry, "build", build_broken)


@pytest.mark.parametrize("fault, over", [
    (state_unchanged, {"grad_norm_gap", "param_change_gap"}),
    (half_batch_left_out, {"grad_norm_gap"}),
])
def test_fault_on_one_chip_comes_out_not_correct(monkeypatch, fault, over):
    broken(monkeypatch, fault)
    result, _ = tiny_run(seed=5)
    assert result["correct"] is False
    failed = {n for n, j in result["compared"].items()
              if not j["value"] <= j["limit"]}
    assert over <= failed, result["compared"]
    if fault is state_unchanged:   # reads 1 by the measure, exactly
        assert result["compared"]["grad_norm_gap"]["value"] == 1.0
        assert result["compared"]["param_change_gap"]["value"] == 1.0


@needs_devices
def test_exchange_between_chips_left_out_comes_out_not_correct(monkeypatch):
    broken(monkeypatch, exchange_left_out)
    result, _ = tiny_run(cell="tiny.s128.dp4", seed=5)
    assert result["correct"] is False
    assert result["compared"]["grad_norm_gap"]["value"] > 0.1


def test_sound_run_of_the_same_seed_is_correct():
    result, _ = tiny_run(seed=5)
    assert result["correct"] is True
