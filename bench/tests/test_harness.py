"""The harness end to end on the CPU at a tiny size, with the look for a
chip skipped: a sound run is correct, the fp8 control in the program's
place is not, and each fault planted in the timed path makes ``correct``
false. Run by hand: ``JAX_PLATFORMS=cpu python -m pytest bench/tests``."""
from __future__ import annotations

import jax.numpy as jnp
import pytest

import tiny


@pytest.fixture(autouse=True)
def _reduced(monkeypatch):
    tiny.reduce_archs(monkeypatch)


def _alter_token(step):
    """A decode step whose every sequence emits token 7."""
    def broken(batch, view):
        return step(batch, view).at[..., 7].add(1e4)
    return broken


def _drop_half(step):
    """A decode step that computes half of the batch and hands the other
    half the first half's logits."""
    def broken(batch, view):
        out = step(batch, view)
        h = max(out.shape[0] // 2, 1)
        return jnp.concatenate([out[:h], out[:out.shape[0] - h]], axis=0)
    return broken


def _alter_answer(pass_fn):
    """A swapped prefill pass whose answer puts token 7 first, where the
    answer is made."""
    def broken(*args, **kw):
        state, stats = pass_fn(*args, **kw)
        if stats is not None:
            state.logits = state.logits.at[..., 7].add(1e4)
        return state, stats
    return broken


@pytest.mark.parametrize("kind", ["chat", "danube", "prefill", "poisson"])
def test_sound_run_is_correct(kind):
    res = tiny.drive(kind)
    assert res["correct"], res["checks"]
    assert res["compiled_in_window"] == 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["metrics"]


@pytest.mark.parametrize("kind", ["chat", "prefill"])
def test_control_reads_far_above_the_program(kind):
    """The fp8 reference in the program's place fails the harness's own
    comparison, reading far above what the program reads."""
    res = tiny.drive(kind, control=True)
    assert res["correct"] is False, res["checks"]
    got = res["readings"]
    name = next(iter(tiny.LIMITS[tiny.cell(kind).mix["requests"]]))
    low, ctl = got["program_" + name], got[name]
    assert res["checks"][name]["value"] == ctl
    assert low <= res["checks"][name]["limit"] < ctl, got
    assert ctl >= 3 * low, got


@pytest.mark.parametrize("kind,hooks", [
    ("chat", {"decode": _alter_token}),
    ("chat", {"decode": _drop_half}),
    ("chat", {"prefill": _alter_answer}),
    ("prefill", {"prefill": _alter_answer}),
])
def test_fault_in_the_timed_path_is_caught(kind, hooks):
    res = tiny.drive(kind, hooks=hooks)
    assert not res["correct"], res["checks"]


def test_traced_run_reads_its_layers():
    res = tiny.drive("chat", trace=True)
    assert res["correct"]
    busy = res["device_busy"]
    assert 0 < busy["busy_s"] <= busy["window_s"]
    assert res["breakdown"]["device_ops"] and res["breakdown"]["idle_gaps"]
    assert "device_idle.chat" in res["metrics"]


class _Device:
    """A device whose bytes in use rise to a peak and fall again."""

    def __init__(self, values):
        self.values = list(values)

    def memory_stats(self):
        v = self.values.pop(0) if len(self.values) > 1 else self.values[0]
        return {"bytes_in_use": v}


def test_memory_sampler_keeps_the_window_peak():
    import time
    import run
    m = run.MemorySampler(_Device([5, 9, 7, 3]), period=0.001)
    m.start()
    time.sleep(0.05)
    m.stop()
    assert m.peak == 9 and m.readings[-1] == 3
    assert run.MemorySampler(_Device([0])).peak == -1   # nothing read yet
