"""bench/trace.py on a small trace recorded on the CPU, and its interval
algebra on hand-made intervals."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import pytest

import tiny  # noqa: F401  (puts bench/ on the path)
import trace as tr


def test_union_gaps_and_cover():
    spans = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0)]
    assert tr.union(spans) == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.covered(spans, 1.0, 3.5) == pytest.approx(1.5)
    assert tr.gaps(spans, -1.0, 5.0) == [(-1.0, 0.0), (2.0, 3.0),
                                         (4.0, 5.0)]


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("trace"))
    matmul = jax.jit(lambda x: x @ x)
    x = jnp.ones((256, 256))
    matmul(x).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            matmul(x).block_until_ready()
            time.sleep(0.05)
    jax.profiler.stop_trace()
    return tr.load(d)


def test_recorded_trace(recorded):
    (lo, hi), = recorded.spans("bench.window")
    window = hi - lo
    busy = tr.busy(recorded, lo, hi)
    assert 0 < busy < window
    # three sleeps of 50 ms leave the device idle for at least 0.15 s
    assert window - busy >= 0.15
    progs = tr.program_seconds(recorded, lo, hi)
    assert any("lambda" in name for name in progs)
    assert sum(progs.values()) == pytest.approx(busy, rel=0.05)
    gaps = tr.idle_gaps(recorded, lo, hi)
    assert gaps[0][1] - gaps[0][0] >= 0.045
