"""Reduction of a profiler trace to busy and idle time."""

from pathlib import Path

import numpy as np
import pytest

DATA = Path(__file__).resolve().parent / "data"


def test_union_and_coverage():
    from chipbench.trace import _Busy, _union
    u = _union(np.array([[5, 7], [0, 2], [1, 3], [6, 9], [12, 13]], float))
    np.testing.assert_array_equal(u, [[0, 3], [5, 9], [12, 13]])
    b = _Busy(u)
    assert b.covered(0, 13) == 8
    assert b.covered(2, 6) == 2
    np.testing.assert_array_equal(b.covered(np.array([0, 9]),
                                            np.array([1, 12.5])), [1, 0.5])


def test_chip_trace_reduces():
    """A trace recorded on a v5e by tests/record_trace.py: three
    engine.step spans that each run a jitted matmul chain, with a
    client.readback and an idle.wait span between them."""
    from chipbench.trace import reduce_trace
    path = DATA / "v5e-small.xplane.pb"
    if not path.exists():
        pytest.fail(f"missing {path}")
    r = reduce_trace(path)
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 <= r["step_idle_pct"] < 100
    labels = dict(r["idle_gaps"])
    assert labels.get("idle.wait", 0) > 0.004       # 3 sleeps of 5 ms
    assert r["device_ops"] and all(t > 0 for _, t in r["device_ops"])
