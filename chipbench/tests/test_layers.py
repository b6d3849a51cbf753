"""Per-layer reduction of a profiler trace: op metadata, named scopes,
XLA modules and idle time by the innermost host span."""

import types
from pathlib import Path

import numpy as np
import pytest

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "v5e-small.xplane.pb"
SCOPED = DATA / "v5e-scopes.xplane.pb"


def _data(path):
    if not path.exists():
        pytest.fail(f"missing {path}")
    return path


def test_small_trace_reduces_as_before():
    """``trace.py``'s numbers on the first recorded trace, as the
    reduction of the benchmark's first version gave them."""
    from chipbench.trace import reduce_trace
    r = reduce_trace(_data(SMALL))
    exact = pytest.approx
    assert r["busy_s"] == exact(0.000418182, rel=1e-12)
    assert r["window_s"] == exact(0.14001512800000002, rel=1e-12)
    assert r["step_idle_pct"] == exact(99.29181006565663, rel=1e-12)
    assert [n for n, _ in r["device_ops"]] == [
        "%fusion", "%convolution_tanh_fusion", "%copy-done",
        "%dynamic_slice.1", "%copy-start"]
    np.testing.assert_allclose(
        [t for _, t in r["device_ops"]],
        [0.000272682, 0.00026980900000000003, 5.5514000000000004e-05,
         9.380000000000001e-07, 7.9e-08], rtol=1e-12)


@pytest.mark.parametrize("path", [SMALL, SCOPED], ids=["small", "scopes"])
def test_metadata_reader_finds_every_op(path):
    """Every device op of the trace has its (program, name) in the
    metadata the wire-format reader decodes, with the program of the
    module run around it."""
    from jax.profiler import ProfileData
    from chipbench import layers, xplane
    meta = xplane.op_scopes(_data(path))
    ops, mods, _ = layers._read(path)
    for plane, evs in ops.items():
        names = {op for _, op in meta[plane]}
        assert {n for _, _, n in evs} <= names
        runs = sorted(mods[plane])
        starts = [r[0] for r in runs]
        for s, _, n in evs:
            k = np.searchsorted(starts, s, side="right") - 1
            if k >= 0 and s < runs[k][1]:
                assert (runs[k][3], n) in meta[plane], n
    assert ProfileData.from_file(str(path)).planes  # the same file


def test_small_trace_paths_and_modules():
    from chipbench import xplane
    from chipbench.layers import reduce_layers
    meta = xplane.op_scopes(_data(SMALL))["/device:TPU:0"]
    fused = [p for (_, n), p in meta.items() if n.startswith("%fusion")]
    assert fused == ["jit(<lambda>)/dot_general:"]
    r = reduce_layers(SMALL)
    assert set(r["modules"]) == {"jit__lambda", "jit_dynamic_slice",
                                 "jit_squeeze"}
    assert r["scopes"] == {} and r["program_spans"] == {}
    assert all(v is None for v in r["layers"].values())


def test_scoped_trace_splits_device_time_by_scope():
    from chipbench.layers import reduce_layers
    from chipbench.trace import reduce_trace
    base = reduce_trace(_data(SCOPED))
    r = reduce_layers(SCOPED)
    busy = base["busy_s"]
    n, dev = r["modules"]["jit_decode_step"]
    assert n == 3 and 0 < dev <= busy
    sc = r["scopes"]["jit_decode_step"]
    unscoped = sc.pop("unscoped")
    assert set(sc) == {"embed", "attention", "moe", "moe/ffn", "unembed"}
    assert all(0 < t <= busy for t in sc.values())
    assert 0 <= unscoped < 0.05 * dev
    # the layer scan's while holds the scopes and carries none itself
    top = {name: path for name, path, _ in r["top_ops"]["jit_decode_step"]}
    assert top["%while"] == ""
    assert sum("/moe/ffn/" in p for p in top.values()) == 1
    assert sum(sc[k] for k in ("embed", "attention", "moe", "unembed")) \
        <= dev <= busy
    assert sc["moe/ffn"] == pytest.approx(sc["moe"])
    lay = r["layers"]
    assert lay["decode_device_ms"] == pytest.approx(1e3 * dev / 3)
    assert lay["decode_moe_ms"] + lay["decode_attn_ms"] \
        <= lay["decode_device_ms"]


def test_scoped_trace_labels_idle_time_by_innermost_span():
    from chipbench.layers import reduce_layers
    r = reduce_layers(_data(SCOPED))
    gaps = dict(r["idle_gaps"])
    # 3 steps of 4 ms host work in step.observe, 1 ms of it in step.migrate
    assert gaps["step.observe"] == pytest.approx(0.009, rel=0.3)
    assert gaps["step.migrate"] == pytest.approx(0.003, rel=0.3)
    assert gaps["idle.wait"] > 0.012
    assert gaps.get("engine.step", 0.0) < 0.25 * sum(
        t for n, t in gaps.items() if n.startswith("step."))
    sp = r["program_spans"]
    assert sp["step.observe"][0] == 3 and sp["step.migrate"][0] == 3
    lay = r["layers"]
    assert lay["control_ms"] == pytest.approx(3.0, rel=0.3)
    assert 0 < lay["migrate_pct"] < 100


def _busy(*iv):
    from chipbench.trace import _Busy, _union
    return _Busy(_union(np.asarray(iv, float)))


def test_idle_split_at_span_ends():
    """Idle time goes to the innermost span open at the time, cut at span
    ends, not whole to the span around the gap's midpoint."""
    from chipbench.layers import _idle_gaps
    spans = {"engine.step": np.array([[0.0, 100.0]]),
             "step.sync": np.array([[0.0, 40.0]]),
             "step.observe": np.array([[40.0, 90.0]]),
             "step.migrate": np.array([[60.0, 70.0]])}
    got = _idle_gaps(_busy((0, 30)), spans, 0.0, 120.0)
    assert got == pytest.approx({
        "step.sync": 10e-9, "step.observe": 40e-9, "step.migrate": 10e-9,
        "engine.step": 10e-9, "none": 20e-9})
    # two spans that start together: the longer one holds the shorter
    spans = {"engine.step": np.array([[0.0, 100.0]]),
             "step.schedule": np.array([[0.0, 10.0]])}
    got = _idle_gaps(_busy((50, 60)), spans, 0.0, 100.0)
    assert got == pytest.approx({"step.schedule": 10e-9,
                                 "engine.step": 80e-9})


def test_queue_wait_from_admission_steps():
    from chipbench.layers import queue_wait_ms
    reqs = {i: types.SimpleNamespace(req_id=i, submitted=float(i),
                                     rejected=False) for i in range(10)}
    window = types.SimpleNamespace(reqs=reqs, t1=20.0)
    # request i admitted by the call of step i that starts at i + 0.5;
    # request 9 is never admitted and waits out the window
    records = {i: types.SimpleNamespace(admitted_step=i if i < 9 else None)
               for i in range(10)}
    starts = {i: i + 0.5 for i in range(9)}
    waits = [500.0] * 9 + [11000.0]
    assert queue_wait_ms(window, records, starts) == pytest.approx(
        float(np.percentile(waits, 90)))
    # a program that keeps no admission step reads nothing
    bare = {i: types.SimpleNamespace() for i in range(10)}
    assert queue_wait_ms(window, bare, starts) is None
