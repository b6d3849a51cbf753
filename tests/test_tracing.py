"""Host spans of the engine step loop (``repro.serving.tracing``), the
admission step on request records, and the names the step programs carry
into a device trace (jit names and named scopes)."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import DriftConfig, ViBEConfig, ViBEController, make_cluster
from repro.models import (decode_fn, init_cache, init_params,
                          make_moe_tables, moe_perm_shape, prefill_chunk_fn,
                          prefill_fn)
from repro.serving import Engine, EngineConfig, Request, SchedulerConfig
from repro.serving import tracing
from repro.serving.engine import sample

ARCH = "granite-moe-3b-a800m"
PHASES = {"step.schedule", "step.admit", "step.launch", "step.sync",
          "step.observe", "step.migrate", "step.finish"}


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each span as
    (enclosing span's name, name, args) when it is entered."""

    log: list = []
    stack: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, dict(args)

    def __enter__(self):
        parent = self.stack[-1].name if self.stack else None
        self.log.append((parent, self.name, self.args))
        self.stack.append(self)
        return self

    def __exit__(self, *exc):
        self.stack.pop()
        return False

    def set_metadata(self, **args):
        self.args.update(args)


@pytest.fixture
def recorder(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log, _Recorder.stack = [], []
    tracing.enable(True)
    try:
        yield _Recorder.log
    finally:
        tracing.enable(False)


def _engine(chunk=16, drift=4):
    cfg = get_smoke(ARCH)
    n_moe, n_slots = moe_perm_shape(cfg, None, "train")
    cluster = make_cluster(4, "mi325x", d_model=cfg.d_model,
                           d_ff=cfg.moe_d_ff, experts_per_rank=n_slots // 4)
    ctl = ViBEController(
        n_moe, n_slots, 4, cluster.fit_models(),
        ViBEConfig(policy="vibe_r", adaptive=True,
                   drift=DriftConfig(window=2 * drift, interval=drift,
                                     cooldown=drift),
                   expert_bytes=3 * cfg.d_model * cfg.moe_d_ff * 2))
    return Engine(cfg, EngineConfig(
        max_batch=2, max_seq=48, seed=0,
        scheduler=SchedulerConfig(name="fcfs", prefill_chunk=chunk)),
        controller=ctl, cluster=cluster)


def _serve(eng, waves=((0, 3), (4, 2)), steps=200):
    """Submit each wave (step to submit at, count) as the loop reaches it;
    the step count at each request's submission."""
    submitted, rid, todo = {}, 0, list(waves)
    for _ in range(steps):
        while todo and todo[0][0] <= eng.stats.steps:
            _, n = todo.pop(0)
            eng.submit([Request(rid + i, 0.0, 20, 5) for i in range(n)])
            submitted.update({rid + i: eng.stats.steps for i in range(n)})
            rid += n
        if not eng.step() and not todo:
            break
    return submitted


def test_off_is_the_shared_noop(monkeypatch):
    def refuse(*a, **k):
        raise AssertionError("an annotation was made with tracing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    sp = tracing.span("step.sync", kind="decode")
    assert sp is tracing.span("step.observe")
    with sp as entered:
        entered.set_metadata(slots=1)


def test_on_nests_names_and_args(recorder):
    with tracing.span("step.observe", kind="decode"):
        with tracing.span("step.migrate", kind="decode") as sp:
            sp.set_metadata(slots=3, bytes=12)
    assert recorder == [
        (None, "step.observe", {"kind": "decode"}),
        ("step.observe", "step.migrate",
         {"kind": "decode", "slots": 3, "bytes": 12})]


def test_engine_step_phases(recorder):
    eng = _engine()
    recorder.clear()            # the initial placement, outside any step
    _serve(eng)
    top = [(n, a) for p, n, a in recorder if p is None]
    names = {n for _, n, _ in recorder}
    assert names <= PHASES
    assert {"step.schedule", "step.admit", "step.launch", "step.sync",
            "step.observe", "step.finish"} <= names
    # every phase span carries the kind of its step; admission its request
    kinds = {a.get("kind") for _, _, a in recorder}
    assert kinds <= {"chunk", "decode", "idle"} and {"chunk", "decode"} \
        <= kinds
    assert all("req_id" in a for n, a in top if n == "step.admit")
    # a migration happens inside the controller's observation
    mig = [(p, a) for p, n, a in recorder if n == "step.migrate"]
    assert mig and eng.stats.migrations > 0
    assert all(p == "step.observe" and a["slots"] >= 0 and a["bytes"] >= 0
               for p, a in mig)
    assert sum(a["slots"] for _, a in mig) == eng.stats.migrated_slots


def test_admitted_step_follows_submission():
    eng = _engine()
    submitted = _serve(eng)
    recs = eng.records
    assert set(submitted) == set(recs)
    for rid, at in submitted.items():
        step = recs[rid].admitted_step
        assert step is not None and at <= step < eng.stats.steps
    # fcfs admits in submission order, the first request at the first step
    order = sorted(recs, key=lambda r: recs[r].admitted_step)
    assert order == sorted(recs) and recs[0].admitted_step == 0


def _run_state(eng, submitted):
    recs = tuple((r.req_id, r.first_token_at, r.finished_at,
                  r.admitted_step) for r in eng.records.values())
    st = eng.stats
    return (recs, st.virtual_time, st.steps, st.migrations,
            st.migrated_slots, st.steal_updates, eng._perm.tobytes(),
            eng.controller.placement.perm.tobytes(),
            np.asarray(eng.tokens).tobytes(), tuple(submitted.items()))


def test_tracing_does_not_change_a_run(tmp_path):
    """One seeded run with the spans off and one with them on under the
    profiler: the same records, virtual times and placements; the trace
    holds the phase spans with their kinds."""
    eng = _engine()
    off = _run_state(eng, _serve(eng))
    eng = _engine()
    tracing.enable(True)
    try:
        with jax.profiler.trace(str(tmp_path)):
            submitted = _serve(eng)
    finally:
        tracing.enable(False)
    assert _run_state(eng, submitted) == off
    assert eng.stats.migrations > 0
    from jax.profiler import ProfileData
    path, = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    seen = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name in PHASES:
                    seen.setdefault(ev.name, set()).update(
                        v for k, v in ev.stats if k == "kind")
    assert set(seen) == PHASES
    assert seen["step.launch"] == {"chunk", "decode"}


def _shapes(cfg, batch=2, max_seq=32):
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    n_moe, n_slots = moe_perm_shape(cfg, None, "train")
    mt = make_moe_tables(cfg, None, perm=np.tile(
        np.arange(n_slots, dtype=np.int32), (n_moe, 1)), n_slots=n_slots)
    cache = jax.eval_shape(lambda: init_cache(cfg, batch, max_seq))
    return params, mt, cache


def _lowered(program):
    cfg = get_smoke(ARCH)
    params, mt, cache = _shapes(cfg)
    i32 = jnp.int32
    if program == "decode_step":
        return jax.jit(decode_fn(cfg)).lower(
            params, jax.ShapeDtypeStruct((2, 1), i32), cache,
            jax.ShapeDtypeStruct((2,), i32), mt)
    if program == "prefill_chunk":
        return jax.jit(prefill_chunk_fn(cfg)).lower(
            params, jax.ShapeDtypeStruct((1, 8), i32), cache, 0, 0, 8, mt)
    return jax.jit(prefill_fn(cfg)).lower(
        params, {"tokens": jax.ShapeDtypeStruct((1, 8), i32)}, mt)


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk",
                                     "prefill"])
def test_step_program_names_and_scopes(program):
    lowered = _lowered(program)
    assert lowered.as_text().startswith(f"module @jit_{program} ")
    text = lowered.compile().as_text()
    assert re.search(rf"^HloModule jit_{program}\b", text, re.M)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    assert all(p.startswith(f"jit({program})/") for p in paths
               if p.startswith("jit("))
    for scope in ("embed", "attention", "moe/router", "moe/dispatch",
                  "moe/ffn", "moe/combine", "unembed"):
        assert any(f"/{scope}/" in p for p in paths), (scope, program)


def test_sample_is_its_own_named_program():
    logits = jnp.asarray([[0.0, 2.0, 1.0], [3.0, -1.0, 0.5]], jnp.float32)
    np.testing.assert_array_equal(sample(logits), [1, 0])
    assert sample(logits).dtype == jnp.int32
    text = sample.lower(logits).compile().as_text()
    assert re.search(r"^HloModule jit_sample\b", text, re.M)
    assert 'op_name="jit(sample)/sample/' in text
