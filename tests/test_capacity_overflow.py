"""Capacity-overflow coverage for the EP dispatch paths (models/moe.py).

The a2a path buckets assignments per physical slot with a fixed capacity;
assignments past a bucket's capacity are dropped. These tests pin the two
contracts of that drop path:

* **conservation** — dropped assignments contribute exactly zero to the
  combined output; kept assignments keep their unmodified gate weights
  (verified against a from-scratch numpy/jnp reference that replays the
  bucketing);
* **visibility** — the drop count is surfaced in the layer tally's final
  column (and aggregated into ``EngineStats.dropped_assignments``) instead
  of being silently zeroed.

Runs in-process on a 1-device mesh, so the fast CI lane covers the real
``shard_map`` dispatch bodies without the multi-process battery.

Since the ragged dropless pipeline became the default (``moe_impl="auto"``
→ ragged, which structurally cannot drop), these tests pin
``moe_impl="capacity"`` explicitly — they are the capacity baseline's
regression suite. The ragged path's no-drop contract is covered in
``test_ragged_dispatch.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models import moe as MOE
from repro.models.sharding import ShardingRules

E, D, F, K = 4, 16, 64, 2
B, S = 2, 16


def _bucket_keep(slot_flat, n_slots, capacity):
    """Replay of ``_bucket_positions``: arrival order within each bucket."""
    pos = np.zeros_like(slot_flat)
    fill = np.zeros(n_slots, dtype=np.int64)
    for i, s in enumerate(slot_flat):
        pos[i] = fill[s]
        fill[s] += 1
    return pos < capacity


def _reference_with_drops(p, x, capacity):
    """Dense oracle with the a2a keep mask applied by hand."""
    xf = np.asarray(x.reshape(B * S, D), np.float32)
    weights, idx, _ = MOE.route(p["router"], jnp.asarray(xf), K)
    weights, idx = np.asarray(weights), np.asarray(idx)
    keep = _bucket_keep(idx.reshape(-1), E, capacity).reshape(idx.shape)
    y_all = np.asarray(MOE.expert_ffn_ref(
        p["w1"], p["w3"], p["w2"],
        jnp.broadcast_to(jnp.asarray(xf, x.dtype), (E, B * S, D))),
        np.float32)
    out = np.zeros((B * S, D), np.float32)
    for t in range(B * S):
        for k in range(K):
            if keep[t, k]:
                out[t] += weights[t, k] * y_all[idx[t, k], t]
    return out.reshape(B, S, D), idx, int((~keep).sum())


@pytest.fixture(scope="module")
def setup():
    p = MOE.moe_init(jax.random.PRNGKey(0), d=D, f=F, n_experts=E, n_slots=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)) \
        .astype(jnp.bfloat16)
    mesh = make_mesh((1,), ("model",))
    return p, x, mesh


def _run_a2a(p, x, mesh, cf):
    rules = ShardingRules(mesh=mesh, dp=(), ep=("model",), fsdp=None,
                          moe_dispatch="a2a", capacity_factor=cf,
                          moe_impl="capacity")
    with jax.set_mesh(mesh):
        y, tally, _ = jax.jit(lambda p, x: MOE.moe_layer(
            p, x, top_k=K, n_experts=E, rules=rules, phase="train"))(p, x)
    return np.asarray(y, np.float32), np.asarray(tally)


def test_a2a_drop_path_conserves_output(setup):
    """With a starved capacity, the a2a output equals the dense oracle with
    the overflowing assignments zeroed — dropped assignments contribute
    nothing, kept ones keep their unmodified gate weights."""
    p, x, mesh = setup
    cf = 0.25
    capacity = MOE._round_up(max(int(np.ceil(B * S * K / E * cf)), 1), 4)
    y, tally = _run_a2a(p, x, mesh, cf)
    ref, idx, n_dropped = _reference_with_drops(p, x, capacity)
    assert n_dropped > 0, "fixture failed to overflow any bucket"
    np.testing.assert_allclose(y, ref, atol=5e-2, rtol=5e-2)
    # drop column matches the replayed bucket accounting exactly
    assert tally[-1] == n_dropped
    # logical tallies are pre-capacity routing counts: conserved regardless
    np.testing.assert_allclose(tally[:E],
                               np.bincount(idx.ravel(), minlength=E))
    assert tally[:E].sum() == B * S * K


def test_a2a_no_drops_at_generous_capacity(setup):
    p, x, mesh = setup
    y, tally = _run_a2a(p, x, mesh, cf=8.0)
    assert tally[-1] == 0
    y_ref, tally_ref, _ = MOE.moe_layer(p, x, top_k=K, n_experts=E,
                                        rules=None)
    np.testing.assert_allclose(y, np.asarray(y_ref, np.float32),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(tally, np.asarray(tally_ref))


def test_dense_path_never_drops(setup):
    p, x, _ = setup
    _, tally, _ = MOE.moe_layer(p, x, top_k=K, n_experts=E, rules=None)
    assert tally.shape == (E + 1,)
    assert tally[-1] == 0


def test_replicated_path_surfaces_drops(setup):
    """The decode (replicated) body counts its local bucket overflow too:
    a router biased onto one expert overflows that expert's bucket."""
    p, x, mesh = setup
    p_hot = dict(p)
    bias = np.zeros((D, E), np.float32)
    bias[:, 0] = 3.0                       # softmax mass piles on expert 0
    p_hot["router"] = p["router"] + jnp.asarray(bias)
    x_pos = jnp.abs(x)                     # positive inputs → bias dominates
    rules = ShardingRules(mesh=mesh, dp=(), ep=("model",),
                          ep_all=("model",), fsdp=None,
                          moe_dispatch="replicated", capacity_factor=2.0,
                          moe_impl="capacity")
    with jax.set_mesh(mesh):
        y, tally, _ = jax.jit(lambda p, x: MOE.moe_layer(
            p, x, top_k=1, n_experts=E, rules=rules, phase="decode"))(
            p_hot, x_pos)
    tally = np.asarray(tally)
    assert tally[:E].sum() == B * S            # top-1: one draw per token
    assert tally[-1] > 0, "hot expert failed to overflow its bucket"
    assert np.isfinite(np.asarray(y, np.float32)).all()


def test_engine_accumulates_dropped_assignments():
    """EngineStats surfaces the per-step drop column (0 on the dense smoke
    path, but the accounting plumbing must run end-to-end)."""
    from repro.configs import get_smoke
    from repro.serving import Engine, WORKLOADS, sample_requests

    eng = Engine(get_smoke("qwen3-moe-235b-a22b"), max_batch=2, max_seq=48)
    reqs = sample_requests(WORKLOADS["sharegpt"], 2, qps=100.0, seed=0)
    reqs = [type(r)(r.req_id, r.arrival, 8, 4) for r in reqs]
    eng.submit(reqs)
    eng.run(max_steps=60)
    assert eng.stats.steps > 0
    assert eng.stats.dropped_assignments == 0.0     # dense path: no drops
