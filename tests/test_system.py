"""End-to-end system behaviour + paper-claim sanity checks."""

import numpy as np
import pytest

from repro.configs import ALL_ARCHS, SHAPES, get, shape_applicable
from repro.core import (make_cluster, solve_model_placement,
                        incremental_update, vibe_placement)
from repro.launch.hlo_analysis import parse_hlo
from repro.serving import WORKLOADS, routing_profile


def test_paper_claim_incremental_vs_full_transfer_volume():
    """Paper §4.2.4: incremental solver converges in 5–30 swaps/layer vs
    >200 slot reassignments for a full re-solve (256 experts, 8 ranks)."""
    model = get("deepseek-v3-671b")
    cluster = make_cluster(8, "mi325x", d_model=model.d_model,
                           d_ff=model.moe_d_ff, experts_per_rank=32)
    perf = cluster.fit_models()
    L, E = model._n_moe_layers(), model.n_experts
    w0 = routing_profile(WORKLOADS["sonnet"], L, E) * 16384 * model.top_k
    w1 = routing_profile(WORKLOADS["sharegpt"], L, E) * 16384 * model.top_k
    pl = vibe_placement(w0, perf)
    res = incremental_update(pl, w1, perf)
    full = vibe_placement(w1, perf)
    swaps_per_layer = res.per_layer_swaps.mean()
    full_moves_per_layer = full.moved_experts(pl) / L
    assert swaps_per_layer <= 35
    assert full_moves_per_layer > 150
    # >10× transfer-volume saving (paper: "over an order of magnitude")
    assert res.moved_expert_count() * 10 < full.moved_experts(pl)


def test_paper_claim_latency_gap_reduction():
    """Paper Fig 10a: token redistribution (EPLB) removes most of the gap;
    ViBE removes a further slice. Checked at the layer-latency level."""
    from repro.serving.simulator import rank_latency_matrix
    model = get("deepseek-v3-671b")
    cluster = make_cluster(8, "mi325x", d_model=model.d_model,
                           d_ff=model.moe_d_ff, experts_per_rank=32)
    perf = cluster.fit_models()
    L, E = model._n_moe_layers(), model.n_experts
    W = routing_profile(WORKLOADS["sonnet"], L, E) * 16384 * model.top_k
    gaps = {}
    for policy in ("contiguous", "eplb", "vibe"):
        pl = solve_model_placement(
            policy, W, 8, perf_models=perf if policy == "vibe" else None)
        rt = rank_latency_matrix(cluster, pl.rank_loads(W))
        gaps[policy] = float(np.median(rt.max(1) - rt.min(1)))
    assert gaps["eplb"] < 0.5 * gaps["contiguous"]      # paper: −63.9%
    assert gaps["vibe"] < gaps["eplb"]                  # paper: −19.6% more


def test_skip_matrix_is_exactly_the_assignment():
    """40 cells − 8 documented skips = 32 runnable cells."""
    runnable, skipped = 0, []
    for arch in ALL_ARCHS:
        cfg = get(arch)
        for shape in SHAPES.values():
            ok, why = shape_applicable(cfg, shape)
            if ok:
                runnable += 1
            else:
                skipped.append((arch, shape.name, why))
    assert runnable == 32, skipped
    long_skips = [s for s in skipped
                  if s[1] == "long_500k" and "full-attention" in s[2]]
    dec_skips = [s for s in skipped if s[0] == "hubert-xlarge"]
    assert len(long_skips) == 6        # pure full-attention archs
    assert len(dec_skips) == 2         # encoder-only: both decode shapes


def test_hlo_parser_trip_count_exact():
    """Roofline provenance: parse_hlo scales with lax.scan trip count
    (cost_analysis counts while bodies once — verified here)."""
    import jax
    import jax.numpy as jnp

    def make(L):
        w = jnp.zeros((L, 128, 128), jnp.float32)

        def f(w, x):
            def body(x, wl):
                return jnp.tanh(x @ wl), None
            y, _ = jax.lax.scan(body, x, w)
            return y.sum()
        x = jax.ShapeDtypeStruct((32, 128), jnp.float32)
        return jax.jit(f).lower(w, x).compile()

    for L in (2, 5):
        c = make(L)
        costs = parse_hlo(c.as_text())
        expect = 2 * 32 * 128 * 128 * L
        assert costs.flops == pytest.approx(expect, rel=1e-6)
        ca = c.cost_analysis()
        # rel=0.05 absorbs elementwise-op flops; a trip-count-multiplying
        # XLA would be off by ~L×, far outside this tolerance
        assert ca["flops"] == pytest.approx(2 * 32 * 128 * 128, rel=0.05), \
            "XLA started multiplying while bodies — update the roofline!"


def test_serve_driver_end_to_end():
    from repro.launch.serve import serve
    engine, records, _ = serve("qwen3-moe-235b-a22b", policy="vibe",
                            n_requests=3, qps=100.0, max_batch=2,
                            max_seq=48)
    done = [r for r in records if np.isfinite(r.finished_at)]
    assert len(done) == 3
    assert engine.stats.virtual_time > 0


class _Device:
    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def _granite_slot_sizes():
    from repro.launch.serve import _resident_bytes
    from repro.models import moe_perm_shape
    cfg = get("granite-moe-3b-a800m")
    n_moe, _ = moe_perm_shape(cfg, None, "train")
    slot_bytes = 3 * cfg.d_model * cfg.moe_d_ff * 2 * n_moe
    return cfg, slot_bytes, _resident_bytes(cfg, 8, 2048)


def test_slot_budget_fits_a_16gb_device(monkeypatch):
    """granite at published width, 8 lanes x 2048 positions, on a device
    with 16 GB free: the expanded experts (with one matrix regathered by a
    migration) plus the non-expert parameters and the KV cache fit."""
    from repro.core import default_slots_per_rank
    from repro.launch import serve as serve_mod
    cfg, slot_bytes, reserved = _granite_slot_sizes()
    limit = 16 * 10**9
    monkeypatch.setattr(serve_mod.jax, "local_devices", lambda: [
        _Device({"bytes_limit": limit, "bytes_in_use": 0})])
    budget = serve_mod.derive_slot_budget(8, cfg.n_experts, slot_bytes,
                                          reserved)
    assert budget.shape == (8,)
    assert budget.min() >= default_slots_per_rank(cfg.n_experts, 8)
    experts = int(budget.sum()) * slot_bytes
    assert experts * 4 / 3 + reserved <= limit
    # the budget the old one-layer sizing gave would not have fit
    assert 8 * int(0.8 * limit / 8 / (slot_bytes // 32)) * slot_bytes > limit


def test_slot_budget_refuses_a_device_too_small(monkeypatch):
    from repro.launch import serve as serve_mod
    cfg, slot_bytes, reserved = _granite_slot_sizes()
    monkeypatch.setattr(serve_mod.jax, "local_devices", lambda: [
        _Device({"bytes_limit": 8 * 10**9, "bytes_in_use": 0})])
    with pytest.raises(ValueError, match="do not fit"):
        serve_mod.derive_slot_budget(8, cfg.n_experts, slot_bytes, reserved)


def test_slot_budget_cpu_falls_back_to_policy_default():
    from repro.core import default_slots_per_rank
    from repro.launch.serve import derive_slot_budget
    cfg, slot_bytes, reserved = _granite_slot_sizes()
    budget = derive_slot_budget(8, cfg.n_experts, slot_bytes, reserved)
    np.testing.assert_array_equal(
        budget, np.full(8, default_slots_per_rank(cfg.n_experts, 8)))


def test_vibe_beats_eplb_on_skewed_system_e2e():
    """Paper Fig 14: on the skewed system (one device −13%), ViBE holds a
    clear SLO edge over EPLB at stress."""
    from repro.serving import (EPSimulator, SimConfig, goodput,
                               sample_requests, PAPER_SLOS)
    model = get("deepseek-v3-671b")
    wl = WORKLOADS["sonnet"]
    cluster = make_cluster(8, "skewed", d_model=model.d_model,
                           d_ff=model.moe_d_ff, experts_per_rank=32)
    perf = cluster.fit_models()
    L, E = model._n_moe_layers(), model.n_experts
    W = routing_profile(wl, L, E) * 16384 * model.top_k
    slo = PAPER_SLOS[("sonnet", "deepseek-v3-671b")]
    gps = {}
    for policy in ("eplb", "vibe"):
        pl = solve_model_placement(
            policy, W, 8, perf_models=perf if policy == "vibe" else None)
        sim = EPSimulator(model, cluster, wl,
                          SimConfig(ep_degree=8, seed=1,
                                    max_prefill_tokens=16384),
                          placement=pl)
        recs = sim.run(sample_requests(wl, 150, qps=20.0, seed=2),
                       phase="prefill")
        gps[policy] = goodput(recs, slo)
    assert gps["vibe"] >= gps["eplb"]
