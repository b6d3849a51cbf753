"""Ragged (dropless) MoE dispatch coverage (ISSUE 4 acceptance gates).

Four layers of contract, mirroring the capacity suite's structure:

* **kernel** — ``ragged_moe_ffn_pallas`` (interpret mode) against the
  pure-jnp ``ragged_moe_ffn_ref`` oracle and against per-expert
  ``moe_ffn_ref`` rows; tile metadata invariants; empty experts own no
  tiles and unoccupied tiles emit zeros.
* **plan** — the sort-based ``_bucket_positions`` is bit-identical to the
  historical one-hot/cumsum build (stable sort == arrival order), active
  mask included.
* **dispatch** — property tests: the ragged path equals the dense oracle
  for *any* routing (no drop column — ``tally[E] == 0`` structurally),
  and equals the capacity path wherever capacity does not drop; where
  capacity *does* drop, ragged still equals the full oracle.
* **bodies** — the real ``shard_map`` a2a/replicated ragged bodies run
  in-process on a 1-device mesh (fast-lane coverage like
  ``test_capacity_overflow``), gradients included.

Plus the vectorized weight-migration builds (``placement_gather_indices``,
``expand_experts``) pinned bit-identical to their old pure-Python loops.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.launch.mesh import make_mesh
from repro.kernels.ragged_moe_ffn import (ragged_moe_ffn_pallas,
                                          ragged_n_tiles,
                                          ragged_tile_metadata)
from repro.kernels.ref import moe_ffn_ref, ragged_moe_ffn_ref
from repro.models import moe as MOE
from repro.models.sharding import ShardingRules

E, D, F, K = 4, 16, 64, 2
B, S = 2, 16


# ---------------------------------------------------------------------------
# kernel level
# ---------------------------------------------------------------------------

def _ragged_buffer(rng, sizes, bm, D, dtype=np.float32):
    """Zero-padded group-sorted buffer + metadata for given segment sizes."""
    sizes = np.asarray(sizes, np.int32)
    A = int(sizes.sum())
    nt = ragged_n_tiles(A, len(sizes), bm)
    row_off, tg = ragged_tile_metadata(jnp.asarray(sizes), bm, nt)
    off = np.asarray(row_off)
    buf = np.zeros((nt * bm, D), dtype)
    for g, s in enumerate(sizes):
        buf[off[g]:off[g] + s] = rng.standard_normal((s, D)).astype(dtype)
    return jnp.asarray(buf), tg, off


@pytest.mark.parametrize("sizes,bm", [
    ((5, 0, 17, 3), 8),        # empty expert in the middle
    ((0, 0, 0, 40), 16),       # all load on one expert
    ((1, 1, 1, 1), 8),         # minimum occupancy
    ((32, 32, 32, 32), 32),    # exactly tile-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ragged_kernel_matches_ref(sizes, bm, dtype):
    rng = np.random.default_rng(sum(sizes) + bm)
    buf, tg, _ = _ragged_buffer(rng, sizes, bm, D,
                                np.float32 if dtype == jnp.float32
                                else np.float32)
    buf = buf.astype(dtype)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    w1 = (jax.random.normal(ks[0], (E, D, F)) / np.sqrt(D)).astype(dtype)
    w3 = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(dtype)
    w2 = (jax.random.normal(ks[2], (E, F, D)) / np.sqrt(F)).astype(dtype)
    y_ref = np.asarray(ragged_moe_ffn_ref(w1, w3, w2, buf, tg), np.float32)
    y_k = np.asarray(ragged_moe_ffn_pallas(w1, w3, w2, buf, tg, bf=32,
                                           interpret=True), np.float32)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(y_k, y_ref, atol=tol, rtol=tol)


def test_ragged_ref_matches_dense_oracle_per_expert():
    """Each occupied segment equals the capacity oracle run on its rows."""
    rng = np.random.default_rng(3)
    sizes = (7, 0, 12, 2)
    bm = 8
    buf, tg, off = _ragged_buffer(rng, sizes, bm, D)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    w1 = (jax.random.normal(ks[0], (E, D, F)) / np.sqrt(D))
    w3 = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D))
    w2 = (jax.random.normal(ks[2], (E, F, D)) / np.sqrt(F))
    y = np.asarray(ragged_moe_ffn_ref(w1, w3, w2, buf, tg))
    for g, s in enumerate(sizes):
        if s == 0:
            continue
        rows = jnp.asarray(np.asarray(buf)[off[g]:off[g] + s])
        y_d = np.asarray(moe_ffn_ref(w1[g:g + 1], w3[g:g + 1], w2[g:g + 1],
                                     rows[None]))[0]
        np.testing.assert_allclose(y[off[g]:off[g] + s], y_d,
                                   rtol=1e-6, atol=1e-6)


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_tile_metadata_invariants(seed):
    rng = np.random.default_rng(seed)
    G = int(rng.integers(1, 9))
    bm = int(2 ** rng.integers(0, 6))
    sizes = rng.integers(0, 40, size=G).astype(np.int32)
    A = int(sizes.sum())
    nt = ragged_n_tiles(A, G, bm)
    row_off, tg = ragged_tile_metadata(jnp.asarray(sizes), bm, nt)
    row_off, tg = np.asarray(row_off), np.asarray(tg)
    # segment starts are tile-aligned; total occupied rows bounded by n_rows
    assert (row_off % bm == 0).all()
    assert row_off[-1] <= nt * bm
    # each group owns exactly ceil(size/bm) tiles, contiguous and in order
    want_tiles = -(-sizes // bm)
    counts = np.bincount(tg[tg < G], minlength=G)
    np.testing.assert_array_equal(counts, want_tiles)
    assert (np.diff(tg) >= 0).all()                  # grouped + sorted
    # everything past the occupied prefix is sentinel
    assert (tg[int(want_tiles.sum()):] == G).all()


def test_ragged_kernel_unoccupied_tiles_zero():
    rng = np.random.default_rng(0)
    buf, tg, off = _ragged_buffer(rng, (3, 0, 5, 0), 8, D)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    w1 = (jax.random.normal(ks[0], (E, D, F)) / np.sqrt(D))
    w3 = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D))
    w2 = (jax.random.normal(ks[2], (E, F, D)) / np.sqrt(F))
    y = np.asarray(ragged_moe_ffn_pallas(w1, w3, w2, buf, tg, bf=32,
                                         interpret=True))
    occupied = np.zeros(y.shape[0], bool)
    for g, s in zip(range(E), (3, 0, 5, 0)):
        occupied[off[g]:off[g] + s] = True
    assert np.abs(y[~occupied]).max() == 0.0
    assert np.abs(y[occupied]).max() > 0.0


# ---------------------------------------------------------------------------
# plan level: sort-based bucketing == historical one-hot/cumsum
# ---------------------------------------------------------------------------

def _bucket_positions_onehot(slot_flat, n_slots, active=None):
    """The pre-ISSUE-4 O(A × n_slots) build, kept as the reference."""
    oh = jax.nn.one_hot(jnp.asarray(slot_flat), n_slots, dtype=jnp.int32)
    if active is not None:
        oh = oh * jnp.asarray(active).astype(jnp.int32)[:, None]
    pos = jnp.cumsum(oh, axis=0) - 1
    return jnp.take_along_axis(pos, jnp.asarray(slot_flat)[:, None],
                               axis=1)[:, 0]


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_sorted_bucket_positions_bit_identical(seed):
    rng = np.random.default_rng(seed)
    n_slots = int(rng.integers(1, 12))
    A = int(rng.integers(1, 200))
    slot = rng.integers(0, n_slots, size=A).astype(np.int32)
    active = rng.random(A) < 0.7
    new = np.asarray(MOE._bucket_positions(jnp.asarray(slot), n_slots))
    old = np.asarray(_bucket_positions_onehot(slot, n_slots))
    np.testing.assert_array_equal(new, old)
    # with a mask, only active positions are defined (callers mask the rest)
    new_m = np.asarray(MOE._bucket_positions(jnp.asarray(slot), n_slots,
                                             jnp.asarray(active)))
    old_m = np.asarray(_bucket_positions_onehot(slot, n_slots, active))
    np.testing.assert_array_equal(new_m[active], old_m[active])


# ---------------------------------------------------------------------------
# dispatch level
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def setup():
    p = MOE.moe_init(jax.random.PRNGKey(0), d=D, f=F, n_experts=E, n_slots=E)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)) \
        .astype(jnp.bfloat16)
    mesh = make_mesh((1,), ("model",))
    return p, x, mesh


def _run(p, x, mesh, *, dispatch, impl, cf, phase, top_k=K, bm=8):
    rules = ShardingRules(mesh=mesh, dp=(), ep=("model",), ep_all=("model",),
                          fsdp=None, moe_dispatch=dispatch,
                          capacity_factor=cf, moe_impl=impl, moe_block_m=bm)
    with jax.set_mesh(mesh):
        y, tally, _ = jax.jit(lambda p, x: MOE.moe_layer(
            p, x, top_k=top_k, n_experts=E, rules=rules, phase=phase))(p, x)
    return np.asarray(y, np.float32), np.asarray(tally)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_ragged_dense_equals_oracle(seed):
    """Ragged == dense oracle for any routing, with a structurally zero
    drop column — the dropless contract (no mesh needed: the ragged dense
    dispatch runs whenever rules carry moe_impl='ragged')."""
    rng = np.random.default_rng(seed)
    t = int(rng.integers(1, 40))
    top_k = int(rng.integers(1, E + 1))
    p = MOE.moe_init(jax.random.PRNGKey(seed), d=D, f=F, n_experts=E,
                     n_slots=E)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, t, D),
                          jnp.float32)
    y_ref, t_ref, a_ref = MOE.moe_layer(p, x, top_k=top_k, n_experts=E,
                                        rules=None)
    rules = ShardingRules(mesh=None, moe_impl="ragged", moe_block_m=8)
    y, tally, aux = MOE.moe_layer(p, x, top_k=top_k, n_experts=E,
                                  rules=rules)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(tally), np.asarray(t_ref))
    assert float(tally[E]) == 0.0
    np.testing.assert_allclose(float(aux), float(a_ref), rtol=1e-6)


def test_ragged_equals_capacity_when_no_drops(setup):
    """Wherever the capacity path does not drop, both implementations are
    the same function (modulo summation order ≤ 1 bf16 ULP)."""
    p, x, mesh = setup
    for dispatch, phase in (("a2a", "train"), ("replicated", "decode")):
        y_c, t_c = _run(p, x, mesh, dispatch=dispatch, impl="capacity",
                        cf=8.0, phase=phase)
        y_r, t_r = _run(p, x, mesh, dispatch=dispatch, impl="ragged",
                        cf=8.0, phase=phase)
        assert t_c[-1] == 0, "fixture unexpectedly dropped"
        np.testing.assert_array_equal(t_c, t_r)
        np.testing.assert_allclose(y_r, y_c, atol=1e-3, rtol=1e-3)


def test_ragged_dropless_where_capacity_drops(setup):
    """At a starved capacity factor the capacity path drops; the ragged
    path keeps every assignment and still equals the full dense oracle."""
    p, x, mesh = setup
    y_ref, t_ref, _ = MOE.moe_layer(p, x, top_k=K, n_experts=E, rules=None)
    y_c, t_c = _run(p, x, mesh, dispatch="a2a", impl="capacity", cf=0.25,
                    phase="train")
    assert t_c[-1] > 0, "fixture failed to overflow any bucket"
    y_r, t_r = _run(p, x, mesh, dispatch="a2a", impl="ragged", cf=0.25,
                    phase="train")
    assert t_r[-1] == 0
    np.testing.assert_allclose(y_r, np.asarray(y_ref, np.float32),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_array_equal(t_r[:E], np.asarray(t_ref)[:E])
    # same on the decode path (replicated body, local buckets)
    y_rr, t_rr = _run(p, x, mesh, dispatch="replicated", impl="ragged",
                      cf=0.25, phase="decode")
    assert t_rr[-1] == 0
    np.testing.assert_allclose(y_rr, np.asarray(y_ref, np.float32),
                               atol=1e-3, rtol=1e-3)


def test_ragged_gradients_flow(setup):
    """The sort/scatter/gather pipeline is differentiable end to end."""
    p, x, mesh = setup
    rules = ShardingRules(mesh=mesh, dp=(), ep=("model",), fsdp=None,
                          moe_dispatch="a2a", moe_impl="ragged",
                          moe_block_m=8)

    def loss(p, x):
        y, _, a = MOE.moe_layer(p, x, top_k=K, n_experts=E, rules=rules,
                                phase="train")
        return (y.astype(jnp.float32) ** 2).mean() + 0.01 * a

    with jax.set_mesh(mesh):
        g = jax.jit(jax.grad(loss))(p, x)
    for k, v in g.items():
        assert float(jnp.linalg.norm(v.astype(jnp.float32))) > 0, k


def test_ragged_weighted_replica_routing(setup):
    """copy_cdf share-weighted replica selection rides the ragged path:
    replicated slots + skewed shares still reproduce the dense oracle."""
    p, x, mesh = setup
    from repro.models.sharding import build_copy_cdf, build_slots_of
    ns = E + 2
    perm = np.concatenate([np.arange(E), [0, 1]])[None, :].astype(np.int32)
    p_rep = {k: (v if k == "router" else v[perm[0]]) for k, v in p.items()}
    share = np.ones((1, ns))
    share[0, :2] = 0.3
    share[0, E:] = 0.7
    so, nc = build_slots_of(perm, E, ns)
    cdf = build_copy_cdf(perm, E, ns, share=share)
    y_ref, t_ref, _ = MOE.moe_layer(p, x, top_k=K, n_experts=E, rules=None)
    rules = ShardingRules(mesh=mesh, dp=(), ep=("model",), fsdp=None,
                          moe_dispatch="a2a", moe_impl="ragged",
                          moe_block_m=8)
    with jax.set_mesh(mesh):
        y, tally, _ = jax.jit(lambda pp, xx: MOE.moe_layer(
            pp, xx, top_k=K, n_experts=E, rules=rules,
            slots_of=jnp.asarray(so[0]), n_copies=jnp.asarray(nc[0]),
            copy_cdf=jnp.asarray(cdf[0]), phase="train"))(p_rep, x)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_array_equal(np.asarray(tally), np.asarray(t_ref))


# ---------------------------------------------------------------------------
# vectorized weight-migration builds == historical Python loops
# ---------------------------------------------------------------------------

def _gather_indices_loop(old_perm, new_perm):
    """Pre-ISSUE-4 pure-Python build, kept as the bit-identity reference."""
    old_perm = np.atleast_2d(old_perm)
    new_perm = np.atleast_2d(new_perm)
    L, NS = old_perm.shape
    idx = np.empty((L, NS), dtype=np.int32)
    for l in range(L):
        inv = np.full(max(int(old_perm.max()), int(new_perm.max())) + 1, -1,
                      dtype=np.int32)
        for q in range(NS):
            if inv[old_perm[l, q]] < 0:
                inv[old_perm[l, q]] = q
        for pslot in range(NS):
            src = inv[new_perm[l, pslot]]
            idx[l, pslot] = src if src >= 0 else pslot
    return idx


def _expand_gi_loop(perm_a2a, perm_dec):
    L, ns_dec = np.atleast_2d(perm_dec).shape
    perm_a2a = np.atleast_2d(perm_a2a)
    perm_dec = np.atleast_2d(perm_dec)
    gi = np.empty((L, ns_dec), dtype=np.int32)
    for l in range(L):
        inv = {int(e): q for q, e in reversed(list(enumerate(perm_a2a[l])))}
        for pslot in range(ns_dec):
            gi[l, pslot] = inv[int(perm_dec[l, pslot])]
    return gi


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_gather_indices_bit_identical(seed):
    """Vectorized placement_gather_indices == the old per-slot scan, on
    permutations with replicas (repeated ids) and phantom padding."""
    rng = np.random.default_rng(seed)
    L = int(rng.integers(1, 4))
    n_exp = int(rng.integers(2, 10))
    NS = int(rng.integers(n_exp, n_exp + 6))
    def perm():
        base = np.arange(n_exp, dtype=np.int32)
        extra = rng.integers(0, n_exp + 2, size=NS - n_exp).astype(np.int32)
        rows = [rng.permutation(np.concatenate([base, extra]))
                for _ in range(L)]
        return np.stack(rows)
    old, new = perm(), perm()
    np.testing.assert_array_equal(
        MOE.placement_gather_indices(old, new),
        _gather_indices_loop(old, new))


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_expand_experts_bit_identical(seed):
    rng = np.random.default_rng(seed)
    L = int(rng.integers(1, 4))
    n_exp = int(rng.integers(2, 8))
    ns_a2a = n_exp + int(rng.integers(0, 4))
    ns_dec = int(rng.integers(1, 3)) * ns_a2a
    perm_a2a = np.stack([
        rng.permutation(np.concatenate(
            [np.arange(n_exp), rng.integers(0, n_exp, size=ns_a2a - n_exp)]
        ).astype(np.int32)) for _ in range(L)])
    perm_dec = rng.integers(0, n_exp, size=(L, ns_dec)).astype(np.int32)
    w = {k: jnp.asarray(rng.standard_normal((L, ns_a2a, 2, 3)),
                        jnp.float32) for k in ("w1", "w2", "w3")}
    got = MOE.expand_experts(w, perm_a2a, perm_dec)
    gi = _expand_gi_loop(perm_a2a, perm_dec)
    for k in ("w1", "w2", "w3"):
        want = np.take_along_axis(np.asarray(w[k]), gi[:, :, None, None],
                                  axis=1)
        np.testing.assert_array_equal(np.asarray(got[k]), want)


def test_expand_experts_missing_expert_raises():
    w = {"w1": jnp.zeros((1, 2, 2, 2))}
    with pytest.raises(KeyError):
        MOE.expand_experts(w, np.array([[0, 1]]), np.array([[0, 3]]))
