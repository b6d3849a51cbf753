"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref
from repro.kernels.moe_ffn import fused_moe_ffn_pallas
from repro.kernels.router import router_topk_pallas


def _rand_ffn(key, E, C, D, F, dtype):
    ks = jax.random.split(key, 4)
    toks = jax.random.normal(ks[0], (E, C, D)).astype(dtype)
    w1 = (jax.random.normal(ks[1], (E, D, F)) / np.sqrt(D)).astype(dtype)
    w3 = (jax.random.normal(ks[2], (E, D, F)) / np.sqrt(D)).astype(dtype)
    w2 = (jax.random.normal(ks[3], (E, F, D)) / np.sqrt(F)).astype(dtype)
    return toks, w1, w3, w2


SHAPES = [
    (1, 8, 64, 128),      # single expert
    (4, 64, 128, 256),    # aligned
    (2, 100, 96, 192),    # unaligned C (pad path)
    (8, 16, 256, 512),    # many experts, small capacity
    (3, 33, 160, 130),    # everything unaligned
]


@pytest.mark.parametrize("E,C,D,F", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_moe_ffn_shape_dtype_sweep(E, C, D, F, dtype):
    toks, w1, w3, w2 = _rand_ffn(jax.random.PRNGKey(E * 7 + C), E, C, D, F,
                                 dtype)
    y_ref = np.asarray(ref.moe_ffn_ref(w1, w3, w2, toks), np.float32)
    y = np.asarray(fused_moe_ffn_pallas(w1, w3, w2, toks, bm=32, bf=64,
                                        interpret=True), np.float32)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(y, y_ref, atol=tol, rtol=tol)


def test_moe_ffn_zero_rows_stay_zero():
    """Capacity-bucket semantics: padded rows in, zeros out."""
    toks, w1, w3, w2 = _rand_ffn(jax.random.PRNGKey(0), 2, 16, 64, 128,
                                 jnp.bfloat16)
    toks = toks.at[:, 8:].set(0)
    y = np.asarray(fused_moe_ffn_pallas(w1, w3, w2, toks, interpret=True))
    assert np.abs(y[:, 8:]).max() == 0.0


def test_moe_ffn_block_size_invariance():
    toks, w1, w3, w2 = _rand_ffn(jax.random.PRNGKey(1), 2, 64, 128, 256,
                                 jnp.float32)
    outs = [np.asarray(fused_moe_ffn_pallas(w1, w3, w2, toks, bm=bm, bf=bf,
                                            interpret=True))
            for bm, bf in [(16, 64), (64, 128), (64, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("T,E,K", [(64, 16, 4), (100, 8, 2), (300, 128, 8),
                                   (7, 4, 1), (513, 40, 8)])
def test_router_topk_sweep(T, E, K):
    logits = jax.random.normal(jax.random.PRNGKey(T + E), (T, E),
                               jnp.float32)
    w_ref, i_ref = ref.router_topk_ref(logits, K)
    w, i = router_topk_pallas(logits, K, bt=64, interpret=True)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_allclose(np.asarray(w), np.asarray(w_ref),
                               rtol=1e-5, atol=1e-6)


def test_router_weights_normalized():
    logits = jax.random.normal(jax.random.PRNGKey(9), (50, 32))
    w, _ = ops.router_topk(logits, 4)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-5)


@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 10_000))
def test_property_moe_ffn_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    E = int(rng.integers(1, 5))
    C = int(rng.integers(4, 48))
    D = int(rng.integers(1, 5)) * 32
    F = int(rng.integers(1, 5)) * 32
    toks, w1, w3, w2 = _rand_ffn(jax.random.PRNGKey(seed), E, C, D, F,
                                 jnp.float32)
    y_ref = np.asarray(ref.moe_ffn_ref(w1, w3, w2, toks))
    y = np.asarray(fused_moe_ffn_pallas(w1, w3, w2, toks, bm=16, bf=32,
                                        interpret=True))
    np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)


def test_ops_wrapper_picks_valid_blocks():
    bm, bf = ops.pick_blocks(8192, 24576)
    # double-buffered x / output / weight blocks + f32 scratch
    resident = (2 * 2 * bm * 8192 * 2 + 2 * 3 * 8192 * bf * 2
                + bm * 8192 * 4 + 3 * bm * bf * 4)
    assert ops.vmem_bytes(bm, bf, 8192) == resident
    assert resident <= ops._TILE_BUDGET < ops._VMEM_LIMIT
    assert bm % 128 == 0 and bf % 128 == 0
    # a pinned row tile (the ragged kernel's) is kept; one that cannot fit
    # raises instead of falling back to an unchecked tile
    assert ops.pick_blocks(8192, 24576, bm=128)[0] == 128
    with pytest.raises(ValueError, match="no grouped-FFN block fits"):
        ops.pick_blocks(8192, 24576, bm=512)


def test_kernel_is_dispatch_compatible():
    """ops.fused_moe_ffn drops into the EP dispatch's ffn slot."""
    from repro.models.moe import expert_ffn_ref
    toks, w1, w3, w2 = _rand_ffn(jax.random.PRNGKey(3), 2, 32, 64, 128,
                                 jnp.bfloat16)
    a = np.asarray(expert_ffn_ref(w1, w3, w2, toks), np.float32)
    b = np.asarray(ops.fused_moe_ffn(w1, w3, w2, toks), np.float32)
    np.testing.assert_allclose(a, b, atol=5e-2, rtol=5e-2)
