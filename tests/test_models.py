"""Per-arch smoke tests (assignment requirement) + mixer correctness.

Every assigned architecture instantiates its REDUCED config and runs one
forward/train step on CPU asserting output shapes + no NaNs; decoder archs
also run prefill + decode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, EXTRA_ARCHS, get_smoke
from repro.models import (block_layout, decode_fn, init_cache, init_params,
                          loss_fn, make_moe_tables, prefill_chunk_fn,
                          prefill_fn)
from repro.models import ssm
from repro.models.flash import flash_attention, flash_decode
from repro.training import adamw_init, adamw_update


def _smoke_batch(cfg, B=2, S=16, seed=0):
    rng = np.random.default_rng(seed)
    if cfg.frontend == "audio":
        return {"feats": jnp.asarray(rng.normal(0, 1, (B, S, cfg.frontend_dim)),
                                     jnp.bfloat16),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                      jnp.int32)}
    if cfg.frontend == "vision":
        st = S - cfg.n_patches
        return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, st)),
                                      jnp.int32),
                "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, st)),
                                      jnp.int32),
                "patches": jnp.asarray(rng.normal(0, 1, (B, cfg.n_patches,
                                                         cfg.frontend_dim)),
                                       jnp.bfloat16)}
    return {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                  jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                  jnp.int32)}


@pytest.mark.parametrize(
    "arch", [pytest.param(a, marks=pytest.mark.slow)
             if a == "jamba-1.5-large-398b" else a
             for a in ALL_ARCHS + EXTRA_ARCHS])
def test_arch_smoke_train_step(arch):
    cfg = get_smoke(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    mt = make_moe_tables(cfg, None)
    batch = _smoke_batch(cfg)
    lossf = loss_fn(cfg)

    (loss, (tallies, aux)), grads = jax.value_and_grad(
        lossf, has_aux=True)(params, batch, mt)
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    gleaves = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in gleaves)
    if cfg.is_moe:
        nb, specs = block_layout(cfg)
        n_moe = nb * sum(1 for s in specs if s.ffn == "moe")
        # logical-expert counts + capacity-dropped-assignment column
        assert tallies.shape == (n_moe, cfg.n_experts + 1)
        assert (np.asarray(tallies)[:, -1] == 0).all()   # dense never drops
        # every token routed top_k times per MoE layer
        t = batch.get("tokens", batch.get("feats"))
        logical = np.asarray(tallies)[:, :cfg.n_experts]
        np.testing.assert_allclose(logical.sum(1),
                                   t.shape[0] * t.shape[1] * cfg.top_k
                                   if "tokens" in batch else logical.sum(1))
    # one optimizer step runs
    opt = adamw_init(params)
    new_params, _ = adamw_update(grads, opt, params)
    assert jax.tree.structure(new_params) == jax.tree.structure(params)


@pytest.mark.parametrize(
    "arch", [pytest.param(a, marks=pytest.mark.slow)
             if a == "jamba-1.5-large-398b" else a
             for a in ALL_ARCHS])
def test_arch_smoke_prefill_decode(arch):
    cfg = get_smoke(arch)
    if not cfg.is_decoder:
        pytest.skip("encoder-only: no decode step")
    params = init_params(cfg, jax.random.PRNGKey(0))
    mt = make_moe_tables(cfg, None)
    B, S = 2, 16
    batch = _smoke_batch(cfg, B, S)
    batch.pop("labels", None)
    logits, cache, tallies = prefill_fn(cfg)(params, batch, mt)
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits)).all()

    dcache = init_cache(cfg, B, 32)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.array([3, 7], jnp.int32)               # per-sequence positions
    lg, ncache, _ = decode_fn(cfg)(params, tok, dcache, pos, mt)
    assert lg.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(lg)).all()


def test_decode_matches_prefill_logits():
    """Teacher-forcing a prompt through decode reproduces prefill logits."""
    cfg = get_smoke("smollm-360m")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 1, 8
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)), jnp.int32)
    logits_p, _, _ = prefill_fn(cfg)(params, {"tokens": tokens}, None)
    cache = init_cache(cfg, B, S + 1)
    df = decode_fn(cfg)
    for t in range(S):
        logits_d, cache, _ = df(params, tokens[:, t:t + 1], cache,
                                jnp.full((B,), t, jnp.int32), None)
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(logits_p),
                               atol=0.75, rtol=0.05)  # bf16 path tolerance


#: arch -> (tolerance as a share of each array's largest magnitude,
#: blocks). Two blocks, so each block must find its own entry of the
#: stacked cache; jamba's mamba states cover that for recurrent states.
#: The xLSTM's recurrent step and its sequence form drift apart with depth
#: (float32: 2% of a logit at one block, 21% at two), so it runs one.
_CARRIED = {"granite-moe-3b-a800m": (1e-5, 2),
            "jamba-1.5-large-398b": (1e-5, 2), "xlstm-350m": (0.05, 1)}


def _close(a, b, tol):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    np.testing.assert_allclose(a, b, rtol=0,
                               atol=tol * max(float(np.abs(b).max()), 1.0))


@pytest.mark.parametrize("arch", sorted(_CARRIED))
def test_carried_cache_matches_prefill(arch):
    """The step programs as the engine jits them, the cache donated: a
    prompt teacher-forced through decode, and (where every mixer is
    attention) fed in chunks, leaves the logits and the cache that
    whole-prompt prefill gives, K and V stored (blocks, lanes, kv heads,
    positions, head_dim). Float32 weights and cache, so the comparison is
    of the computation and not of bfloat16 rounding."""
    tol, blocks = _CARRIED[arch]
    cfg = get_smoke(arch)
    cfg = dataclasses.replace(
        cfg, n_layers=blocks * len(block_layout(cfg)[1]))
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    mt = make_moe_tables(cfg, None)
    B, S, S_max = 2, 8, 16
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (B, S)), jnp.int32)
    logits_p, cache_p, _ = prefill_fn(cfg)(params, {"tokens": tokens}, mt)
    _, specs = block_layout(cfg)

    def check_cache(cache):
        for spec, got, want in zip(specs, cache, cache_p):
            for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
                g = np.asarray(g)
                if spec.mixer == "attn":
                    assert g.shape == (w.shape[0], B, cfg.n_kv_heads, S_max,
                                       cfg.hd)
                    assert not g[:, :, :, S:].any()    # no row past the prompt
                    g = g[:, :, :, :S]
                _close(g, w, tol)

    df = jax.jit(decode_fn(cfg), donate_argnums=(2,))
    cache = init_cache(cfg, B, S_max, dtype=jnp.float32)
    for t in range(S):
        given = jax.tree.leaves(cache)
        logits_d, cache, _ = df(params, tokens[:, t:t + 1], cache,
                                jnp.full((B,), t, jnp.int32), mt)
        assert all(a.is_deleted() for a in given)      # updated in place
    _close(logits_d, logits_p, tol)
    check_cache(cache)

    if any(s.mixer != "attn" for s in specs):
        return                        # chunked prefill refuses recurrences
    cf = jax.jit(prefill_chunk_fn(cfg), donate_argnums=(2,))
    cache, C = init_cache(cfg, B, S_max, dtype=jnp.float32), 3
    for lane in range(B):
        for off in range(0, S, C):
            n_valid = min(C, S - off)
            buf = np.zeros((1, C), np.int32)
            buf[0, :n_valid] = tokens[lane, off:off + n_valid]
            logits_c, cache, _ = cf(params, jnp.asarray(buf), cache, lane,
                                    off, n_valid, mt)
        _close(logits_c[0], logits_p[lane], tol)
    check_cache(cache)


def test_decode_writes_one_row_per_lane():
    """A decode step changes exactly one position of each lane's K and V,
    the lane's own, in every layer, and leaves every other row as it was."""
    cfg = get_smoke("granite-moe-3b-a800m")
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S_max = 3, 16
    pos = np.array([3, 0, 11])
    dirty = jax.tree.map(lambda c: jnp.asarray(np.random.default_rng(1).normal(
        size=c.shape), c.dtype), init_cache(cfg, B, S_max))
    before = [np.asarray(a) for a in jax.tree.leaves(dirty)]
    _, cache, _ = jax.jit(decode_fn(cfg), donate_argnums=(2,))(
        params, jnp.ones((B, 1), jnp.int32), dirty, jnp.asarray(pos),
        make_moe_tables(cfg, None))
    for old, new in zip(before, jax.tree.leaves(cache)):
        changed = (np.asarray(new) != old).any(axis=(2, 4))   # (nb, B, S)
        want = np.zeros_like(changed)
        want[:, np.arange(B), pos] = True
        np.testing.assert_array_equal(changed, want)


def test_gemma3_window_pattern():
    cfg = get_smoke("gemma3-4b")
    from repro.models.model import _windows
    win = _windows(cfg)
    assert win is not None
    flat = win.reshape(-1)
    assert (flat == 0).sum() == cfg.n_layers // cfg.global_every
    assert (flat[flat > 0] == cfg.window).all()


def test_jamba_block_structure():
    cfg = get_smoke("jamba-1.5-large-398b")
    nb, specs = block_layout(cfg)
    assert len(specs) == 8
    assert specs[0].mixer == "attn"
    assert all(s.mixer == "mamba" for s in specs[1:])
    assert sum(1 for s in specs if s.ffn == "moe") == 4


def test_xlstm_block_structure():
    cfg = get_smoke("xlstm-350m")
    nb, specs = block_layout(cfg)
    assert specs[0].mixer == "slstm"
    assert all(s.mixer == "mlstm" for s in specs[1:])


# -- mixer correctness: chunked/parallel forms vs step recurrence ----------

def test_mamba_chunked_equals_step():
    B, S, D = 2, 24, 32
    p = ssm.mamba_init(jax.random.PRNGKey(0), D)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)).astype(jnp.bfloat16)
    y_full, st_full = ssm.mamba_seq(p, x, chunk=8)
    st = ssm.mamba_state_init(B, D)
    ys = []
    for t in range(S):
        y, st = ssm.mamba_step(p, x[:, t:t + 1], st)
        ys.append(y)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full, np.float32),
                               np.asarray(y_step, np.float32),
                               atol=2e-2, rtol=2e-2)
    np.testing.assert_allclose(np.asarray(st_full["h"]), np.asarray(st["h"]),
                               atol=1e-4, rtol=1e-3)


def test_mlstm_chunked_equals_step():
    B, S, D, H = 2, 16, 32, 2
    p = ssm.mlstm_init(jax.random.PRNGKey(0), D, n_heads=H)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)).astype(jnp.bfloat16)
    y_full, stf = ssm.mlstm_seq(p, x, chunk=4)
    st = None
    ys = []
    for t in range(S):
        y, st = ssm.mlstm_step(p, x[:, t:t + 1], st)
        ys.append(y)
    y_step = jnp.concatenate(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_full, np.float32),
                               np.asarray(y_step, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_slstm_chunk_size_invariance():
    B, S, D, H = 2, 16, 32, 2
    p = ssm.slstm_init(jax.random.PRNGKey(0), D, n_heads=H)
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)).astype(jnp.bfloat16)
    y1, _ = ssm.slstm_seq(p, x, chunk=4)
    y2, _ = ssm.slstm_seq(p, x, chunk=16)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32),
                               atol=1e-3, rtol=1e-3)


# -- flash attention -------------------------------------------------------

def _quad_ref(q, k, v, causal, window, hd):
    S = q.shape[1]
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q, k) / np.sqrt(hd)
    qp, kp = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= kp <= qp
    if window:
        mask &= (qp - kp) < window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    return jnp.einsum("bkgqs,bskh->bqkgh", jax.nn.softmax(scores, -1), v)


@pytest.mark.parametrize("causal,window", [(True, 0), (True, 7), (False, 0)])
@pytest.mark.parametrize("chunks", [(16, 8), (64, 64), (11, 5)])
def test_flash_vs_quadratic(causal, window, chunks):
    B, S, KV, G, hd = 2, 64, 2, 3, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, KV, G, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    ref = _quad_ref(q, k, v, causal, window, hd)
    out = flash_attention(q, k, v, causal=causal,
                          window=jnp.int32(window) if window else None,
                          q_chunk=chunks[0], kv_chunk=chunks[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_per_sequence_positions():
    B, S_max, KV, G, hd = 3, 32, 2, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, KV, G, hd))
    kc = jax.random.normal(ks[1], (B, KV, S_max, hd))
    vc = jax.random.normal(ks[2], (B, KV, S_max, hd))
    pos = jnp.array([5, 17, 31])
    out = flash_decode(q, kc, vc, pos, kv_chunk=8)
    for b in range(B):
        sc = jnp.einsum("kgh,ksh->kgs", q[b], kc[b]) / np.sqrt(hd)
        sc = jnp.where((jnp.arange(S_max) <= pos[b])[None, None], sc, -1e30)
        ref = jnp.einsum("kgs,ksh->kgh", jax.nn.softmax(sc, -1), vc[b])
        np.testing.assert_allclose(np.asarray(out[b]), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)
